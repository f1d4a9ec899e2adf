"""Bytes the cache algorithm behind TinyLFU admission must move per
request, from the geometry.

As ``bytes_model`` counts the flat cache's algorithm and not an
implementation, this counts what admission adds to it, a request at a
time:

* the flat cache's bytes (``bytes_model.request_bytes``): the set probed,
  read whole, and one entry written, 180 B at 8 ways;
* ``record``: the key's counter word in each of the 4 sketch rows and its
  doorkeeper word, each read and written once: 5 words x 4 B x 2 = 40 B;
* ``admit``: the victim's estimate reads its 4 counter words and its
  doorkeeper word, 20 B.  The candidate's estimate reads the words its
  own ``record`` just wrote, and the victim is way 0 of the set the probe
  already read, so neither adds bytes.

That is 240 B a request at 8 ways.  The sketch's aging pass (every counter
word halved and every door word cleared once per ``sample`` requests) adds
about 0.5 B a request at the program's sizing (``for_capacity``: 4.25 MB
read or written once per 8 x capacity requests) and is left out.
"""
from __future__ import annotations

from bench import bytes_model

ROWS = 4
WORD_BYTES = 4


def record_bytes() -> int:
    return (ROWS + 1) * WORD_BYTES * 2


def admit_bytes() -> int:
    return (ROWS + 1) * WORD_BYTES


def request_bytes(conf: dict) -> int:
    """Bytes per request of a flat cache of ``conf["ways"]`` ways behind
    TinyLFU admission."""
    return bytes_model.request_bytes(conf) + record_bytes() + admit_bytes()
