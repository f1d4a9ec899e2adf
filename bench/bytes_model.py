"""Bytes the cache algorithm must move per request, from the geometry.

A roofline share counts the algorithm's bytes, not an implementation's:
for each request, the one set it must probe, read whole (its ways times
the five 4-byte lanes: key, fingerprint, value and two policy counters),
plus one entry written.  Block shapes, padding and DMA sizes of any kernel
do not enter, so the yardstick reads the same work whatever implements it.
"""
from __future__ import annotations

LANES = 5          # key, fingerprint, value, meta_a, meta_b
LANE_BYTES = 4


def set_bytes(ways: int) -> int:
    return ways * LANES * LANE_BYTES


def entry_bytes() -> int:
    return LANES * LANE_BYTES


def request_bytes(conf: dict) -> int:
    """Bytes per request of a flat cache of ``conf["ways"]`` ways."""
    return set_bytes(int(conf["ways"])) + entry_bytes()
