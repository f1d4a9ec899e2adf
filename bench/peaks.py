"""The chip's published peaks, keyed by JAX's ``device_kind``
(``peaks.json``, with its source).  A device that is not in the table is
an error: a share of a guessed peak is no measurement."""
from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


def peak(device_kind: str) -> dict:
    devices = json.loads(_TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {_TABLE.name}; known: {sorted(devices)}")
    return devices[device_kind]
