"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

The table is ``peaks.json``; a device that is not in it is an error, never a
default, so a roofline share is always taken against the chip it ran on.
"""
from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


class UnknownDevice(KeyError):
    """The device kind has no row in the peak table."""


def peaks(device_kind: str, table: Path = _TABLE) -> dict:
    rows = json.loads(table.read_text())
    if device_kind not in rows:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; known: {sorted(rows)}")
    return rows[device_kind]
