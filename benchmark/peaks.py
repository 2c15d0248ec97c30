"""The one table of device peaks, keyed by JAX's `device_kind`."""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def lookup(device_kind: str) -> dict:
    """Peaks of `device_kind`; a device that is not in the table is an
    error, never a default."""
    with open(_PATH, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {_PATH}: add its "
            "published numbers with their source before reporting a share "
            "of a peak on it"
        )
    return table[device_kind]
