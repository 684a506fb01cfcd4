"""The chip's published peaks, keyed by JAX's ``device_kind``.

``peaks.json`` beside this package is the only table; a device that is
not in it is an error, never a default.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict

TABLE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, table: str = TABLE) -> Dict[str, Any]:
    with open(table) as f:
        known = json.load(f)
    if device_kind not in known:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r} in {table}; "
            f"known: {sorted(known)}")
    return known[device_kind]
