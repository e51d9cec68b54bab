"""The chips' published peaks (`peaks.json`), by JAX's device kind. A
device missing from the table is an error, never a default."""

from __future__ import annotations

import json
import os

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "peaks.json")) as _f:
    DEVICES: dict = json.load(_f)["devices"]


def peak(kind: str, key: str) -> float:
    if kind not in DEVICES:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return DEVICES[kind][key]
