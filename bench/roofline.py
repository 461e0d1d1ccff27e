"""Least work of a solve, and the table of device peaks."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``; an unknown device
    is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def batch_least_bytes(n: int, arcs: int, rows: int) -> int:
    """Bytes that any exact solve of ``rows`` full rows must move at
    least: the CSR input read once (int32 ``indptr``, int32 sources and
    f32 weights of every arc) and each f32 row written once."""
    return 4 * (n + 1) + 8 * arcs + 4 * rows * n
