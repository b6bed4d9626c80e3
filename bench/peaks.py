"""The chip's published peaks, from `peaks.json`, keyed by `device_kind`.
A device that is not in the table is an error, not a default."""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PATH}; have {sorted(table)}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, device_kind: str
                  ) -> tuple[float, str]:
    """The least time the chip needs for `flops` operations and `nbytes`
    of HBM traffic, and which bound sets it ('compute' or 'memory')."""
    p = peaks(device_kind)
    t_c = flops / p["flops_per_s"]
    t_m = nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
