"""The work a step needs, computed from shapes, and the chip's peaks.

These are the yardstick of every roofline and utilization share: what the
algorithm needs, not what an implementation happens to do.
"""
from __future__ import annotations

import json
import os
from typing import Dict

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown device is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in {PEAKS}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


# ------------------------------------------------------------ vector pool
def extend_bytes(tasks: int, dim: int, itemsize: int = 4) -> int:
    """Bytes an extend step must read for ``tasks`` distance tasks: one
    corpus row of ``dim`` float32 each (graph rows, queries and the search
    state are small beside them and not counted, so this bounds below)."""
    return int(tasks) * int(dim) * itemsize


def extend_flops(tasks: int, dim: int) -> int:
    """Squared L2: a subtract, a multiply and an add per element."""
    return 3 * int(tasks) * int(dim)


def least_seconds(flops: float, nbytes: float, peak: Dict[str, float]):
    """(seconds, bound): the least time the chip needs for the work, and
    which of compute or memory bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_m, "bytes") if t_m >= t_c else (t_c, "flops")

