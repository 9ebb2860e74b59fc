"""Operation and byte counts from shapes, and the table of peaks.

Counts are of the operations the algorithm REQUIRES: recomputation,
padding and masked-out halves are not counted, so a share of peak built
on them cannot be raised by doing more work."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unlisted kind is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} is not in roofline/peaks.json")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, which bound applies)."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
