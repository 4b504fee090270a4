"""Model FLOPs of a training step, and the chip's peaks.

A step of the importance-sampling job runs the forward and backward
passes of the b trained rows and the forward pass of the B = ratio·b
pool rows it scores; a uniform step only the first. The backward pass
counts twice the forward; work the program recomputes (rematerialised
forwards) is not counted. A row's forward FLOPs are summed from each
block kind's count (``refs/<kind>.py``: ``forward_flops``) and the
logits.
"""
from __future__ import annotations

import json
from pathlib import Path

from chipbench import reference

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def step_flops(cfg, tr):
    b, T = int(tr["global_batch"]), int(tr["seq_len"])
    pool = b * int(tr["presample_ratio"]) if tr["scheme"] == "presample" \
        else 0
    return (3 * b + pool) * reference.flops_per_row(cfg, T)


def peak(device_kind, what="bf16_flops"):
    """The table's peak for this device; a device not in the table is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return float(table[device_kind][what])
