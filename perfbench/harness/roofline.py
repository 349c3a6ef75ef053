"""Peaks of each chip and the work a kernel needs, for roofline shares.

A kernel's roofline share is the least time the chip could take for the
work the algorithm needs (the larger of operations over peak operations
per second and bytes over peak memory bandwidth) over the kernel's device
time from the trace.  The work is counted from the problem's real sizes,
not the padded ones the program runs, so padding shows as lost share.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peaks of `device_kind` (as JAX names it); a chip that is not in
    the table is an error, not a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"have {sorted(table)}")
    return table[device_kind]


def fill_round_work(cons: int, tasks: int, batch: int) -> tuple[float,
                                                                float]:
    """Operations and bytes one `fill_round` call needs for `batch`
    simulations of one DAG: two matrix-vector products per simulation
    (used and unfrozen share of every constraint) against the shared
    float32 (cons, tasks) incidence matrix, which has to be read once, and
    the two float32 vectors in and out per simulation."""
    flops = 2.0 * 2 * cons * tasks * batch
    nbytes = 4.0 * (cons * tasks + 2 * tasks * batch + 2 * cons * batch)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict
                  ) -> tuple[float, str]:
    """The least time for the work, and which bound sets it."""
    t_ops = flops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
