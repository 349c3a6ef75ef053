"""Exact oracle (`core/des.py` `simulate`, the float64 numpy DES): seconds
per request of the `des.exact` spans inside `plan()` (the ideal, the
re-rank of the best genomes and the final plan's simulation)."""
from perfbench.harness.request_spans import per_request
from perfbench.harness.spans import total


def read(ctx):
    return per_request(ctx, "des.exact", total)
