"""Exact oracle (`core/des.py` `simulate`): `des.exact` spans per request,
one per float64 simulation inside `plan()`."""
from perfbench.harness.request_spans import per_request


def read(ctx):
    return per_request(ctx, "des.exact", len)
