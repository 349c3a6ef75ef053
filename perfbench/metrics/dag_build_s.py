"""DAG build (`core/schedule.py` `build_comm_dag`): host seconds per
request, by the benchmark's clock around the call."""
from perfbench.harness.spans import mean


def read(ctx):
    return mean(r["dag_s"] for r in ctx.loop.requests)
