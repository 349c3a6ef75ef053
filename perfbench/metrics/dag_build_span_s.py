"""DAG build (`core/schedule.py` `build_comm_dag`): seconds per request of
its `dag.build` span."""
from perfbench.harness.request_spans import per_request
from perfbench.harness.spans import total


def read(ctx):
    return per_request(ctx, "dag.build", total, dag=True)
