"""DAG build (`core/schedule.py` `reduce_dag`): seconds per request of the
`dag.prune` span, the dominance pruning of the reduced DAG's candidate
dependencies, inside the request's DAG build."""
from perfbench.harness.request_spans import per_request
from perfbench.harness.spans import total


def read(ctx):
    return per_request(ctx, "dag.prune", total, dag=True)
