"""API (`core/api.py` `plan`): seconds per request of the `plan` span less
the union of the intervals of the spans inside it (the `DESProblem`
builds, the GA's set-up outside its spans, the result's assembly)."""
from perfbench.harness.request_spans import per_request, self_time


def read(ctx):
    return per_request(ctx, "plan", lambda spans: sum(
        self_time(s, ctx.spans) for s in spans))
