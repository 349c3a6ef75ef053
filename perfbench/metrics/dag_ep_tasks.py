"""DAG build (`core/schedule.py` `build_comm_dag`): expert-parallel
all-to-all tasks per request, the `ep_tasks` attr of the request's
`dag.build` span; nothing where the span does not carry it."""
from perfbench.harness.spans import mean, select


def read(ctx):
    return mean(s.attrs["ep_tasks"] for r in ctx.loop.requests
                for s in select(ctx.spans, "dag.build", r["t0"],
                                r["t_plan"])
                if "ep_tasks" in s.attrs)
