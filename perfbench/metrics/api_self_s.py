"""API outside the GA (`core/api.py` `_plan_dag`: the numpy ideal, the
exact re-rank of the best genomes and `_from_des`): seconds per request of
`plan()` outside its `ga.evolve` span."""
from perfbench.harness.spans import mean, select, total


def read(ctx):
    return mean(r["plan_s"] - total(select(ctx.spans, "ga.evolve",
                                           r["t_plan"], r["t1"]))
                for r in ctx.loop.requests)
