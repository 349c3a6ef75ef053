"""GA loop on the host (`core/ga.py` `_evolve`): seconds per request of
the `ga.generation` spans outside the `des.simulate` spans inside them
(selection, variation, repair, dedup and the fitness cache)."""
from perfbench.harness.spans import mean, select, total


def read(ctx):
    out = []
    for r in ctx.loop.requests:
        gens = select(ctx.spans, "ga.generation", r["t_plan"], r["t1"])
        out.append(sum(g.dur - total(select(ctx.spans, "des.simulate",
                                            g.t0, g.t0 + g.dur))
                       for g in gens))
    return mean(out)
