"""DES on the device (`core/des_jax.py` `batch_genomes`): mean seconds of
one fitness batch, the `des.simulate` span with entry=batch_genomes, which
ends when the makespans are back on the host."""
from perfbench.harness.spans import mean, select


def read(ctx):
    return mean(s.dur for s in select(ctx.spans, "des.simulate",
                                      entry="batch_genomes"))
