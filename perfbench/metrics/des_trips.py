"""DES on the device (`core/des_jax.py` `batch_genomes`): mean trips of the
vmapped event loop per fitness batch, the `trips` attr (the slowest
lane's count, which every lane runs) of the `des.simulate` spans with
entry=batch_genomes."""
from perfbench.harness.spans import mean, select


def read(ctx):
    return mean(s.attrs["trips"] for s in select(
        ctx.spans, "des.simulate", entry="batch_genomes")
        if "trips" in s.attrs)
