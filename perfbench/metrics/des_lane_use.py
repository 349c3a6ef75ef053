"""DES on the device (`core/des_jax.py` `batch_genomes`): share of the
vmapped event loop's lane trips that did work, 100 x the lanes' own trips
(`lane_trips`) over population x the slowest lane's trips (`pop` x
`trips`), summed over the `des.simulate` spans with entry=batch_genomes."""
from perfbench.harness.spans import select


def read(ctx):
    spans = [s for s in select(ctx.spans, "des.simulate",
                               entry="batch_genomes") if "trips" in s.attrs]
    run = sum(s.attrs["pop"] * s.attrs["trips"] for s in spans)
    if run == 0:
        return None
    return 100.0 * sum(s.attrs["lane_trips"] for s in spans) / run
