"""`kernels/waterfill.py` `fill_round`: share of its roofline.  The least
time for the work of every call in the trace (`roofline.fill_round_work`
at the cell's real DAG sizes, one call per filling round of a whole
fitness batch) over the kernel's device time."""
from perfbench.harness.roofline import fill_round_work, least_seconds, peaks

# the kernel's HLO custom call: `fill_matvec.<k>`
KERNEL = "fill_matvec"


def read(ctx):
    d = ctx.device
    shape = ctx.loop.kernel_shape()
    if d is None or shape is None:
        return None
    calls, seconds = d.op_calls(lambda n: n.split(".")[0] == KERNEL)
    if calls == 0 or seconds <= 0:
        return None
    flops, nbytes = fill_round_work(*shape)
    least, _ = least_seconds(calls * flops, calls * nbytes,
                             peaks(ctx.device_kind))
    return 100.0 * least / seconds
