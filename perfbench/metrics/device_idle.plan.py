"""Device idle share of the plan cells' window: 100 times one minus the
union of the chip's operation intervals over the traced window."""
from perfbench.harness.devtrace import idle_percent


def read(ctx):
    return idle_percent(ctx)
