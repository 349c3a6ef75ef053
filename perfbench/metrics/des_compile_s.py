"""DES compile (`core/des_jax.py` `CompiledDES`): seconds of the `des.jit`
spans during set-up, the first call of each jitted entry (trace, compile
or load from the persistent cache)."""
from perfbench.harness.spans import select, total


def read(ctx):
    spans = select(ctx.setup_spans, "des.jit")
    return total(spans) if spans else None
