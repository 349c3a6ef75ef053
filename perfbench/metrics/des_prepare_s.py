"""DES set-up (`core/des_jax.py` `JaxDES.__init__`): seconds per request of
the `des.prepare` spans inside `plan()` (padding, `DESArrays`, the upload
of its leaves and the compiled-bucket lookup)."""
from perfbench.harness.request_spans import per_request
from perfbench.harness.spans import total


def read(ctx):
    return per_request(ctx, "des.prepare", total)
