"""Sharding rules, int8 ring all-reduce (subprocess with fake devices),
and API-level plan comparison."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import REGISTRY
from repro.distributed import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models import model as M


def test_param_specs_cover_all_archs_1device():
    mesh = make_host_mesh(1)
    for arch in sorted(REGISTRY):
        cfg = REGISTRY[arch].config.reduced()
        params = jax.eval_shape(
            lambda c=cfg: M.init_params(c, jax.random.PRNGKey(0)))
        specs = shd.tree_specs(params, mesh, "params", cfg=cfg)
        assert len(jax.tree.leaves(params)) == len(
            jax.tree.leaves(specs, is_leaf=lambda x: isinstance(
                x, jax.sharding.PartitionSpec)))


def test_assign_divisibility_fallback():
    from jax.sharding import PartitionSpec as P
    mesh = make_host_mesh(1)
    spec = shd.assign((7, 13), mesh, [(("model",), [0, 1])])
    assert spec == P(None, None)  # size-1 axis -> nothing to shard


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import ring_allreduce_int8

    # no axis_types: implicit Auto on old jax, explicit default on new
    mesh = jax.make_mesh((4,), ("data",))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 1000)).astype(np.float32)

    def body(v):
        v = v.reshape(-1)
        total, res = ring_allreduce_int8(v, "data")
        exact = jax.lax.psum(v, "data")
        return total[None], res[None], exact[None]

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                               out_specs=P("data")))
    total, res, exact = fn(jnp.asarray(x))
    total, res, exact = map(np.asarray, (total, res, exact))
    scale = np.abs(x).max() * 4 / 127
    err = np.abs(total - exact).max()
    assert err <= 4 * scale + 1e-5, (err, scale)
    # all devices agree
    assert np.allclose(total[0], total[1]) and np.allclose(total[0],
                                                           total[3])
    # residual bounded by one quantization step
    assert np.abs(res).max() <= scale + 1e-6
    print("RING_OK", err / max(np.abs(exact).max(), 1e-9))
""")


def test_int8_ring_allreduce_subprocess():
    out = subprocess.run([sys.executable, "-c", _SUBPROC],
                         capture_output=True, text=True, cwd="/root/repo",
                         timeout=300)
    assert "RING_OK" in out.stdout, out.stdout + out.stderr


def test_api_compare_orders_methods():
    from conftest import gpt7b_job
    from repro.core.api import compare
    from repro.core.ga import GAOptions
    from repro.core.schedule import build_comm_dag
    dag = build_comm_dag(gpt7b_job(3))
    res = compare(dag, methods=("prop-alloc", "iter-halve", "delta-fast"),
                  ga_options=GAOptions(time_limit=20, patience=10, seed=0))
    assert all(r.feasible for r in res.values())
    best_baseline = min(res["prop-alloc"].nct, res["iter-halve"].nct)
    assert res["delta-fast"].nct <= best_baseline + 1e-6
