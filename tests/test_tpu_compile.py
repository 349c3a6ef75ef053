"""Compile the Pallas kernels and the fused DES step for a TPU v5e chip.

Nothing runs: the chip is described, not attached, so these tests show
what the v5e compiler accepts at the planner's real shapes (block
alignment, VMEM use, device memory) on a machine without one.  Every case
passes ``backend="pallas", interpret=False`` explicitly, because code that
resolves its backend from `jax.default_backend()` sees the CPU here, and
asserts that the kernel really is in the compiled program.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import PAPER_WORKLOADS, make_job
from repro.core import schedule
from repro.core.des import DESProblem
from repro.core.des_jax import (_ARRAY_FIELDS, CompiledDES, DESArrays,
                                DESOptions, PadSpec, _StaticCfg,
                                default_max_events)
from repro.core.schedule import build_comm_dag
from repro.kernels import ops

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compile_v5e(one_chip, no_persistent_cache):
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled
    return run


F32 = jnp.float32


@pytest.mark.parametrize("cons,n", [(80, 832),     # megatron-462b bucket
                                    (168, 3648)])  # deepseek-671b bucket
def test_fill_round_compiles(compile_v5e, cons, n):
    compile_v5e(
        lambda w, lvl, unf: ops.fill_round(w, lvl, unf, backend="pallas",
                                           interpret=False),
        ((cons, n), F32), ((n,), F32), ((n,), F32))


def test_tclosure_step_compiles(compile_v5e):
    compile_v5e(lambda a: ops.tclosure_step(a, backend="pallas",
                                            interpret=False),
                ((300, 300), F32))


def test_maxplus_compiles(compile_v5e):
    compile_v5e(lambda a, b: ops.maxplus(a, b, backend="pallas",
                                         interpret=False),
                ((300, 300), F32), ((300, 300), F32))


def test_dominance_prune_compiles(compile_v5e):
    """The DAG build's dominance prune (max-plus kernel and edge codes) at
    deepseek-671b's 3617 tasks padded to the kernel's tile, within the
    device bytes by which the build decides that it fits."""
    n = 3840
    m = compile_v5e(
        lambda d, tau, band: schedule._dominance_codes(
            d, tau, band, backend="pallas", interpret=False),
        ((n, n), F32), ((n,), F32), ((), F32)).memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert used <= schedule._PRUNE_DEVICE_BYTES * n * n


def test_fused_batch_genomes_step_compiles(compile_v5e):
    """The GA's fitness step (genome scatter + vmapped DES with the
    fill_round kernel) at megatron-462b's Table I bucket, population 48.
    Its masked max-plus dependency readiness stays fused: no (48, n, n)
    buffer is written."""
    arch = PAPER_WORKLOADS["megatron-462b"]
    dag = build_comm_dag(make_job(arch, seq_len=4096,
                                  microbatches=arch.plan.num_microbatches),
                         inter_pod_gbps=400.0)
    problem = DESProblem(dag)
    ropt = DESOptions(backend="pallas", interpret=False).resolve()
    assert (ropt.backend, ropt.interpret) == ("pallas", False)
    pad = PadSpec.exact(problem).bucketed(ropt)
    assert (pad.n, pad.cons) == (832, 80)
    P = dag.cluster.num_pods
    cd = CompiledDES(_StaticCfg(
        n=pad.n, num_cons=pad.cons, num_link_cons=pad.links, P=P,
        max_events=default_max_events(pad.n), backend=ropt.backend,
        interpret=ropt.interpret, members=0))
    arrays = DESArrays.from_problem(problem, pad)
    leaves = tuple(getattr(arrays, f) for f in _ARRAY_FIELDS)
    E = len(dag.undirected_pairs())

    def step(leaves, genomes, eu, ev, mask):
        def one(g):
            return cd._run(leaves, cd._scatter(g, eu, ev),
                           jnp.asarray(False), mask)[:2]
        return jax.vmap(one)(genomes)

    leaf_shapes = [(a.shape, a.dtype) for a in leaves]
    compiled = compile_v5e(
        lambda *a: step(tuple(a[:len(leaves)]), *a[len(leaves):]),
        *leaf_shapes, ((48, E), jnp.int32), ((E,), jnp.int32),
        ((E,), jnp.int32), ((P, P), F32))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
    assert mem.temp_size_in_bytes < 48 * pad.n * pad.n * 4 // 8
