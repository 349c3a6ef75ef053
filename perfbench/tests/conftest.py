"""A tiny cell added from data files alone, for running the harness on the
CPU: a copy of the benchmark's files beside the program's `src/`, with one
more configuration (a 4-pod gpt-7b job, 16 inter-pod tasks), a plan
traffic mix with GA population 8 for 2 generations, and a workload of
them."""
from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "name": "gpt-7b-tiny",
    "model": {"name": "gpt-7b", "family": "dense", "layers": 32,
              "d_model": 4096, "heads": 32, "kv_heads": 32, "d_ff": 11008,
              "vocab": 50257},
    "parallelism": {"tp": 2, "pp": 4, "dp": 2, "ep": 1,
                    "gpus_per_pod_per_replica": 4, "microbatches": 4,
                    "micro_batch_size": 1, "gpu_flops": 140e12},
    "cluster": {"gpus": 16, "pods": 4, "inter_pod_gbps": 400.0,
                "seq_len": 4096, "act_bytes": 2, "grad_bytes": 2},
    "precision": {"device_des": "float32", "host_oracle": "float64"},
    "dag": {"tasks": 16, "deps": 54, "pods": 4, "active_pairs": 3,
            "ports": 16, "flows": 32.0, "volume_bytes": 27820326912.0,
            "delay_s": 21.264645652990374,
            "pairs": {
                "0>1": {"tasks": 4, "flows": 8.0,
                        "volume_bytes": 134217728.0},
                "0>2": {"tasks": 2, "flows": 4.0,
                        "volume_bytes": 6887972864.0},
                "1>0": {"tasks": 4, "flows": 8.0,
                        "volume_bytes": 134217728.0},
                "1>3": {"tasks": 2, "flows": 4.0,
                        "volume_bytes": 6887972864.0},
                "2>0": {"tasks": 2, "flows": 4.0,
                        "volume_bytes": 6887972864.0},
                "3>1": {"tasks": 2, "flows": 4.0,
                        "volume_bytes": 6887972864.0}},
            "digest": "dbc84b36b925c7f959f5e1a22e791065"
                      "919d1f53320cda9807522d5e29f2b4d5"},
}

TINY_GA = {"pop_size": 8, "max_generations": 2, "patience": 2}
TINY_PLAN = {"kind": "plan", "method": "delta-fast", "ga": TINY_GA,
             "check": {"device_sample": 6}}


def add_cells(root: Path) -> None:
    """Adds a configuration, a traffic mix and a workload of them,
    `tiny-plan`, to the copy at `root`, by data files and BENCHMARK.json
    entries only; it reports what the plan cells report."""
    bench_dir = root / "perfbench"
    (bench_dir / "configs" / "gpt-7b-tiny.json").write_text(
        json.dumps(TINY_CONFIG))
    (bench_dir / "traffic" / "plan-tiny.json").write_text(
        json.dumps(TINY_PLAN))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "gpt-7b-tiny", "source": "test",
                            "file": "perfbench/configs/gpt-7b-tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-plan", "config": "gpt-7b-tiny",
                              "traffic": "plan-tiny", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "m177-plan" in m.get("workloads", ()):
            m["workloads"].append("tiny-plan")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(REPO / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    os.symlink(REPO / "src", root / "src")
    add_cells(root)
    return root



@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Runs here leave JAX's persistent cache as the test run set it."""
    from perfbench.harness import bench
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: "")
