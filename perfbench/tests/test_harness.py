"""The harness on the CPU: parts found by name, a cell added from data
files alone, what the end-to-end metrics measure, and no result without a
TPU."""
from __future__ import annotations

import io
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from perfbench.harness import bench, plan
from perfbench.tests.conftest import REPO


def test_every_part_of_every_cell_is_found_by_name():
    spec = bench.load_spec(REPO)
    for w in spec["workloads"]:
        cell = bench.load_cell(w["name"], REPO, spec)
        assert cell.config["name"] == w["config"]
        assert bench.kind_module(cell.traffic).Loop
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        for m in cell.per_layer:
            assert callable(bench.load_reader(m["name"], REPO))


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        bench.load_cell("no-such-cell", REPO)


def run(root, workload="tiny-plan", seed=5, seconds=1.0, trace=False,
        control=None):
    out, err = io.StringIO(), io.StringIO()
    res = bench.run_cell(workload, seed, seconds, trace,
                         t_start=time.perf_counter(), require_chip=False,
                         control=control, root=root, out=out, err=err)
    return res, out.getvalue(), err.getvalue()


def test_a_cell_added_from_data_files_runs_and_is_correct(tiny_root):
    res, out, err = run(tiny_root, seed=2**31 + 17)
    assert json.loads(out.strip().splitlines()[-1]) == res
    assert res["correct"], err
    assert set(res["metrics"]) == {"setup_s", "plan_s", "nct"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check des_gap")


def test_traced_run_reports_the_per_layer_metrics(tiny_root):
    res, _, err = run(tiny_root, trace=True)
    assert res["correct"], err
    # no device trace on the CPU: its metrics are left out, not 0.
    # des_compile_s needs a compile in set-up, which an earlier run in
    # this process may have made already
    assert {"dag_build_s", "api_self_s", "ga_host_s", "des_batch_s"} \
        <= set(res["metrics"]) <= {"dag_build_s", "api_self_s",
                                   "ga_host_s", "des_batch_s",
                                   "des_compile_s"}
    assert res["device"]["window_s"] > 0


def test_plan_s_is_the_wall_of_whole_requests_over_their_count(tiny_root):
    cell = bench.load_cell("tiny-plan", tiny_root)
    d = plan.Loop(cell, seed=1)
    walls = [0.05, 0.04, 0.06, 0.05, 0.05, 0.05]

    def fake_request(index, generations=None):
        t0 = time.perf_counter()
        time.sleep(walls[index])
        t1 = time.perf_counter()
        return {"index": index, "t0": t0, "t1": t1, "wall": t1 - t0}

    d._request = fake_request
    d.tap = SimpleNamespace(recording=False)
    d.window(0.22)
    # judged by the fourth, the fifth would end after the window: it never
    # starts
    assert len(d.requests) == 4
    d.first_nct = 1.0
    got = d.end_to_end()["plan_s"]
    assert got == pytest.approx(sum(r["wall"] for r in d.requests) / 4)
    assert got == pytest.approx(sum(walls[:4]) / 4, rel=0.2)


def test_first_request_always_runs(tiny_root):
    d = plan.Loop(bench.load_cell("tiny-plan", tiny_root), seed=1)
    d._request = lambda i, generations=None: {"wall": 1.0}
    d.tap = SimpleNamespace(recording=False)
    d.window(0.0)
    assert d.attempted == 1 and len(d.requests) == 1


def test_no_tpu_is_an_exit_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "perfbench" / "run.py"), "--workload",
         "m177-plan", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_too_few_chips_is_refused(monkeypatch):
    import jax
    fake = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")]
    monkeypatch.setattr(jax, "devices", lambda: fake)
    assert bench.find_chips(1) == fake
    with pytest.raises(bench.NoChip):
        bench.find_chips(4)
