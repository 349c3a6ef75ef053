"""The per-layer readers of the program's spans, on synthetic records:
two requests with known sizes, and a program that records none of the
spans (an older program) for which each reader gives nothing; and the
readers in a traced run of the tiny cell on the CPU."""
from __future__ import annotations

import io
import json
import time
from types import SimpleNamespace

import pytest

from perfbench.harness import bench
from perfbench.tests.conftest import REPO
from repro.obs import SpanRecord

NEW = ("oracle_s", "oracle_calls", "des_prepare_s", "plan_self_s",
       "dag_build_span_s", "des_trips", "des_lane_use")


def rec(name, t0, t1, depth, **attrs):
    return SpanRecord(name, t0, t1 - t0, None, depth, 1, attrs, root=1)


def request(off: float, extra_exact: bool) -> tuple[dict, list]:
    """One request at `off`: DAG build 0.8 s, then a 10 s `plan` span
    holding an ideal simulation, a DES set-up, the GA (two fitness
    batches of 48 lanes that run 100 and 50 trips), the re-rank with two
    exact simulations and the plan's own; `extra_exact` adds a 0.2 s one."""
    o = off
    spans = [
        rec("dag.build", o + 0.1, o + 0.9, 0, tasks=16),
        rec("plan", o + 1, o + 11, 0, kind="dag", method="delta-fast"),
        rec("des.exact", o + 1.5, o + 2, 1, ideal=True),
        rec("des.prepare", o + 2, o + 2.5, 1, n=64, hit=True),
        rec("ga.evolve", o + 3, o + 7, 1),
        rec("ga.fitness_batch", o + 3.4, o + 4.6, 2),
        rec("des.simulate", o + 3.5, o + 4.5, 3, entry="batch_genomes",
            pop=48, trips=100, lane_trips=2400),
        rec("des.simulate", o + 5, o + 6, 3, entry="batch_genomes",
            pop=48, trips=50, lane_trips=2400),
        rec("ga.rerank", o + 7, o + 9, 1, candidates=1),
        rec("des.exact", o + 7.2, o + 7.7, 2, ideal=False),
        rec("des.exact", o + 8, o + 8.5, 2, ideal=False),
        rec("des.exact", o + 9.5, o + 10, 1, ideal=False),
    ]
    if extra_exact:
        spans.append(rec("des.exact", o + 10.2, o + 10.4, 1, ideal=False))
    return {"t0": o, "t_plan": o + 1, "t1": o + 11}, spans


def context(spans, requests):
    return SimpleNamespace(loop=SimpleNamespace(requests=requests),
                           spans=spans, setup_spans=[])


@pytest.fixture
def ctx():
    r1, s1 = request(0.0, extra_exact=False)
    r2, s2 = request(20.0, extra_exact=True)
    return context(s1 + s2, [r1, r2])


@pytest.mark.parametrize("metric,expected", [
    ("oracle_s", (2.0 + 2.2) / 2),
    ("oracle_calls", (4 + 5) / 2),
    ("des_prepare_s", 0.5),
    # 10 s less the union of 0.5 + 0.5 + 4 + 2 + 0.5 (+ 0.2) s of children
    ("plan_self_s", (2.5 + 2.3) / 2),
    ("dag_build_span_s", 0.8),
    ("des_trips", 75.0),
    ("des_lane_use", 100.0 * 4 * 2400 / (48 * 300)),
])
def test_reader_on_synthetic_spans(ctx, metric, expected):
    assert bench.load_reader(metric, REPO)(ctx) == pytest.approx(expected)


def test_readers_give_nothing_without_the_programs_spans():
    """An older program records only the GA's and the device DES's spans,
    with no trip counts: every new reader gives None and none raises."""
    r, spans = request(0.0, extra_exact=False)
    keep = {"ga.evolve", "ga.fitness_batch", "des.simulate"}
    old = []
    for s in spans:
        if s.name in keep:
            attrs = {k: v for k, v in s.attrs.items()
                     if k not in ("trips", "lane_trips")}
            old.append(SpanRecord(s.name, s.t0, s.dur, None, s.depth, 1,
                                  attrs))
    c = context(old, [r])
    for metric in NEW:
        assert bench.load_reader(metric, REPO)(c) is None, metric


def test_traced_tiny_run_reports_the_new_metrics(tiny_root):
    """With the tiny cell added to the new metrics' cells, a traced run
    reports each of them, and the program's spans split what the
    benchmark's clocks lump together."""
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append("tiny-plan")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    err = io.StringIO()
    res = bench.run_cell("tiny-plan", 5, 1.0, True,
                         t_start=time.perf_counter(), require_chip=False,
                         control=None, root=tiny_root, out=io.StringIO(),
                         err=err)
    assert res["correct"], err.getvalue()
    assert set(NEW) <= set(res["metrics"])
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["plan_self_s"] + m["oracle_s"] + m["des_prepare_s"] \
        < m["api_self_s"]
    assert m["dag_build_span_s"] <= m["dag_build_s"]
    assert 0 < m["des_lane_use"] <= 100
