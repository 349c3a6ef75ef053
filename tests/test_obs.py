"""repro.obs: metrics exposition, span semantics, timeline schema,
journal replay, and the planner-scoped report deltas."""
from __future__ import annotations

import json
import time

import numpy as np
import pytest

from repro.core.des import DESProblem, simulate
from repro.obs import (FleetJournal, MetricsRegistry, Tracer,
                       rebuild_event, schedule_timeline, serialize_event,
                       slack_report, task_slack, validate_trace,
                       write_trace)
from repro.obs.tracing import _NULL_SPAN
from conftest import gpt7b_job, one_circuit_topology


# ------------------------------------------------------------------ metrics
class TestMetrics:
    def test_counter_gauge_histogram_roundtrip(self):
        """Counters and gauges (the registry has no histograms: none had
        a producer)."""
        reg = MetricsRegistry(enabled=True)
        c = reg.counter("requests_total", "requests served")
        c.inc()
        c.inc(2, method="get")
        g = reg.gauge("pool_ports", "free ports")
        g.set(7)
        g.dec(3)
        assert c.value() == 1 and c.value(method="get") == 2
        assert g.value() == 4
        assert not hasattr(reg, "histogram")

    def test_counter_rejects_negative(self):
        reg = MetricsRegistry(enabled=True)
        with pytest.raises(ValueError):
            reg.counter("c").inc(-1)

    def test_type_conflict_raises(self):
        reg = MetricsRegistry(enabled=True)
        reg.counter("x_total")
        with pytest.raises(TypeError):
            reg.gauge("x_total")

    def test_prometheus_exposition_golden(self):
        """Exact text exposition: # HELP / # TYPE + one line per series,
        labels sorted, metrics by name."""
        reg = MetricsRegistry(enabled=True)
        c = reg.counter("events_total", "events handled")
        c.inc(3, kind="arrival")
        c.inc(1, kind="departure")
        g = reg.gauge("tenants", "admitted tenants")
        g.set(2)
        assert reg.render_prometheus() == (
            "# HELP events_total events handled\n"
            "# TYPE events_total counter\n"
            'events_total{kind="arrival"} 3\n'
            'events_total{kind="departure"} 1\n'
            "# HELP tenants admitted tenants\n"
            "# TYPE tenants gauge\n"
            "tenants 2\n")

    def test_snapshot_is_json_and_scoped_deltas(self):
        reg = MetricsRegistry(enabled=True)
        c = reg.counter("hits_total")
        c.inc(5)
        scope = reg.scope()
        c.inc(2)
        c.inc(4, shard="a")
        assert scope.delta("hits_total") == 2
        assert scope.delta("hits_total", shard="a") == 4
        assert scope.delta("missing_total") == 0
        snap = json.loads(reg.to_json())
        assert snap["hits_total"]["series"][""] == 7
        assert snap["hits_total"]["series"]["shard=a"] == 4

    def test_disabled_registry_is_inert(self):
        reg = MetricsRegistry(enabled=False)
        c = reg.counter("c_total")
        c.inc(100)
        assert c.value() == 0
        assert reg.snapshot()["c_total"]["series"] == {}


# ------------------------------------------------------------------ tracing
class TestTracing:
    def test_nesting_and_parents(self):
        tr = Tracer(enabled=True)
        with tr.span("outer"):
            with tr.span("inner", k=1):
                pass
            with tr.span("inner2"):
                pass
        recs = {r.name: r for r in tr.records}
        assert recs["inner"].parent == "outer" and recs["inner"].depth == 1
        assert recs["inner2"].parent == "outer"
        assert recs["outer"].parent is None and recs["outer"].depth == 0
        assert recs["inner"].attrs == {"k": 1}
        assert all(r.dur >= 0 for r in tr.records)

    def test_exception_safety(self):
        tr = Tracer(enabled=True)
        with pytest.raises(RuntimeError), tr.span("outer"), \
                tr.span("boom"):
            raise RuntimeError("x")
        recs = {r.name: r for r in tr.records}
        assert recs["boom"].attrs["error"] == "RuntimeError"
        assert recs["outer"].attrs["error"] == "RuntimeError"
        # the stack unwound fully: a new span is a root again
        with tr.span("after"):
            pass
        assert {r.name: r for r in tr.records}["after"].parent is None

    def test_disabled_mode_is_nullspan_and_cheap(self):
        """Disabled spans must stay WELL under the 2% overhead budget of
        the delta-fast smoke: the ga hot loop takes >=100us per
        generation, so <2us per disabled span() call is a 50x margin --
        and immune to CI wall-clock noise, unlike an end-to-end A/B."""
        tr = Tracer(enabled=False)
        assert tr.span("x", a=1) is _NULL_SPAN
        n = 100_000
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("hot", i=0):
                pass
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 2e-6, f"{per_call * 1e6:.2f}us per disabled span"
        assert tr.records == []

    def test_summary_and_chrome_trace(self):
        tr = Tracer(enabled=True)
        for _ in range(3):
            with tr.span("work"):
                pass
        s = tr.summary()["work"]
        assert s["count"] == 3 and s["total_s"] >= 0
        assert s["max_s"] <= s["total_s"] + 1e-12
        trace = tr.to_chrome_trace()
        assert validate_trace(trace) == []

    def test_enabled_context_manager_restores(self):
        tr = Tracer(enabled=False)
        with tr.enabled(True), tr.span("x"):
            pass
        assert not tr.is_enabled
        assert len(tr.records) == 1

    def test_max_records_drop(self):
        tr = Tracer(enabled=True, max_records=2)
        for _ in range(5):
            with tr.span("x"):
                pass
        assert len(tr.records) == 2 and tr.dropped == 3


    def test_root_is_shared_by_nested_spans_and_new_per_outermost(self):
        tr = Tracer(enabled=True)
        with tr.span("a"), tr.span("b"):
            pass
        with tr.span("c"):
            pass
        recs = {r.name: r for r in tr.records}
        assert recs["a"].root == recs["b"].root != recs["c"].root
        assert tr.records[0].as_dict()["root"] == recs["b"].root
        args = [e["args"] for e in tr.to_chrome_trace()["traceEvents"]
                if e["ph"] == "X"]
        assert sorted(a["root"] for a in args) == sorted(
            r.root for r in tr.records)


# ------------------------------------------------------- spans of a plan
@pytest.fixture
def traced():
    """The process tracer, on and empty for one test."""
    from repro.obs import TRACER
    TRACER.clear()
    with TRACER.enabled(True):
        yield TRACER
    TRACER.clear()


def _plan_tiny(dag, seed=0):
    from repro.core.api import PlanRequest, plan
    from repro.core.ga import GAOptions
    opts = GAOptions(seed=seed, pop_size=8, max_generations=1, patience=1,
                     time_limit=1e9, backend="jax")
    return plan(PlanRequest(dag=dag, method="delta-fast", ga_options=opts))


class TestPlanSpans:
    def test_one_plan_root_per_request_shared_by_its_spans(self, traced,
                                                           tiny_dag):
        _plan_tiny(tiny_dag, seed=0)
        _plan_tiny(tiny_dag, seed=1)
        recs = traced.records
        roots = [r for r in recs if r.name == "plan"]
        assert len(roots) == 2 and all(r.depth == 0 for r in roots)
        assert roots[0].attrs == {"kind": "dag", "method": "delta-fast"}
        assert roots[0].root != roots[1].root
        for root in roots:
            inside = [r for r in recs if root.t0 <= r.t0
                      and r.t0 + r.dur <= root.t0 + root.dur]
            assert {r.root for r in inside} == {root.root}
            assert {"des.exact", "des.prepare", "ga.evolve", "ga.rerank",
                    "des.simulate"} <= {r.name for r in inside}
        assert all(r.root in {x.root for x in roots} for r in recs)

    def test_exact_spans_count_simulate_calls_and_their_events(
            self, traced, tiny_dag, monkeypatch):
        import repro.core.des as des
        results, rounds = [], []
        inner, fill = des._simulate, des._filled_rates

        def counted(*args):
            rounds.append(0)
            out = inner(*args)
            results.append(out[0])
            return out

        def counted_fill(*args):
            out = fill(*args)
            rounds[-1] += out[1]
            return out

        monkeypatch.setattr(des, "_simulate", counted)
        monkeypatch.setattr(des, "_filled_rates", counted_fill)
        _plan_tiny(tiny_dag)
        exact = [r for r in traced.records if r.name == "des.exact"]
        assert len(exact) == len(results) > 2
        # the ideal first, the plan's own simulation last
        assert exact[0].attrs["ideal"] and not exact[-1].attrs["ideal"]
        for rec, res, r in zip(exact, results, rounds):
            assert rec.attrs["n"] == tiny_dag.num_tasks
            # each trip of the event loop but the last adds one event time
            # to the first, t = 0
            assert rec.attrs["events"] == len(res.events)
            assert rec.attrs["rounds"] == r > 0
        rerank = [r for r in traced.records if r.name == "ga.rerank"][0]
        assert sum(r.parent == "ga.rerank" for r in exact) == \
            rerank.attrs["candidates"] + 1

    def test_batch_trips_are_lane_max_and_sum_results_unchanged(
            self, tiny_dag):
        from repro.core.des_jax import JaxDES
        from repro.core.ga import TopologySpace
        from repro.obs import TRACER
        space = TopologySpace(tiny_dag)
        genomes = space.random_init_batch(np.random.default_rng(3), 6)
        genomes[0] = space.xbar          # the fastest lane
        genomes[1] = space.g_min         # a slow one
        jd = JaxDES(DESProblem(tiny_dag))
        eu, ev = space.edge_u, space.edge_v
        off = jd.batch_genome_makespan(genomes, eu, ev)
        TRACER.clear()
        with TRACER.enabled(True):
            on = jd.batch_genome_makespan(genomes, eu, ev)
        rec = [r for r in TRACER.records if r.name == "des.simulate"][-1]
        TRACER.clear()
        for a, b in zip(off, on):
            assert a.dtype == b.dtype and np.array_equal(a, b)
        ones = np.ones((jd.P, jd.P), dtype=np.float32)
        _, _, trips = jd._compiled.batch_genomes(
            jd._leaves, np.asarray(genomes), np.asarray(eu, np.int32),
            np.asarray(ev, np.int32), ones)
        trips = np.asarray(trips)
        assert trips.shape == (6,) and (trips > 0).all()
        assert rec.attrs["trips"] == trips.max()
        assert rec.attrs["lane_trips"] == trips.sum()
        assert rec.attrs["pop"] == 6


# ----------------------------------------------------------------- timeline
class TestTimeline:
    def test_slack_report_matches_des_makespan(self, small_dag):
        x = one_circuit_topology(small_dag)
        res = simulate(DESProblem(small_dag), x, record_rates=True)
        slack = task_slack(small_dag, res)
        rep = slack_report(small_dag, res)
        assert rep["feasible"]
        assert rep["makespan"] == pytest.approx(res.makespan)
        # realized finishes agree with the reported makespan
        finite = np.isfinite(res.finish)
        assert res.finish[finite].max() == pytest.approx(rep["makespan"])
        # the DES-certified critical path has (numerically) zero slack
        rel = 1e-6 * res.makespan
        for tid in rep["critical_path"]:
            assert slack[tid] <= rel
        assert rep["zero_slack_tasks"], "some task must be critical"
        # non-critical tasks: slack == how far the finish can slip; all
        # slacks are non-negative on a feasible realized schedule
        assert (slack[finite] >= -rel).all()

    def test_schedule_timeline_schema_and_tracks(self, small_dag):
        x = one_circuit_topology(small_dag)
        trace = schedule_timeline(small_dag, x)
        assert validate_trace(trace) == []
        events = trace["traceEvents"]
        pairs = DESProblem(small_dag).pairs
        names = {e["args"]["name"] for e in events
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert len(names) == len(pairs)
        tasks = [e for e in events if e["ph"] == "X"]
        assert len(tasks) == sum(1 for _ in small_dag.real_tasks())
        # per-link utilization counters from the rate trace, within [0, 1+]
        counters = [e for e in events if e["ph"] == "C"]
        assert counters
        assert all(e["args"]["utilization"] >= 0 for e in counters)
        assert trace["otherData"]["makespan_s"] > 0
        # round-trips through JSON
        assert validate_trace(json.loads(json.dumps(trace))) == []

    def test_write_trace_rejects_invalid(self, tmp_path):
        with pytest.raises(ValueError):
            write_trace({"traceEvents": [{"ph": "Z"}]},
                        str(tmp_path / "bad.json"))

    def test_infeasible_plan_raises(self, small_dag):
        P = small_dag.cluster.num_pods
        with pytest.raises(ValueError):
            schedule_timeline(small_dag, np.zeros((P, P), dtype=np.int64))


# ------------------------------------------------------------------ journal
class TestJournal:
    def test_event_serialization_roundtrip(self):
        from repro.fleet.loop import (JobArrival, JobDeparture,
                                      TrafficChange)
        job = gpt7b_job(4)
        for ev in (JobArrival("a", job, port_min=True, base_pod=1),
                   JobDeparture("a"),
                   TrafficChange("a", gpt7b_job(8))):
            data = json.loads(json.dumps(serialize_event(ev)))
            assert rebuild_event(data) == ev

    def test_jsonl_roundtrip_and_replay(self, tmp_path):
        from repro.fleet.loop import JobArrival, JobDeparture
        path = tmp_path / "journal.jsonl"
        j = FleetJournal(path)
        events = [JobArrival("m", gpt7b_job(4)), JobDeparture("m")]
        for i, ev in enumerate(events):
            j.record_event(ev, {"i": i, "np": np.int64(3)})
        j.record("note", msg="not an event")
        j.close()
        entries = FleetJournal.load(path)
        assert [e["seq"] for e in entries] == [0, 1, 2]
        assert entries[0]["record"]["np"] == 3    # numpy scalars serialized
        assert FleetJournal.rebuild_events(entries) == events
        assert FleetJournal.rebuild_events(path) == events


# ------------------------------------------------------- fleet integration
@pytest.mark.slow
class TestFleetObs:
    def _mini_fleet(self):
        from repro.core.ga import GAOptions
        from repro.fleet import FleetSpec
        job = gpt7b_job(2)
        ent = max(job.placement().port_limits())
        fleet = FleetSpec(num_pods=4, ports_per_pod=2 * ent, nic_gbps=100.0)
        ga = GAOptions(seed=0, pop_size=12, max_generations=5, patience=3,
                       time_limit=10.0)
        return fleet, ga, job

    def test_report_scoped_and_journal_replay(self, tmp_path):
        from repro.core.des_jax import des_cache_clear
        from repro.fleet import FleetPlanner, JobArrival, JobDeparture
        # earlier test files may have warmed the compile-bucket cache for
        # this very DES shape; the >=1-miss assertion needs a cold cache
        des_cache_clear()
        fleet, ga, job = self._mini_fleet()
        path = tmp_path / "fleet.jsonl"
        p1 = FleetPlanner(fleet, ga_options=ga, seed=0,
                          journal=FleetJournal(path))
        p1.handle(JobArrival("m", job))
        r1 = p1.report()
        assert r1["des_cache"]["misses"] >= 1     # first plan jit-compiles

        # a SECOND planner in the same process: its scope starts at the
        # current counters, so the first planner's compile misses must
        # not leak into its report (the satellite bug this PR fixes)
        p2 = FleetPlanner(fleet, ga_options=ga, seed=0)
        r2 = p2.report()
        assert r2["des_cache"]["misses"] == 0
        assert r2["des_cache"]["hits"] == 0
        assert r2["events"] == {}

        p1.handle(JobDeparture("m"))
        r1b = p1.report()
        assert r1b["events"]["kind=arrival,outcome=ok"] == 1
        assert r1b["events"]["kind=departure,outcome=ok"] == 1

        # journal replay re-drives a fresh planner to the same decisions
        replayed = FleetJournal.rebuild_events(path)
        assert [type(e).__name__ for e in replayed] == \
            ["JobArrival", "JobDeparture"]
        p3 = FleetPlanner(fleet, ga_options=ga, seed=0)
        records = p3.process(replayed)
        assert records[0]["ports"] == p1.history[0]["ports"]
        assert records[0]["nct"] == pytest.approx(p1.history[0]["nct"])
