"""The benchmark's runner: finds a cell's parts by name, runs it once and
prints the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name `BENCHMARK.json` gives it:

    perfbench/configs/<config>.json     a deployment (file named in
                                        BENCHMARK.json's `configs`)
    perfbench/traffic/<traffic>.json    a traffic mix; its "kind" names the
                                        generator that reads it
                                        (perfbench/harness/<kind>.py)
    perfbench/metrics/<metric>.py       one per-layer metric: `read(ctx)`

A run, in order: look for the chips (none, or too few, is an exit without a
result), set up the cell (counted in `setup_s`), drive the timed window,
read the device's peak memory, free the program's state, compare what the
window produced with the plain reference, and print one JSON line.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    """One workload of BENCHMARK.json with its parts loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, root: Path = ROOT, spec: dict | None = None
              ) -> Cell:
    spec = spec if spec is not None else load_spec(root)
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "perfbench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def load_reader(metric: str, root: Path = ROOT):
    """The `read(ctx)` function of perfbench/metrics/<metric>.py."""
    path = root / "perfbench" / "metrics" / f"{metric}.py"
    mod_name = "perfbench_metric_" + metric.replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def kind_module(traffic: dict):
    """The generator of a traffic mix: perfbench/harness/<kind>.py."""
    return importlib.import_module(f"perfbench.harness.{traffic['kind']}")


# --------------------------------------------------------------- device
def find_chips(chips: int):
    """The devices of this run; raises NoChip without a TPU or with fewer
    chips than the cell asks for.  Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devices[0].platform!r}, "
                     f"not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, or where $JAX_COMPILATION_CACHE_DIR says."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts lowerings and backend compiles that JAX reports, so that the
    window can show it compiled nothing."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon
        self.lowered = 0
        self.compiled = 0
        self._mon = mon
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.COMPILE:
            self.compiled += 1

    def count(self) -> int:
        return self.lowered + self.compiled

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._on)


def device_record(devices, chips: int) -> dict:
    """Platform, kind and count as JAX reports them, and the peak memory
    in use on the fullest of the chips the cell uses."""
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


# ---------------------------------------------------------------- trace
@dataclass
class TraceWindow:
    """The profiler around the timed window (`--trace 1` only), with the
    clock pair that puts the program's spans on the trace's time line."""

    directory: str
    unix_ns: int = 0
    perf_ns: int = 0

    @classmethod
    def start(cls) -> "TraceWindow":
        import jax
        d = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=opts)
        return cls(directory=d, unix_ns=time.time_ns(),
                   perf_ns=time.perf_counter_ns())

    def stop(self):
        """Stops the profiler, reduces the trace and deletes it."""
        import jax
        from perfbench.harness import devtrace
        jax.profiler.stop_trace()
        try:
            return devtrace.reduce_dir(self.directory, self.unix_ns,
                                       self.perf_ns)
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)


# --------------------------------------------------------------- checks
@dataclass
class Checks:
    """Numbers compared with the reference, each with its limit.  The run
    is correct when every number is finite and at most its limit."""

    items: dict = field(default_factory=dict)

    def add(self, name: str, value: float, limit: float) -> None:
        self.items[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        import math
        return bool(self.items) and all(
            math.isfinite(v["value"]) and v["value"] <= v["limit"]
            for v in self.items.values())

    def lines(self) -> list[str]:
        return [f"check {k}: {v['value']!r} limit {v['limit']!r}"
                for k, v in self.items.items()]


# ------------------------------------------------------------------ run
@dataclass
class RunContext:
    """What a per-layer reader may read: the cell, the loop's records
    of the window, the program's spans, and the reduced device trace."""

    cell: Cell
    loop: object
    spans: list = field(default_factory=list)        # window spans
    setup_spans: list = field(default_factory=list)  # set-up spans
    device: object = None                            # devtrace.Reduced
    device_kind: str = ""


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             control: str | None = None, root: Path = ROOT,
             out=sys.stdout, err=sys.stderr) -> dict:
    """One run of one cell; returns the result line it printed."""
    cell = load_cell(workload, root)
    import jax
    if require_chip:
        devices = find_chips(cell.chips)
    else:
        devices = jax.devices()
    enable_compile_cache()
    from repro.obs import TRACER
    kind = kind_module(cell.traffic)
    loop = kind.Loop(cell, seed)
    if trace:
        TRACER.enable()
    compiles = CompileCounter()
    loop.setup()
    setup_records = len(TRACER.records)
    setup_s = time.perf_counter() - t_start
    compiles_before = compiles.count()
    tw = TraceWindow.start() if trace else None
    loop.window(seconds)
    reduced = tw.stop() if tw is not None else None
    window_compiles = compiles.count() - compiles_before
    compiles.close()
    device = device_record(devices, cell.chips)
    spans = list(TRACER.records[setup_records:])
    setup_spans = list(TRACER.records[:setup_records])
    TRACER.disable()
    TRACER.clear()
    loop.free()
    checks = Checks()
    checks.add("window_compiles", window_compiles, 0)
    loop.check(checks, control=control)
    if trace:
        ctx = RunContext(cell=cell, loop=loop, spans=spans,
                         setup_spans=setup_spans, device=reduced,
                         device_kind=device["kind"])
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    else:
        values = loop.end_to_end()
        values["setup_s"] = setup_s
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": checks.correct, "attempted": loop.attempted,
              "failed": loop.failed, "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = reduced.breakdown(spans)
    result["checks"] = checks.items
    for line in checks.lines():
        print(line, file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return result

