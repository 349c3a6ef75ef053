"""The program's spans as profiler annotations: their place in a device
trace's host plane against the clock pairing that `devtrace` uses to put
idle gaps down to spans."""
from __future__ import annotations

import glob
import os
import shutil
import time

import jax

from perfbench.harness import devtrace
from perfbench.harness.bench import TraceWindow
from repro.obs import Tracer


def test_program_spans_land_in_the_host_plane_on_the_pairing_clock():
    """With tracing on, each span is also a profiler annotation: under a
    trace on the CPU its name appears in the host plane, and its start
    there, put on the host's clock by the pairing that `devtrace` uses
    for idle gaps, matches the span's own start within 2 ms."""
    tr = Tracer(enabled=True)
    tw = TraceWindow.start()
    try:
        for i in range(3):
            with tr.span(f"probe.{i}"):
                time.sleep(0.01)
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tw.directory, "**", "*.xplane.pb"),
                         recursive=True)[0]
        red = devtrace.reduce_file(path, tw.unix_ns, tw.perf_ns)
        pd = jax.profiler.ProfileData.from_file(path)
    finally:
        shutil.rmtree(tw.directory, ignore_errors=True)
    host = {e.name: e.start_ns for plane in pd.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events if e.name.startswith("probe.")}
    assert sorted(host) == ["probe.0", "probe.1", "probe.2"]
    for r in tr.records:
        assert abs(red.start_perf_s + host[r.name] / 1e9 - r.t0) < 2e-3
