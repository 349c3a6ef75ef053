"""Reduction of a JAX profiler trace to device busy time, idle gaps and
per-operation device time.

The profiler writes one `.xplane.pb` per host.  Each TPU is a plane named
`/device:TPU:<i>`; its line "XLA Ops" holds one event per operation the
chip ran, with start and duration in nanoseconds from the start of the
trace, named by its HLO instruction text.  A `while` operation's event
spans the operations of its body, which are events of their own, so an
operation's time here is its self time: its span less its children's.
Busy time is the union of the intervals inside the traced window; idle is
the rest.  The "Task Environment" plane carries the
trace's start and stop on the wall clock, which puts the program's spans
(host monotonic clock, via the pair taken when the trace started) on the
same time line, so that each idle gap can be put down to what the host was
doing in it.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclass
class DeviceOps:
    """One chip's operations: merged busy intervals and self time per op."""

    busy: np.ndarray                     # (k, 2) ns, disjoint, sorted
    ops: dict = field(default_factory=dict)   # name -> [count, self ns]


def op_name(hlo_text: str) -> str:
    """`%fill_matvec.6 = f32[...] custom-call(...)` -> `fill_matvec.6`."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def self_times(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Each interval's length less the lengths of the intervals directly
    nested in it."""
    order = np.lexsort((-ends, starts))
    own = ends - starts
    stack: list[int] = []
    for i in order:
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack:
            own[stack[-1]] -= ends[i] - starts[i]
        stack.append(i)
    return own


def merge(intervals: np.ndarray) -> np.ndarray:
    """Union of (start, end) intervals as disjoint sorted intervals."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), dtype=bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]
    return np.stack([starts, ends[last]], axis=1)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


@dataclass
class Reduced:
    window_s: float
    devices: list                       # DeviceOps per chip
    start_perf_s: float                 # trace t=0 on the host's clock

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips in the trace."""
        if not self.devices:
            return 0.0
        return float(np.mean([(d.busy[:, 1] - d.busy[:, 0]).sum()
                              for d in self.devices]) / 1e9)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self) -> dict:
        """Seconds per operation name, averaged over the chips."""
        out: dict = defaultdict(float)
        for d in self.devices:
            for name, (_, ns) in d.ops.items():
                out[name] += ns / 1e9 / len(self.devices)
        return dict(out)

    def op_calls(self, match) -> tuple[int, float]:
        """Calls and seconds (both averaged over the chips) of the
        operations whose name satisfies `match`."""
        calls = secs = 0.0
        for d in self.devices:
            for name, (count, ns) in d.ops.items():
                if match(name):
                    calls += count / len(self.devices)
                    secs += ns / 1e9 / len(self.devices)
        return int(round(calls)), secs

    def gaps(self) -> np.ndarray:
        """Idle (start, end) intervals of the first chip, in ns."""
        if not self.devices:
            return np.zeros((0, 2))
        b = self.devices[0].busy
        edges = np.r_[0.0, b.ravel(), self.window_s * 1e9].reshape(-1, 2)
        return edges[edges[:, 1] > edges[:, 0]]

    def breakdown(self, spans, top: int = 10) -> dict:
        """The operations that took most device time, and the idle time
        by the innermost program span open in the middle of each gap."""
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        idle: dict = defaultdict(float)
        for lo, hi in self.gaps():
            mid = self.start_perf_s + (lo + hi) / 2e9
            best, depth = "no span", -1
            for s in spans:
                if s.t0 <= mid <= s.t0 + s.dur and s.depth > depth:
                    best, depth = s.name, s.depth
            idle[best] += float(hi - lo) / 1e9
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def idle_percent(ctx) -> float | None:
    """The idle share of a run's traced window in percent, or None where
    the run has no device trace."""
    d = ctx.device
    if d is None or not d.devices:
        return None
    return 100.0 * d.idle_share


def reduce_file(path: str, unix_ns: int, perf_ns: int,
                window_s: float | None = None) -> Reduced:
    """Reduce one `.xplane.pb`.  `unix_ns` and `perf_ns` are the wall and
    monotonic clocks read together when the trace started."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    start_unix = stop_unix = None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            start_unix = stats.get("profile_start_time")
            stop_unix = stats.get("profile_stop_time")
    if window_s is None:
        window_s = (stop_unix - start_unix) / 1e9
    hi = window_s * 1e9
    devices = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            events = list(line.events)
            starts = np.array([e.start_ns for e in events], dtype=float)
            ends = starts + np.array([e.duration_ns for e in events],
                                     dtype=float)
            own = self_times(starts, ends)
            ops: dict = {}
            for e, t in zip(events, own):
                c = ops.setdefault(op_name(e.name), [0, 0.0])
                c[0] += 1
                c[1] += float(t)
            iv = np.stack([starts, ends], axis=1)
            devices.append(DeviceOps(busy=merge(clip(iv, 0.0, hi)),
                                     ops=ops))
    origin = start_unix if start_unix is not None else unix_ns
    start_perf_s = (perf_ns + (origin - unix_ns)) / 1e9
    return Reduced(window_s=window_s, devices=devices,
                   start_perf_s=start_perf_s)


def reduce_dir(directory: str, unix_ns: int, perf_ns: int) -> Reduced:
    files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_file(files[-1], unix_ns, perf_ns)
