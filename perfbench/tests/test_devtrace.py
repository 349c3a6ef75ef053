"""The reduction from a profiler trace to busy time, idle gaps and kernel
time, and the work and peaks behind a roofline share."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench.harness import devtrace, roofline

DATA = Path(__file__).resolve().parent / "data"


def test_merge_takes_the_union_of_overlapping_intervals():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [10, 11]], float)
    assert devtrace.merge(iv).tolist() == [[0, 3], [5, 9], [10, 11]]
    assert devtrace.merge(np.zeros((0, 2))).shape == (0, 2)


def test_busy_idle_and_gaps_of_a_synthetic_window():
    busy = devtrace.merge(np.array([[0, 2e9], [3e9, 4e9]]))
    dev = devtrace.DeviceOps(busy=busy, ops={"fusion": [3, 2.5e9],
                                             "fill_matvec.1": [4, 0.5e9]})
    red = devtrace.Reduced(window_s=5.0, devices=[dev], start_perf_s=100.0)
    assert red.busy_s == pytest.approx(3.0)
    assert red.idle_share == pytest.approx(0.4)
    assert red.gaps().tolist() == [[2e9, 3e9], [4e9, 5e9]]
    assert red.op_calls(lambda n: "fill_matvec" in n) == (4, 0.5)
    spans = [SimpleNamespace(name="bench.plan", t0=100.0, dur=5.0, depth=0),
             SimpleNamespace(name="ga.generation", t0=102.0, dur=1.5,
                             depth=1)]
    b = red.breakdown(spans)
    assert b["device_ops"][0] == ["fusion", 2.5]
    assert b["idle_gaps"] == [["ga.generation", 1.0], ["bench.plan", 1.0]]


def test_self_time_takes_nested_operations_out():
    starts = np.array([0.0, 1.0, 2.0, 5.0, 8.0])
    ends = np.array([10.0, 2.0, 4.0, 6.0, 9.0])
    assert devtrace.self_times(starts, ends).tolist() == [5, 1, 2, 1, 1]


def test_reduction_of_a_recorded_trace():
    """One pop-8 fitness batch of a 4-pod gpt-7b DAG, traced on a TPU v5
    lite: 1194 operations, 16 of them fill_round kernel calls."""
    red = devtrace.reduce_file(str(DATA / "gpt-7b-batch.xplane.pb"), 0, 0)
    assert len(red.devices) == 1
    assert red.window_s == pytest.approx(0.307111504)
    assert red.busy_s == pytest.approx(0.000454905)
    # self times partition the busy time: no operation counted twice
    assert sum(red.op_seconds().values()) == pytest.approx(red.busy_s)
    assert red.op_calls(lambda n: n.split(".")[0] == "fill_matvec") == \
        (16, pytest.approx(0.000101776))
    gaps = red.gaps()
    assert (np.diff(gaps, axis=1) > 0).all()
    assert (gaps[:, 1] - gaps[:, 0]).sum() / 1e9 == pytest.approx(
        red.window_s - red.busy_s)


def test_fill_round_work_on_known_shapes():
    flops, nbytes = roofline.fill_round_work(cons=58, tasks=801, batch=48)
    assert flops == 4 * 58 * 801 * 48
    assert nbytes == 4 * (58 * 801 + 2 * 801 * 48 + 2 * 58 * 48)
    peak = roofline.peaks("TPU v5 lite")
    t, bound = roofline.least_seconds(flops, nbytes, peak)
    assert bound == "memory"
    assert t == pytest.approx(nbytes / 819e9)


def test_a_chip_without_peaks_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
