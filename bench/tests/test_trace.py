"""The trace reduction: busy time is the union of device op intervals
inside the window, idle gaps are labelled by the innermost benchmark
span, kernel time sums the kernel's events."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench.common import trace

SAMPLE = Path(__file__).parent / "data" / "sample.xplane.pb"


def synthetic():
    ms = 1e6
    return {
        "devices": {"/device:TPU:0": [
            ("fusion.1", 0 * ms, 10 * ms),
            ("block_fp", 5 * ms, 15 * ms),      # overlaps fusion.1
            ("block_fp", 40 * ms, 50 * ms),
            ("copy.2", 90 * ms, 120 * ms),      # runs past the window
            ("late", 130 * ms, 140 * ms),       # after it
        ]},
        "spans": [
            ("bench.window", 0 * ms, 100 * ms),
            ("bench.save", 0 * ms, 60 * ms),
            ("bench.train_step", 60 * ms, 100 * ms),
            ("bench.other", 200 * ms, 300 * ms),
        ],
    }


def test_busy_is_the_union_inside_the_window():
    r = trace.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.100)
    # [0, 15] + [40, 50] + [90, 105] ms: the window widened by the
    # clock skew allowance of 5 ms on either side
    assert r["busy_s"] == pytest.approx(0.040)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["?/block_fp"] == pytest.approx(0.020)
    assert "?/late" not in ops


def test_idle_gaps_are_labelled_by_the_innermost_span():
    r = trace.reduce(synthetic())
    idle = dict(r["breakdown"]["idle_gaps"])
    # [15, 40] and [50, 60] inside save; [60, 90] inside train_step;
    # [-5, 0] before any span
    assert idle["save"] == pytest.approx(0.035)
    assert idle["train_step"] == pytest.approx(0.030)
    assert idle["idle"] == pytest.approx(0.005)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["?/copy.2"] == pytest.approx(0.015)   # clipped


def test_a_trace_without_a_window_span_is_refused():
    tr = synthetic()
    tr["spans"] = [s for s in tr["spans"] if s[0] != "bench.window"]
    with pytest.raises(ValueError):
        trace.reduce(tr)


def test_recorded_trace():
    """A trace recorded on one v5e chip (``record_trace.py``): two runs of
    the fingerprint program inside ``bench.save``, a matrix product inside
    ``bench.train_step``, then 20 ms of host sleep with the device idle."""
    r = trace.reduce(trace.load(str(SAMPLE)))
    assert r["devices_busy"] == 1
    assert 0.020 < r["window_s"] < 0.030
    assert 0 < r["busy_s"] < 0.001
    idle = dict(r["breakdown"]["idle_gaps"])
    assert idle["idle_wait"] > 0.019
    seconds, runs = trace.program_seconds(r, "jit__fingerprint")
    assert runs == 2 and 0 < seconds < 1e-4
    ops = [n for n, _ in r["breakdown"]["device_ops"]]
    assert "jit__fingerprint/%block_fp.1" in ops
