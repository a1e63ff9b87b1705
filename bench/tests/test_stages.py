"""The program's save-path stages as the benchmark reads them: idle gaps
labelled by the program span open on the window's thread, the per-stage
readers of ``bench/metrics/``, and ``bench/stage_trace.py`` end to end
on the CPU at a tiny size."""
from __future__ import annotations

import copy
from pathlib import Path

import pytest

from bench.common import harness, stages, trace
from bench.stage_trace import STAGE_METRICS, measure

SAMPLE = Path(__file__).parent / "data" / "sample.xplane.pb"
MS = 1e6
WIN = ("/host:CPU", 0)
WRITER = ("/host:CPU", 3)


def synthetic():
    """Device busy [0, 10] and [70, 80] ms of a 100 ms window; ``save``
    over [0, 60], ``train_step`` over [60, 100]; on the window's thread
    the save's stages, on a writer thread an encode over the whole save."""
    return {
        "devices": {"/device:TPU:0": [("fusion.1", 0 * MS, 10 * MS),
                                      ("fusion.2", 70 * MS, 80 * MS)]},
        "spans": [("bench.window", 0 * MS, 100 * MS),
                  ("bench.save", 0 * MS, 60 * MS),
                  ("bench.train_step", 60 * MS, 100 * MS)],
        "program": [("ckpt.save", 1 * MS, 59 * MS, WIN),
                    ("ckpt.save.snapshot", 2 * MS, 40 * MS, WIN),
                    ("ckpt.save.d2h", 20 * MS, 30 * MS, WIN),
                    ("ckpt.save.drain", 40 * MS, 55 * MS, WIN),
                    ("ckpt.write.encode", 0 * MS, 60 * MS, WRITER)],
        "window_line": WIN,
    }


def test_a_stage_on_the_window_thread_splits_the_idle_under_save():
    idle = dict(stages.idle_gaps(synthetic()))
    # the idle under save, [10, 60] ms, part by part
    assert idle["save/ckpt.save.snapshot"] == pytest.approx(0.020)
    assert idle["save/ckpt.save.d2h"] == pytest.approx(0.010)
    assert idle["save/ckpt.save.drain"] == pytest.approx(0.015)
    assert idle["save/ckpt.save"] == pytest.approx(0.004)    # [55, 59]
    assert idle["save"] == pytest.approx(0.001)              # [59, 60]
    assert idle["train_step"] == pytest.approx(0.030)
    assert idle["idle"] == pytest.approx(0.010)
    # the writer's encode covers the whole save and labels none of it
    assert not any("ckpt.write" in n for n in idle)


def test_stages_leave_each_benchmark_span_its_idle():
    tr = synthetic()
    plain = dict(trace.reduce(tr)["breakdown"]["idle_gaps"])
    split = stages.idle_gaps(tr)
    for bench_span, seconds in plain.items():
        assert sum(s for n, s in split
                   if n.split("/", 1)[0] == bench_span) \
            == pytest.approx(seconds)
    # a trace without program spans reduces label for label as before
    del tr["program"]
    assert stages.idle_gaps(tr) == trace.reduce(tr)["breakdown"]["idle_gaps"]


def test_recorded_trace_reduces_as_before():
    tr = stages.load(str(SAMPLE))
    assert tr["program"] == [] and tr["window_line"] is not None
    assert stages.idle_gaps(tr) == \
        trace.reduce(trace.load(str(SAMPLE)))["breakdown"]["idle_gaps"]


STAGE_OF = {"save_fingerprint_s": "ckpt.save.fingerprint",
            "save_d2h_s": "ckpt.save.d2h",
            "save_pack_s": "ckpt.save.pack",
            "save_drain_s": "ckpt.save.drain",
            "save_commit_s": "ckpt.save.commit",
            "writer_encode_s": "ckpt.write.encode",
            "writer_store_s": "ckpt.write.store"}


def fake_record():
    names = list(STAGE_OF.values())
    return {"save_stats": [
        {"d2h_bytes": 600, "stages": {n: 1.0 + i for i, n in
                                      enumerate(names)}},
        {"d2h_bytes": 200, "stages": {n: 3.0 + i for i, n in
                                      enumerate(names)}}]}


@pytest.mark.parametrize("metric", STAGE_METRICS)
def test_stage_readers_take_the_mean_per_event(metric):
    read = harness.metric_reader(metric).read
    rec = fake_record()
    if metric == "save_d2h_bytes_per_s":
        # 400 bytes and 2.0 + 1 = 3.0 s of ckpt.save.d2h per event
        assert read(rec) == pytest.approx(400 / 3.0)
    else:
        i = list(STAGE_OF).index(metric)
        assert read(rec) == pytest.approx(2.0 + i)
    stage = STAGE_OF.get(metric, "ckpt.save.d2h")
    missing = copy.deepcopy(rec)
    for s in missing["save_stats"]:
        del s["stages"][stage]
    assert read(missing) is None
    # a program without the spans (the stats have no ``stages``)
    for s in missing["save_stats"]:
        del s["stages"]
    assert read(missing) is None
    assert read({"save_stats": []}) is None


def test_stage_trace_on_the_cpu(tiny_files):
    """``bench/stage_trace.py``'s measurement end to end at a tiny size:
    the CPU trace has no device plane, so no idle gap is labelled."""
    rows = measure(tiny_files("mamba2-370m.train-parity"),
                   seed=2**31 + 5, units=2)
    assert [r["traced"] for r in rows] == [False, True]
    for r in rows:
        assert all(v is not None and v > 0 for v in r["metrics"].values())
        assert 0 < r["save_children_share"] <= 1
        assert 0 < r["snapshot_children_share"] <= 1
        assert 0 < r["ckpt_save_s"] <= r["save_call_s"]
        assert r["d2h_calls"] > 0
    assert rows[1]["idle_gaps"] == []
    assert rows[1]["save_idle_unlabelled_share"] is None
