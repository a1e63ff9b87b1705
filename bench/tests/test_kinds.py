"""Each traffic kind's whole run (set-up, window, comparison) on the CPU
at a tiny size, called past the command line's look for a chip: a sound
program comes out correct (the faults are in ``test_faults.py``)."""
from __future__ import annotations

import time

import pytest

from bench.common import harness

CELLS = ["mamba2-370m.train-parity"]
#: (cell, mix): every mix of ``bench/traffic/``, the ones no cell runs
#: yet under the training cell's configuration
MIXES = [(CELLS[0], None), (CELLS[0], "resume"),
         (CELLS[0], "serve-promote")]


def run(files, trace=False):
    return harness.run_cell(files["workload"]["name"], seed=2**31 + 11,
                            seconds=2, trace=trace, t_start=time.time(),
                            files=files)


@pytest.mark.parametrize("cell,mix", MIXES)
def test_sound_run_is_correct(cell, mix, tiny_files):
    out = run(tiny_files(cell, mix))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["compiles_in_window"] == 0
    for m in out["metrics"].values():
        assert m["value"] > 0
    assert list(out)[-1] == "checks"


def test_traced_training_run_reads_its_per_layer_metrics(tiny_files,
                                                         monkeypatch):
    """The traced path end to end.  The CPU has no peak in the table (a
    chip run refuses it), so the test lends it the v5e's; its trace has
    no TPU plane, so the fingerprint program's roofline reads nothing."""
    from bench.common import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    out = run(tiny_files(CELLS[0]), trace=True)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_step_s", "train_mfu",
                                   "save_call_s", "save_snapshot_s",
                                   "device_idle_share.train"}
    assert out["device"]["window_s"] > 0
    assert len(out["breakdown"]["idle_gaps"]) <= 10


@pytest.mark.parametrize("mix", [None, "serve-promote"])
def test_a_compared_kind_needs_the_reference_its_configuration_names(
        mix, tiny_files):
    """A kind that compares against a plain reference takes the one the
    configuration names, and refuses a configuration that names none
    (the resume kind compares against none and needs none)."""
    files = tiny_files(CELLS[0], mix)
    del files["config"]["ref"]
    with pytest.raises(ValueError, match="'tiny-mamba2' names no plain"):
        run(files)


@pytest.mark.parametrize("name", ["../mamba2", "mamba2.py", "bench.ref", 3])
def test_a_reference_is_named_as_a_module_of_bench_ref(name):
    """``ref`` names a module of ``bench/ref/`` and nothing else: no path,
    no file name, no dotted name."""
    from bench import ref

    with pytest.raises(ValueError, match="is not a module name"):
        ref.load({"name": "tiny", "ref": name})
    assert ref.load({"name": "tiny", "ref": "mamba2"}).__name__ == \
        "bench.ref.mamba2"
