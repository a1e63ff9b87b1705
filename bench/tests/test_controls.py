"""Each cell's control, at a size a test run holds, fails a number the
cell compares (the chip readings at the cells' own sizes are in
PERF.md): the reference one precision below the configuration's
(float8 matrix products for the bfloat16 step and served model), and
for the resume the program's own lossy ``int8`` codec."""
from __future__ import annotations

import tempfile

import pytest

from bench import calibrate
from bench.common import gaps, harness

SEEDS = [2**31 + 21, 22, 23]


@pytest.mark.parametrize("seed", SEEDS)
def test_float8_training_fails_a_number(seed, tiny_files):
    files = tiny_files("mamba2-370m.train-parity")
    d = calibrate.make(files, seed, tempfile.mkdtemp(), harness.Recorder())
    want = d.reference()
    got = d.numbers(d.reference(calibrate.LOW), want)
    assert any(got[k] > files["limits"][k] for k in got), got


def test_int8_codec_resume_fails(tiny_files, tmp_path):
    files = tiny_files("mamba2-370m.train-parity", "resume")
    _, checks = calibrate.windowed(files, SEEDS[0], tmp_path, 1,
                                   codec="int8")
    assert checks["restore_mismatch"] > files["limits"]["restore_mismatch"]


def test_float8_serving_fails_the_logit_gap():
    """At the served model's published widths (two of its layers, the
    whole vocabulary), on seeded weights and tokens: the token the
    float8 reference puts first lies further below the float32
    reference's best than the serving limit allows."""
    import jax
    import numpy as np

    from bench.common import program, stategen
    from bench.ref import mamba2 as ref

    from bench.tests.conftest import KINDS

    files = harness.cell_files("mamba2-370m.train-parity")
    cfg = dict(files["config"], num_layers=2)
    model = program.build(cfg)
    master = stategen.make_master_fn(program.state_specs(model)["params"],
                                     program.stacked_roots(model))
    params = jax.tree.map(lambda x: x.astype(jax.numpy.bfloat16),
                          master(stategen.seed_key(SEEDS[0])))
    seq = np.random.default_rng(SEEDS[0]).integers(
        0, cfg["vocab_size"], (4, 128)).astype(np.int32)
    sz = ref.Sizes.of(cfg)
    f32 = np.asarray(ref.logits(ref.make_mm(None), sz, params, seq))
    low = ref.logits(ref.make_mm(calibrate.LOW), sz, params, seq)
    gap = gaps.logit_gap(f32, np.asarray(low.argmax(-1)))
    assert gap > KINDS["promote"]["limits"]["logit_gap"], gap
