"""The program broken underneath the timed path comes out not correct,
once for each fault the cells can have: a training step that returns
its state unchanged, half of the batch left out, a save that changes
the state it reads, a restored answer altered, a served token altered
(tiny sizes, on the CPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench.common import program
from bench.tests.test_kinds import CELLS, run


def test_step_returning_its_state_unchanged_is_caught(tiny_files,
                                                      monkeypatch):
    real = program.jit_train_step

    def frozen(model, tcfg):
        step = jax.jit(real(model, tcfg).__wrapped__)
        return lambda state, batch: (state, step(state, batch)[1])

    monkeypatch.setattr(program, "jit_train_step", frozen)
    out = run(tiny_files(CELLS[0]))
    assert not out["correct"]
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_caught(tiny_files, monkeypatch):
    real = program.jit_train_step

    def halved(model, tcfg):
        step = real(model, tcfg)
        return lambda state, batch: step(
            state, {"tokens": batch["tokens"][:len(batch["tokens"]) // 2]})

    monkeypatch.setattr(program, "jit_train_step", halved)
    assert not run(tiny_files(CELLS[0]))["correct"]


def test_a_save_that_changes_the_live_state_is_caught(tiny_files,
                                                     monkeypatch):
    """A save that leaves the state it was handed altered, as one that
    wrote into a donated buffer would: the steps after it train on the
    altered state, and the restore matches what was saved."""
    real = program.manager

    def altering(*a, **k):
        mgr = real(*a, **k)
        save = mgr.save

        def altered(state, **kw):
            out = save(state, **kw)
            w = state["params"]["embed"]["w"]
            state["params"]["embed"]["w"] = w.at[0, 0].add(1)
            return out

        mgr.save = altered
        return mgr

    monkeypatch.setattr(program, "manager", altering)
    out = run(tiny_files(CELLS[0]))
    assert not out["correct"]
    assert out["checks"]["save_mutation"]["value"] > 0


def test_a_restored_answer_altered_is_caught(tiny_files, monkeypatch):
    real = program.reader

    def altering(root, model, store):
        mgr = real(root, model, store)
        restore = mgr.restore

        def altered(*a, **k):
            st = restore(*a, **k)
            w = st["params"]["embed"]["w"]
            st["params"]["embed"]["w"] = w.at[0, 0].add(1)
            return st

        mgr.restore = altered
        return mgr

    monkeypatch.setattr(program, "reader", altering)
    out = run(tiny_files(CELLS[0], "resume"))
    assert not out["correct"] and out["failed"] == out["attempted"]


def test_a_served_token_altered_is_caught(tiny_files, monkeypatch):
    real = program.serve_steps

    def altering(model):
        prefill, decode = real(model)

        def first_token_zero(params, batch):
            logits, cache = prefill(params, batch)
            return logits.at[:, 0].set(jnp.max(logits) + 1.0), cache

        return first_token_zero, decode

    monkeypatch.setattr(program, "serve_steps", altering)
    out = run(tiny_files(CELLS[0], "serve-promote"))
    assert not out["correct"]
    assert out["checks"]["params_mismatch"]["value"] == 0
