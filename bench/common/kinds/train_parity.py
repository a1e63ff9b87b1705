"""Training with selective checkpoint events (``"kind": "train_parity"``).

Set-up builds the program's donated jitted train step and its state
(drawn from the seed), runs the first ``steps_per_event`` steps through
the window's own call and feed, and saves event 0 (always every unit).
The first ``checked_steps`` of those steps are the ones the reference
follows.  A unit of the window is ``events_per_unit`` events, each
preceded by ``steps_per_event`` steps; with the parity policy a pair of
events covers the model once.

Compared, once the window has closed and the state is freed:

- ``loss_gap``: each checked step's loss against the reference's,
  relative, the worst step;
- ``grad_gap``: per-leaf norms of the first step's clipped gradient as
  the optimizer got it (m / (1 - b1) after step 1), the worst leaf;
- ``change_gap``: per-leaf norms of the master weights' change over the
  checked steps, the worst leaf, leaving out leaves whose reference
  gradient is under a thousandth of the median leaf's;
- ``save_mutation``: (leaf, unit) pairs of the live state that differ
  after a save from what they were before it, summed over every event,
  the window's included; the next step trains on what the save left, so
  a save that touched the state it read (the donation hazard of the
  save path) shows here;
- ``restore_mismatch``: (leaf, unit) pairs of the composite state the
  program restores from the window's last event that differ from the
  live state before the event that last saved the unit.

The checkpoints go to the program's RAM tier (``program.ram_store``).
The reference and its optimizer's weight decay are the module the
configuration names (``bench.ref.load``).
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, Optional

import jax

from bench import ref
from bench.common import composite, counts, gaps, program, stategen
from bench.common.checksum import (
    leaves_with_paths,
    make_checksum,
    mismatches,
    to_host,
)
from bench.common.tokens import TrainTokens


class Driver:
    def __init__(self, *, config: Dict, traffic: Dict, seed: int, root,
                 rec):
        self.cfg, self.tr, self.seed = config, traffic, seed
        self.root, self.rec = Path(root), rec
        self.ref = ref.load(config)
        self.model = program.build(config)
        self.specs = program.state_specs(self.model)
        self.units = program.units(self.model, config["name"])
        self.roots = program.stacked_roots(self.model)
        self.n_active = counts.active_params(
            [(p, s.shape)
             for p, s in leaves_with_paths(self.specs["params"])],
            tie_embeddings=config.get("tie_embeddings", False),
            routed=config.get("routed"), config=config["name"])
        self.opt = traffic["optimizer"]
        self.key = stategen.seed_key(seed)
        self.tokens = TrainTokens(vocab_size=config["vocab_size"],
                                  batch=traffic["batch"],
                                  seq_len=traffic["seq_len"], seed=seed)
        self.store = program.ram_store()
        self.mgr = None
        self.state = None
        self.records: Dict = {}

    # ---------------------------------------------------------- set-up
    def start(self, step_fn=None):
        """State at step 0, the step function, the checked steps."""
        tr = self.tr
        self.step_fn = step_fn or program.jit_train_step(
            self.model, program.train_config(self.opt))
        self.state = stategen.make_state_fn(
            self.specs["params"], self.roots, moments=False)(self.key, 0)
        self.step = 0
        self.losses = []
        b1 = self.opt["adam_b1"]
        for _ in range(tr["checked_steps"]):
            self.losses.append(self._step(span=False))
            if self.step == 1:
                self.prog_grad = gaps.to_host(gaps.leaf_norms(
                    jax.tree.map(lambda m: m / (1.0 - b1),
                                 self.state["opt"]["m"])))
        master0 = stategen.make_master_fn(self.specs["params"],
                                          self.roots)(self.key)
        self.prog_change = gaps.to_host(gaps.diff_norms(
            self.state["opt"]["master"], master0))
        del master0

    def setup(self):
        self.start()
        tr = self.tr
        self.checksum = make_checksum(self.roots)
        self.mgr = program.manager(self.root, self.model, tr["policy"],
                                   self.store, codec=tr["codec"],
                                   opt=self.opt)
        self.event, self.save_stats = 0, []
        self.event_sums, self.after_sums = {}, {}
        while self.step < tr["steps_per_event"]:
            self._step(span=False)
        self._save(span=False)
        program.warm_fingerprint_compare(self.mgr, self.state)
        self.bytes0 = program.store_bytes(self.root, self.store)
        self.window_steps = 0

    def _step(self, span: bool = True) -> float:
        batch = {"tokens": self.tokens.batch_at(self.step)}
        if span:
            with self.rec.span("train_step"):
                self.state, metrics = self.step_fn(self.state, batch)
                loss = float(metrics["loss"])
        else:
            self.state, metrics = self.step_fn(self.state, batch)
            loss = float(metrics["loss"])
        self.step += 1
        return loss

    def _save(self, span: bool = True):
        self.event_sums[self.event] = self.checksum(self.state)
        if span:
            with self.rec.span("save"):
                self.mgr.save(self.state, step=self.step)
            self.save_stats.append(dict(self.mgr.last_save_stats))
        else:
            self.mgr.save(self.state, step=self.step)
        self.after_sums[self.event] = self.checksum(self.state)
        self.event += 1

    # ---------------------------------------------------------- window
    def unit(self, i: int) -> None:
        for _ in range(self.tr["events_per_unit"]):
            for _ in range(self.tr["steps_per_event"]):
                self._step()
                self.window_steps += 1
            self._save()

    def after_window(self) -> None:
        self.bytes1 = program.store_bytes(self.root, self.store)
        self.window_events = len(self.save_stats)
        self.records.update(
            save_stats=self.save_stats,
            train_tokens=self.window_steps * self.tr["batch"]
            * self.tr["seq_len"],
            n_active=self.n_active,
            fp_traced=self.fingerprinted(range(1, 1 + self.tr[
                "events_per_unit"])))
        self.state = None

    def end_to_end(self, records: Dict) -> Dict:
        return {
            "train_tokens_per_s": records["train_tokens"]
            / records["window_s"],
            "ckpt_bytes_per_event": (self.bytes1 - self.bytes0)
            / self.window_events,
        }

    def fingerprinted(self, events) -> Dict:
        """Bytes and runs of the fingerprint program in these events: one
        run per saved unit and kind (its weights; its master, m and v),
        over every leaf of that tree."""
        nbytes, runs = 0, 0
        leaves = list(leaves_with_paths(self.specs["params"]))
        for e in events:
            for u in self.units:
                if not composite.saves(self.tr["policy"], e, u):
                    continue
                runs += 2
                for path, spec in leaves:
                    if path[:len(u.path)] != u.path:
                        continue
                    shape = spec.shape[1:] if u.index is not None \
                        else spec.shape
                    nbytes += counts.fingerprint_bytes(shape, "bfloat16")
                    nbytes += 3 * counts.fingerprint_bytes(shape, "float32")
        return {"bytes": nbytes, "runs": runs}

    # ------------------------------------------------------ comparison
    def restored_mismatch(self) -> int:
        want = composite.expected(
            self.tr["policy"], self.event - 1,
            {e: to_host(s) for e, s in self.event_sums.items()},
            self.units)
        reader = program.reader(self.root, self.model, self.store)
        try:
            state = reader.restore(self.specs)
            got = to_host(self.checksum(state))
            del state
        finally:
            reader.close()
        return mismatches(got, want)

    def reference(self, mm_dtype: Optional[str] = None,
                  rows: Optional[int] = None):
        """(losses, first-gradient leaf norms, change leaf norms) of the
        plain reference over the checked steps."""
        master0 = stategen.make_master_fn(self.specs["params"],
                                          self.roots)(self.key)
        batches = [self.tokens.batch_at(s)[:rows]
                   for s in range(self.tr["checked_steps"])]
        losses, grad, master = self.ref.train(
            mm_dtype, self.ref.Sizes.of(self.cfg), master0, batches,
            self.opt, stacked_roots=self.roots)
        out = (losses, gaps.to_host(gaps.leaf_norms(grad)),
               gaps.to_host(gaps.diff_norms(master, master0)))
        return out

    @staticmethod
    def numbers(prog, want) -> Dict[str, float]:
        """The gaps between two (losses, grad norms, change norms)."""
        keep = gaps.moved(want[1])
        return {"loss_gap": gaps.max_rel(prog[0], want[0]),
                "grad_gap": gaps.worst_leaf(prog[1], want[1]),
                "change_gap": gaps.worst_leaf(prog[2], want[2], keep)}

    def save_mutation(self) -> int:
        return sum(mismatches(to_host(self.after_sums[e]),
                              to_host(self.event_sums[e]))
                   for e in self.event_sums)

    def check(self):
        mutated = self.save_mutation()
        mism = self.restored_mismatch()
        prog = (self.losses, self.prog_grad, self.prog_change)
        checks = self.numbers(prog, self.reference())
        checks["save_mutation"] = mutated
        checks["restore_mismatch"] = mism
        return checks, self.window_events, int(mism > 0 or mutated > 0)

    def close(self):
        if self.mgr is not None:
            self.mgr.close()
            self.mgr = None
        self.store = None
