"""Serving replica promotion and rollback (``"kind": "promote"``).

Set-up draws two train states from the seed and saves them as a chain
(``base_step`` with every unit, ``event_step`` under ``policy``), then
cold-loads the weights of ``base_step`` into the program's
``WeightService`` and warms up both swap directions and serving.  A unit
of the window swaps to the other step of the chain (promotion, then
rollback, alternately) and serves one greedy batch of ``batch`` prompts
of ``prompt_len`` tokens, ``new_tokens`` each, from the promoted params
through the program's ``prefill`` and ``decode_step``.

Compared, after the window:

- ``params_mismatch``: (leaf, unit) pairs of the params served after
  each swap that differ from the composite the target manifest holds;
- ``logit_gap``: over ``sample_batches`` served batches drawn from the
  seed, the widest gap by which a served token's logit under the plain
  float32 reference, run over the prompt and the served tokens with the
  weights the chain holds, lies below the reference's best.  The
  reference is the module the configuration names (``bench.ref.load``).
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import ref
from bench.common import composite, gaps, program, stategen
from bench.common.checksum import make_checksum, mismatches, to_host
from bench.common.kinds.resume import Driver as ResumeDriver
from bench.common.tokens import prompts


class Driver(ResumeDriver):
    def __init__(self, *, config: Dict, **kw):
        self.ref = ref.load(config)
        super().__init__(config=config, **kw)

    def setup(self):
        if self.cfg.get("ssm") is None or self.cfg.get("hybrid"):
            raise ValueError("the promote driver serves the ssm family")
        self.checksum = make_checksum(self.roots)
        self.psum = lambda p: self.checksum({"params": p})
        sums = self.write_chain(self.checksum)
        self.steps = (self.tr["base_step"], self.tr["event_step"])
        params_only = {e: {k: v for k, v in s.items()
                           if k.startswith("params/")}
                       for e, s in sums.items()}
        self.want = {self.steps[0]: params_only[0],
                     self.steps[1]: composite.expected(
                         self.tr["policy"], 1, params_only, self.units)}
        self.reader = program.reader(self.root, self.model, self.store)
        self.svc = program.weight_service(self.reader, self.model,
                                          self.steps[0])
        self.prefill, self.decode = program.serve_steps(self.model)
        self.served, self.got, self.swap_stats = [], [], []
        for warm in (1, 2):              # both directions, then serving
            self._swap()
            self._serve(warm, stream=4)
        self.served, self.got, self.swap_stats = [], [], []

    def _swap(self):
        target = self.steps[1] if self.svc.step == self.steps[0] \
            else self.steps[0]
        manifest = self.reader.manifests.load(target)
        with self.rec.span("swap"):
            self.svc.swap(manifest)
        self.swap_stats.append(dict(self.svc.last_swap_stats))
        self.got.append((target, self.psum(self.svc.current())))

    def _serve(self, index: int, stream: int = 2):
        tr = self.tr
        params = self.svc.current()
        toks = prompts(vocab_size=self.cfg["vocab_size"], batch=tr["batch"],
                       prompt_len=tr["prompt_len"], seed=self.seed,
                       index=index, stream=stream)
        with self.rec.span("serve"):
            logits, cache = self.prefill(params,
                                         {"tokens": jnp.asarray(toks)})
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out = [tok]
            for j in range(tr["new_tokens"] - 1):
                logits, cache = self.decode(
                    params, cache, {"tokens": tok[:, None],
                                    "pos": jnp.int32(tr["prompt_len"] + j)})
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                out.append(tok)
            served = np.stack(jax.device_get(out), axis=1)
        self.served.append((self.svc.step, toks, served))

    def unit(self, i: int) -> None:
        self._swap()
        self._serve(i)

    def after_window(self) -> None:
        self.records.update(swap_stats=self.swap_stats)
        self.svc = None
        self.close()

    def end_to_end(self, records: Dict) -> Dict:
        return {"promote_s": sum(records["spans"]["swap"])
                / records["units"]}

    # ------------------------------------------------------ comparison
    def chain_params(self) -> Dict[int, Dict]:
        """The weights each step of the chain serves, as bf16 params."""
        master = stategen.make_master_fn(self.specs["params"], self.roots)
        per_event = {e: jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                                     master(jax.random.fold_in(self.key, e)))
                     for e in (0, 1)}
        return {self.steps[0]: per_event[0],
                self.steps[1]: composite.assemble_params(
                    self.tr["policy"], 1, per_event, self.units)}

    def sample(self):
        rng = np.random.default_rng([self.seed, 3])
        n = min(self.tr["sample_batches"], len(self.served))
        return [self.served[i] for i in
                sorted(rng.choice(len(self.served), n, replace=False))]

    def gap_of(self, params, toks, served, mm_dtype: Optional[str] = None
               ) -> float:
        """Served tokens' gap under the reference; with ``mm_dtype`` the
        tokens are instead the ones that precision's reference puts
        first at the same positions."""
        seq = jnp.asarray(np.concatenate([toks, served], axis=1)[:, :-1])
        p = self.tr["prompt_len"]
        f32 = np.asarray(self._ref_logits(None)(params, seq)[:, p - 1:])
        if mm_dtype is None:
            return gaps.logit_gap(f32, served)
        low = self._ref_logits(mm_dtype)(params, seq)[:, p - 1:]
        return gaps.logit_gap(f32, np.asarray(jnp.argmax(low, axis=-1)))

    def _ref_logits(self, mm_dtype: Optional[str]):
        cache = self.__dict__.setdefault("_ref_fns", {})
        if mm_dtype not in cache:
            r = self.ref
            mm, sz = r.make_mm(mm_dtype), r.Sizes.of(self.cfg)
            cache[mm_dtype] = jax.jit(
                lambda params, seq: r.logits(mm, sz, params, seq))
        return cache[mm_dtype]

    def check(self):
        bad = [mismatches(to_host(s), self.want[t]) for t, s in self.got]
        chain = self.chain_params()
        gap = max(self.gap_of(chain[step], toks, served)
                  for step, toks, served in self.sample())
        return ({"params_mismatch": sum(bad), "logit_gap": gap},
                len(bad), sum(1 for b in bad if b))

    def close(self):
        if getattr(self, "reader", None) is not None:
            self.reader.close()
            self.reader = None
        self.store = None
