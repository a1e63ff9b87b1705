"""Plain references of the benchmark's configurations.

A configuration file names its reference by the descriptive key ``ref``:
``"ref": "mamba2"`` is ``bench/ref/mamba2.py``.  ``load(cfg)`` imports it
by that name; there is no registry and no default.  A reference imports
nothing of the program and takes nothing the program made.

A reference module provides:

- ``Sizes.of(cfg)``: the sizes it reads from the configuration file;
- ``make_mm(dtype)``: the matrix product ``mm(spec, a, b)`` of every
  layer, in float32 at ``Precision.HIGHEST`` (``dtype`` None) or with
  both operands rounded to ``dtype`` first (the control);
- ``logits(mm, sz, params, tokens)``: (B, S, vocab) float32 logits from
  the program's parameter tree;
- ``nll_sum(mm, sz, params, tokens)``: the summed next-token negative
  log-likelihood of a block of rows;
- ``no_decay(path, ndim)``: whether AdamW leaves the leaf at ``path``,
  of ``ndim`` dimensions per layer, out of weight decay;
- ``train(mm_dtype, sz, master, batches, opt, *, stacked_roots)``:
  ``(losses, first clipped gradient, final master)`` of plain AdamW from
  ``master``, as a call into ``bench.ref.adamw.train``.
"""
from __future__ import annotations

import importlib
import re
from types import ModuleType
from typing import Dict

_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def load(cfg: Dict) -> ModuleType:
    """The reference module ``bench.ref.<cfg["ref"]>``."""
    name = cfg.get("ref")
    if name is None:
        raise ValueError(f"configuration {cfg.get('name')!r} names no plain "
                         f"reference: add \"ref\": <module of bench/ref/>")
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"configuration {cfg.get('name')!r}: ref {name!r} "
                         f"is not a module name")
    return importlib.import_module(f"{__name__}.{name}")
