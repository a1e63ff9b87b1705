"""Operation and byte counts computed from shapes alone.

- ``active_params``: the 6·N·T convention's N.  Token-embedding tables are
  gathers and do not count, unless tied to the output head, where the
  table takes part in the unembedding matrix product.  A routed expert's
  leaves count at the published share of them a token passes through,
  experts per token over routed experts, whatever number of experts the
  chip holds.
- ``train_flops``: 6·N_active per trained token (forward and backward).
- ``fingerprint_bytes``: the HBM bytes a fingerprint program
  (``block_fp``, with its jitted wrapper) must move for one leaf at
  least: the leaf read once, and per 64 KiB block the checksum pair
  written, with the sum of squares for the dtypes the kernel decodes.
  The wrapper's integer view and tile padding can move more; never less.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Optional, Sequence, Tuple

BLOCK_BYTES = 65536          # bytes fingerprinted per block
ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int32": 4,
            "uint32": 4, "int8": 1, "uint8": 1, "bool": 1}
DECODED = ("float32", "bfloat16")  # dtypes whose sum of squares is written


def active_params(leaves: Iterable[Tuple[Tuple[str, ...], Sequence[int]]],
                  *, tie_embeddings: bool, routed: Optional[Dict] = None,
                  config: str = "the configuration") -> int:
    """``leaves``: (path, shape) of every parameter.  ``routed``: the
    configuration's descriptive key of that name, ``{"paths": ['/'-joined
    path prefixes of the routed experts' leaves], "experts": <published
    routed experts>, "per_token": <published experts per token>}``; a
    leaf under one of ``paths`` counts at ``per_token / experts`` of its
    size (the routed sum rounded down to a whole parameter)."""
    prefixes = []
    if routed is not None:
        prefixes = [tuple(p.split("/")) for p in routed["paths"]]
        if not prefixes or not 0 < routed["per_token"] <= routed["experts"]:
            raise ValueError(f"{config}: routed needs paths and 0 < "
                             f"per_token <= experts, got {routed}")
    dense, sparse, hit = 0, 0, set()
    for path, shape in leaves:
        if path[0] == "embed" and not tie_embeddings:
            continue
        under = [p for p in prefixes if tuple(path[:len(p)]) == p]
        if under:
            sparse += math.prod(shape)
            hit.update(under)
        else:
            dense += math.prod(shape)
    missing = ["/".join(p) for p in prefixes if p not in hit]
    if missing:
        raise ValueError(f"{config}: routed paths {missing} match no "
                         f"parameter")
    if routed is None:
        return dense
    return dense + sparse * routed["per_token"] // routed["experts"]


def train_flops(n_active: int, tokens: int) -> float:
    return 6.0 * n_active * tokens


def fingerprint_bytes(shape: Sequence[int], dtype: str,
                      block_bytes: int = BLOCK_BYTES) -> int:
    nbytes = math.prod(shape) * ITEMSIZE[dtype]
    n_blocks = max(1, -(-nbytes // block_bytes))
    table = n_blocks * (8 + (4 if dtype in DECODED else 0))
    return nbytes + table
