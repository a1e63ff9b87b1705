"""Operation and byte counts computed from shapes alone.

- ``active_params``: the 6·N·T convention's N.  Token-embedding tables are
  gathers and do not count, unless tied to the output head, where the
  table takes part in the unembedding matrix product.
- ``train_flops``: 6·N_active per trained token (forward and backward).
- ``fingerprint_bytes``: the HBM bytes a fingerprint program
  (``block_fp``, with its jitted wrapper) must move for one leaf at
  least: the leaf read once, and per 64 KiB block the checksum pair
  written, with the sum of squares for the dtypes the kernel decodes.
  The wrapper's integer view and tile padding can move more; never less.
"""
from __future__ import annotations

import math
from typing import Iterable, Sequence, Tuple

BLOCK_BYTES = 65536          # bytes fingerprinted per block
ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int32": 4,
            "uint32": 4, "int8": 1, "uint8": 1, "bool": 1}
DECODED = ("float32", "bfloat16")  # dtypes whose sum of squares is written


def active_params(leaves: Iterable[Tuple[Tuple[str, ...], Sequence[int]]],
                  *, tie_embeddings: bool) -> int:
    """``leaves``: (path, shape) of every parameter."""
    n = 0
    for path, shape in leaves:
        if path[0] == "embed" and not tie_embeddings:
            continue
        n += math.prod(shape)
    return n


def train_flops(n_active: int, tokens: int) -> float:
    return 6.0 * n_active * tokens


def fingerprint_bytes(shape: Sequence[int], dtype: str,
                      block_bytes: int = BLOCK_BYTES) -> int:
    nbytes = math.prod(shape) * ITEMSIZE[dtype]
    n_blocks = max(1, -(-nbytes // block_bytes))
    table = n_blocks * (8 + (4 if dtype in DECODED else 0))
    return nbytes + table
