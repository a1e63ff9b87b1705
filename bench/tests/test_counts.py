"""Operation and byte counts on shapes worked out by hand."""
from __future__ import annotations

import pytest

from bench.common import counts
from bench.common.peaks import peaks_for


def test_six_n_t():
    leaves = [(("embed", "w"), (100, 8)), (("blocks", "w"), (2, 8, 8)),
              (("final_norm", "scale"), (8,))]
    assert counts.active_params(leaves, tie_embeddings=True) == 800 + 128 + 8
    assert counts.active_params(leaves, tie_embeddings=False) == 128 + 8
    assert counts.train_flops(936, 1000) == 6 * 936 * 1000


@pytest.mark.parametrize("shape,dtype,want", [
    # 1 KiB of f32: one 64 KiB block; the leaf, one checksum pair (8 B)
    # and one sum of squares (4 B)
    ((256,), "float32", 1024 + 12),
    # 1024 x 1024 bf16 = 2 MiB = 32 blocks
    ((1024, 1024), "bfloat16", 2 * 2**20 + 32 * 12),
    # 10 blocks of f32 and one element -> 11 blocks
    ((10 * 16384 + 1,), "float32", (10 * 16384 + 1) * 4 + 11 * 12),
    # int32 is not decoded: no sum of squares written
    ((16384,), "int32", 65536 + 8),
])
def test_fingerprint_bytes(shape, dtype, want):
    assert counts.fingerprint_bytes(shape, dtype) == want


def test_peaks_are_keyed_by_device_kind():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")
