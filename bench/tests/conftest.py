"""Shared fixtures of the harness's CPU tests: tiny configurations of the
benchmark's architectures and a cell's files pointed at them.

The tiny mamba2 is wide enough (d_model 128, 4 x 128 tokens) that a
sound run's training gaps sit well inside the cell's limits, which were
set from full-size readings on the chip; at d_model 64 the worst-leaf
change gap of a sound run reaches them.
"""
from __future__ import annotations

import copy
import sys
from pathlib import Path

import pytest

CHECKOUT = Path(__file__).resolve().parents[2]
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))

TINY = {
    "mamba2-370m": {
        "name": "tiny-mamba2", "arch": "mamba2-370m", "ref": "mamba2",
        "reduced": [],
        "num_layers": 2, "d_model": 128, "vocab_size": 512,
        "tie_embeddings": True, "norm_eps": 1e-5,
        "ssm": {"state_dim": 16, "head_dim": 16, "expand": 2,
                "conv_kernel": 4, "chunk_size": 32, "ngroups": 1}},
    # blocks in two places: block_000 the unstacked dense layer, block_001
    # to block_003 rows 0-2 of the stacked MoE layers
    "deepseek-v2-lite-16b": {
        "name": "tiny-deepseek", "arch": "deepseek-v2-lite-16b",
        "reduced": [],
        "num_layers": 4, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
        "head_dim": 24, "d_ff": 128, "vocab_size": 256,
        "moe": {"num_experts": 4, "top_k": 2, "d_ff_expert": 32,
                "num_shared_experts": 1, "first_k_dense": 1,
                "d_ff_first_dense": 128, "capacity_factor": 8.0},
        "mla": {"kv_lora_rank": 32, "q_lora_rank": 0,
                "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                "v_head_dim": 16},
        "routed": {"paths": ["blocks/moe/we_gate", "blocks/moe/we_up",
                             "blocks/moe/we_down"],
                   "experts": 4, "per_token": 2}},
}

#: traffic sizes cut for the CPU (everything else as the cell states it)
TINY_TRAFFIC = {"train_parity": {"batch": 4, "seq_len": 128},
                "resume": {}, "promote": {"prompt_len": 16, "new_tokens": 4,
                                          "sample_batches": 3}}


#: limits and end-to-end metrics of the mixes that no cell runs yet (the
#: serving limit is the one a 16-layer Mamba2-370m cell was held to)
KINDS = {"resume": {"limits": {"restore_mismatch": 0},
                    "end_to_end": [{"name": "resume_s", "unit": "s"}]},
         "promote": {"limits": {"params_mismatch": 0, "logit_gap": 0.2},
                     "end_to_end": [{"name": "promote_s", "unit": "s"}]}}


@pytest.fixture
def tiny_files():
    """``tiny_files(workload)``: the cell's files with a tiny config;
    ``tiny_files(workload, traffic)`` the same configuration under
    another mix of ``bench/traffic/``."""
    from bench.common import harness

    def make(workload: str, traffic: str = None):
        files = copy.deepcopy(harness.cell_files(workload))
        files["config"] = copy.deepcopy(TINY[files["workload"]["config"]])
        if traffic is not None:
            files["traffic"] = harness.read_json(
                harness.BENCH / "traffic" / f"{traffic}.json")
            kind = KINDS[files["traffic"]["kind"]]
            files["limits"] = dict(kind["limits"])
            files["end_to_end"] = kind["end_to_end"] + [
                m for m in files["end_to_end"] if m["name"] == "setup_s"]
            files["per_layer"] = []
            files["workload"] = dict(files["workload"], traffic=traffic)
        files["traffic"].update(TINY_TRAFFIC[files["traffic"]["kind"]])
        return files

    return make
