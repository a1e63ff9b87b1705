"""A configuration, its plain reference, a traffic mix and a per-layer
metric are added with new files and new BENCHMARK.json entries alone: the
harness of a copy of the benchmark finds and runs them without an edit to
any file it had."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.common import harness
from bench.tests.conftest import KINDS, TINY, TINY_TRAFFIC

#: the hybrid family, which no cell runs yet, under the resume mix
TINY_ZAMBA = {
    "name": "tiny-dummy", "arch": "zamba2-2.7b", "reduced": [],
    "num_layers": 4, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
    "head_dim": 16, "d_ff": 128, "vocab_size": 256,
    "ssm": {"state_dim": 16, "head_dim": 16, "expand": 2,
            "conv_kernel": 4, "chunk_size": 32, "ngroups": 1},
    "hybrid": {"shared_period": 2, "shared_d_ff": 128}}

#: a configuration that names a reference of its own, ``tiny_copy``
TINY_COPY = dict(TINY["mamba2-370m"], name="tiny-copy", ref="tiny_copy")

#: what an altered copy of the reference appends to itself.  Training
#: compares losses: logits doubled move the tiny model's by 0.8-1.5%
#: relative (by 1.05, only 0.03-0.06%, about the limit).  A served token
#: is judged by the reference's best token, which no scale moves, so the
#: served copy shifts every logit to the next token of the vocabulary.
ALTERED = {
    "train_parity": "\n_logits = logits\n\n\n"
                    "def logits(mm, sz, params, tokens):\n"
                    "    return 2.0 * _logits(mm, sz, params, tokens)\n",
    "promote": "\n_logits = logits\n\n\n"
               "def logits(mm, sz, params, tokens):\n"
               "    return jnp.roll(_logits(mm, sz, params, tokens), 1, -1)\n",
}
#: the kind's mix and the number the altered copy fails
MIX = {"train_parity": ("train-parity", "loss_gap"),
       "promote": ("serve-promote", "logit_gap")}

SCRIPT = """
import json, sys, time
sys.path.insert(0, {root!r})
from bench.common import harness
out = harness.run_cell({workload!r}, seed=3, seconds=1,
                       trace={trace!r}, t_start=time.time())
print(json.dumps(out))
"""


def checkout(tmp_path):
    """A copy of the benchmark and its BENCHMARK.json over the program."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.CHECKOUT / "BENCHMARK.json", root)
    os.symlink(harness.CHECKOUT / "src", root / "src")
    return root


def add_cell(root, cfg, traffic_name, traffic, limits, *, end_to_end=(),
             per_layer=()):
    """Write the cell's files and entries; returns the workload's name.
    ``end_to_end`` and ``per_layer``: metric entries, or the names of
    entries already there that the cell reports too."""
    b = root / "bench"
    workload = f"{cfg['name']}.{traffic_name}"
    (b / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    (b / "traffic" / f"{traffic_name}.json").write_text(json.dumps(traffic))
    (b / "limits" / f"{workload}.json").write_text(json.dumps(limits))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": cfg["name"], "source": "test",
                            "file": f"bench/configs/{cfg['name']}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": workload, "config": cfg["name"],
                              "traffic": traffic_name, "chips": 1,
                              "why": "test"})
    for key, metrics in (("end_to_end", end_to_end),
                         ("per_layer", per_layer)):
        have = {m["name"]: m for m in spec[key]}
        for m in metrics:
            if isinstance(m, str):
                have[m]["workloads"].append(workload)
            else:
                spec[key].append(dict(m, workloads=[workload]))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return workload


def run_in(root, workload, trace=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(root=str(root),
                                             workload=workload,
                                             trace=trace)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_new_config_traffic_and_metric_by_files_alone(tmp_path):
    root = checkout(tmp_path)
    (root / "bench" / "metrics" / "dummy_resumes.py").write_text(
        "def read(rec):\n    return float(rec['units'])\n")
    workload = add_cell(
        root, TINY_ZAMBA, "resume-dummy",
        {"kind": "resume", "base_step": 1, "event_step": 2,
         "policy": "parity", "codec": "auto"},
        {"restore_mismatch": 0},
        end_to_end=[{"name": "resume_s", "unit": "s", "better": "lower",
                     "bound": 0.25, "source": "host_clock"}],
        per_layer=[{"name": "dummy_resumes", "unit": "resumes",
                    "better": "higher", "source": "host_clock",
                    "layer": "restore engine", "moves": "resume_s"}])
    out = run_in(root, workload, trace=True)
    assert out["correct"]
    assert out["metrics"]["dummy_resumes"]["value"] >= 1


def test_config_of_unstacked_and_stacked_blocks_by_files_alone(tmp_path):
    """A DeepSeek-V2-Lite cut whose first block is unstacked and the rest
    rows of one stack resumes its parity chain as the composite rule by
    depth says it must (by stacked row, blocks 1-3 swapped parity and
    block 0 had none)."""
    root = checkout(tmp_path)
    workload = add_cell(
        root, TINY["deepseek-v2-lite-16b"], "resume-parity",
        {"kind": "resume", "base_step": 1, "event_step": 2,
         "policy": "parity", "codec": "auto"},
        {"restore_mismatch": 0},
        end_to_end=[{"name": "resume_s", "unit": "s", "better": "lower",
                     "bound": 0.25, "source": "host_clock"}])
    out = run_in(root, workload)
    assert out["correct"], out["checks"]
    assert out["checks"]["restore_mismatch"]["value"] == 0
    assert out["attempted"] >= 1 and out["failed"] == 0


@pytest.mark.parametrize("altered", [False, True],
                         ids=["faithful", "altered"])
@pytest.mark.parametrize("kind", ["train_parity", "promote"])
def test_new_config_with_its_own_reference_by_files_alone(kind, altered,
                                                          tmp_path):
    """A cell compared against ``bench/ref/tiny_copy.py``, a text copy of
    the Mamba2 reference that only its configuration names: the faithful
    copy comes out correct, the altered one fails its number."""
    mix, number = MIX[kind]
    root = checkout(tmp_path)
    text = (harness.BENCH / "ref" / "mamba2.py").read_text()
    (root / "bench" / "ref" / "tiny_copy.py").write_text(
        text + (ALTERED[kind] if altered else ""))
    traffic = dict(harness.read_json(harness.BENCH / "traffic"
                                     / f"{mix}.json"), **TINY_TRAFFIC[kind])
    if kind == "train_parity":
        limits = harness.read_json(harness.BENCH / "limits"
                                   / "mamba2-370m.train-parity.json")
        e2e = ["train_tokens_per_s", "ckpt_bytes_per_event"]
    else:
        limits = KINDS[kind]["limits"]
        e2e = [dict(m, better="lower", bound=0.25, source="host_clock")
               for m in KINDS[kind]["end_to_end"]]
    workload = add_cell(root, TINY_COPY, f"{mix}-tiny", traffic, limits,
                        end_to_end=e2e)
    out = run_in(root, workload)
    check = out["checks"][number]
    if altered:
        assert not out["correct"]
        assert check["value"] > check["limit"], check
    else:
        assert out["correct"], out["checks"]
