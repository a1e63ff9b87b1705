"""A configuration, a traffic mix and a per-layer metric are added with
new files and new BENCHMARK.json entries alone: the harness of a copy of
the benchmark finds and runs them without an edit to any file it had."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from bench.common import harness

#: the hybrid family, which no cell runs yet, under the resume mix
TINY_ZAMBA = {
    "name": "tiny-dummy", "arch": "zamba2-2.7b", "reduced": [],
    "num_layers": 4, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
    "head_dim": 16, "d_ff": 128, "vocab_size": 256,
    "ssm": {"state_dim": 16, "head_dim": 16, "expand": 2,
            "conv_kernel": 4, "chunk_size": 32, "ngroups": 1},
    "hybrid": {"shared_period": 2, "shared_d_ff": 128}}

SCRIPT = """
import json, sys, time
sys.path.insert(0, {root!r})
from bench.common import harness
out = harness.run_cell("tiny-dummy.resume-dummy", seed=3, seconds=1,
                       trace=True, t_start=time.time())
print(json.dumps(out))
"""


def test_new_config_traffic_and_metric_by_files_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.CHECKOUT / "BENCHMARK.json", root)
    os.symlink(harness.CHECKOUT / "src", root / "src")
    b = root / "bench"
    (b / "configs" / "tiny-dummy.json").write_text(json.dumps(TINY_ZAMBA))
    (b / "traffic" / "resume-dummy.json").write_text(json.dumps(
        {"kind": "resume", "base_step": 1, "event_step": 2,
         "policy": "parity", "codec": "auto"}))
    (b / "limits" / "tiny-dummy.resume-dummy.json").write_text(
        json.dumps({"restore_mismatch": 0}))
    (b / "metrics" / "dummy_resumes.py").write_text(
        "def read(rec):\n    return float(rec['units'])\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-dummy", "source": "test",
                            "file": "bench/configs/tiny-dummy.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-dummy.resume-dummy",
                              "config": "tiny-dummy",
                              "traffic": "resume-dummy", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "resume_s", "unit": "s",
                               "better": "lower", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["tiny-dummy.resume-dummy"]})
    spec["per_layer"].append({"name": "dummy_resumes", "unit": "resumes",
                              "better": "higher", "source": "host_clock",
                              "layer": "restore engine", "moves": "resume_s",
                              "workloads": ["tiny-dummy.resume-dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c",
                           SCRIPT.format(root=str(root))],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"]
    assert out["metrics"]["dummy_resumes"]["value"] >= 1
