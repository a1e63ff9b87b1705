#!/usr/bin/env python
"""Serving-fleet smoke for the CI smoke tier (``scripts/check.sh smoke``).

The delta-push promotion loop end to end, across real processes:

1. train a few events under the ``parity`` policy (checkpoint at step A);
2. start TWO server processes (``python -m repro.launch.serve``) pinned
   to step A with ``--hot-swap`` — one on the process IO backend, one
   with a /dev/shm-backed block cache (both /dev/shm owners exercised);
3. resume training in this process until a newer checkpoint (step B)
   commits into the SAME store the servers are watching;
4. both servers promote A -> B by digest diff and generate — their
   ``tokens_digest`` must be bit-identical to a cold-restored reference
   serve of step B (hot-swapped weights == cold-loaded weights);
5. no ``repro-io-*`` /dev/shm segment (worker arenas, staging slots, or
   cache segments) may survive the fleet.

This is a CPU smoke: this process and both servers run JAX at once, and
an accelerator belongs to one process at a time, so every process is
forced onto the CPU.  ``python chip_smoke.py`` is the one-process check
of the same promotion path on a TPU.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

# before JAX loads here; the servers inherit it
os.environ["JAX_PLATFORMS"] = "cpu"

SRC = Path(__file__).resolve().parent.parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

TRAIN = dict(arch="llama3.2-3b", batch=4, seq_len=32, ckpt_interval=10,
             policy_name="parity", seed=0, lr=1e-3)
SERVE_ARGS = ["--batch", "2", "--prompt-len", "16", "--new-tokens", "8"]


def main() -> int:
    from repro.launch.train import train

    shm_before = set(glob.glob("/dev/shm/repro-io-*"))
    tmp = Path(tempfile.mkdtemp(prefix="serve_smoke_"))
    try:
        # one event at step 10; servers pin to it and wait for newer
        train(ckpt_dir=str(tmp), total_steps=10, **TRAIN)

        # The fleet: two replicas restoring from ONE store, pinned to the
        # current checkpoint, waiting to receive a promotion.  Pinning by
        # --from-step makes the drill race-free: whenever the newer
        # manifest lands, the next poll sees it.
        cmd = [sys.executable, "-m", "repro.launch.serve",
               "--arch", TRAIN["arch"], "--from-ckpt", str(tmp),
               "--from-step", "10", "--hot-swap", "--swap-wait", "300",
               *SERVE_ARGS]
        env = dict(os.environ)
        env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
        fleet = [
            subprocess.Popen(cmd + ["--io-backend", "process"],
                             stdout=subprocess.PIPE, cwd=SRC.parent,
                             env=env),
            subprocess.Popen(cmd + ["--cache-mb", "64", "--cache-shm"],
                             stdout=subprocess.PIPE, cwd=SRC.parent,
                             env=env),
        ]

        # The promotion: resume training, committing step 20 into the
        # store the fleet is polling.  One event only: a server that
        # starts after several commits would otherwise promote straight
        # to a step whose parity events re-saved every unit.
        train(ckpt_dir=str(tmp), total_steps=20, resume=True, **TRAIN)

        outs = []
        for p in fleet:
            raw, _ = p.communicate(timeout=600)
            assert p.returncode == 0, f"server died rc={p.returncode}"
            outs.append(json.loads(raw))

        # The invariant under test: hot-swapped weights must generate
        # bit-identically to a COLD restore of the step they promoted to.
        from repro.launch.serve import serve
        refs = {}
        for out in outs:
            step = out["served_step"]
            swap = out["swap"]
            assert swap and swap["step_from"] == 10 and step == 20, out
            # parity policy re-saves a subset of units per event: the
            # inherited entries keep their digests, so a digest-diffed
            # swap must skip at least one unit (the whole point).
            assert swap["units_skipped"] > 0, swap
            if step not in refs:
                refs[step] = serve(arch=TRAIN["arch"], from_ckpt=str(tmp),
                                   from_step=step, batch=2, prompt_len=16,
                                   new_tokens=8)
            assert out["tokens_digest"] == refs[step]["tokens_digest"], (
                "hot-swapped server output diverged from the cold-"
                f"restored reference at step {step}: "
                f"{out['tokens_digest']} vs {refs[step]['tokens_digest']}")
        cached = outs[1]
        assert cached["cache"] is not None and cached["cache"]["misses"] > 0

        leaked = set(glob.glob("/dev/shm/repro-io-*")) - shm_before
        assert not leaked, f"leaked /dev/shm segments: {sorted(leaked)}"
        print(f"serve_smoke: OK (fleet=2, "
              f"swap 10->{[o['served_step'] for o in outs]}, "
              f"swap_bytes={[o['swap']['bytes_read'] for o in outs]}, "
              f"skipped={[o['swap']['units_skipped'] for o in outs]}, "
              f"parity vs cold restore, no shm leaks)")
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
