#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers on many seeds
(the lower reading) and the control's and the faults' on a few (the
upper reading).  The benchmark's own runs never run this.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 [--seconds 10]

Prints one JSON line per reading: ``{"seed", "role", "checks"}``, where
``role`` is ``program``, ``control`` (the reference computed one precision
below the configuration's, or the program's own lossy path) or a fault.

- ``train_parity``: the program's first steps against the float32
  reference the configuration names (no window); the control is that
  reference with every matrix product's operands rounded to float8
  (e4m3); the fault ``half_batch`` is the reference over half of each
  batch's rows.
- ``resume``: the control saves the chain with the program's lossy
  ``int8`` codec and resumes once.
- ``promote``: the program's numbers come from a short window; the
  control reads, at the same positions of the same served sequences, the
  gap of the token the float8 reference puts first.
"""
from __future__ import annotations

import argparse
import json
import logging
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

LOW = "float8_e4m3fn"


def emit(seed, role, checks) -> None:
    print(json.dumps({"seed": seed, "role": role, "checks": checks}),
          flush=True)


def make(files, seed, work, rec, **traffic):
    from bench.common import harness
    tr = dict(files["traffic"], **traffic)
    return harness.driver(tr["kind"]).Driver(
        config=files["config"], traffic=tr, seed=seed,
        root=Path(work) / f"ckpt-{seed}-{len(traffic)}", rec=rec)


def leaf_gaps(d, seed, role, got, want) -> None:
    """Each leaf's gradient and change gaps, for finding which leaves
    set the worst-leaf numbers."""
    import numpy as np

    from bench.common.checksum import leaves_with_paths
    names = ["/".join(p) for p, _ in leaves_with_paths(d.specs["params"])]
    out = {}
    for key, i in (("grad", 1), ("change", 2)):
        g, w = np.asarray(got[i]), np.asarray(want[i])
        rel = np.abs(g - w) / np.maximum(w, np.median(w))
        out[key] = {n: float(f"{r:.3g}") for n, r in zip(names, rel)}
    emit(seed, role + ".leaves", out)


def train(files, seeds, control_seeds, work) -> None:
    from bench.common import harness
    rec = harness.Recorder()
    step_fn = None
    for seed in seeds:
        d = make(files, seed, work, rec)
        d.start(step_fn)
        step_fn = d.step_fn
        prog = (d.losses, d.prog_grad, d.prog_change)
        d.state = None
        want = d.reference()
        emit(seed, "program", d.numbers(prog, want))
        leaf_gaps(d, seed, "program", prog, want)
    rows = files["traffic"]["batch"] // 2
    for seed in control_seeds:
        d = make(files, seed, work, rec)
        want = d.reference()
        low = d.reference(LOW)
        emit(seed, "control", d.numbers(low, want))
        leaf_gaps(d, seed, "control", low, want)
        emit(seed, "half_batch", d.numbers(d.reference(rows=rows), want))


def windowed(files, seed, work, seconds, **traffic):
    from bench.common import harness
    rec = harness.Recorder()
    d = make(files, seed, work, rec, **traffic)
    try:
        d.setup()
        harness.window(d.unit, seconds=seconds)
        d.after_window()
        return d, d.check()[0]
    finally:
        d.close()


def resume(files, seeds, control_seeds, work, seconds) -> None:
    for seed in seeds:
        emit(seed, "program", windowed(files, seed, work, seconds)[1])
        shutil.rmtree(work, ignore_errors=True)
    for seed in control_seeds:
        emit(seed, "control", windowed(files, seed, work, seconds,
                                       codec="int8")[1])
        shutil.rmtree(work, ignore_errors=True)


def promote(files, seeds, control_seeds, work, seconds) -> None:
    for seed in sorted(set(seeds) | set(control_seeds)):
        d, checks = windowed(files, seed, work, seconds)
        if seed in seeds:
            emit(seed, "program", checks)
        if seed in control_seeds:
            chain = d.chain_params()
            gap = max(d.gap_of(chain[step], toks, served, LOW)
                      for step, toks, served in d.sample())
            emit(seed, "control", {"logit_gap": gap})
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING)
    from bench.common import device, harness, program
    files = harness.cell_files(args.workload)
    program.use_compile_cache()
    device.require_tpu(files["workload"]["chips"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    work = tempfile.mkdtemp(prefix="bench-cal-")
    t0 = time.time()
    try:
        kind = files["traffic"]["kind"]
        if kind == "train_parity":
            train(files, seeds, controls, work)
        elif kind == "resume":
            resume(files, seeds, controls, work, args.seconds)
        else:
            promote(files, seeds, controls, work, args.seconds)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(f"calibrate: {time.time() - t0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
