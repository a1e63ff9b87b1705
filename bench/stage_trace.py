#!/usr/bin/env python3
"""Where a cell's save time goes, stage by stage, and what the profiler
costs it.

    python3 bench/stage_trace.py --workload <cell> --seed <n> [--units 3]

Sets the cell up as ``bench/run.py`` does, then runs whole units of work
one at a time with the profiler on for the second unit only, and prints
one JSON object as the last line of standard output: per unit,
``save_call_s`` (the benchmark span ``save``, mean per event), the
per-stage readers of ``bench/metrics/`` (``STAGE_METRICS``), and the
shares of ``ckpt.save`` and ``ckpt.save.snapshot`` that their
train-thread children cover; for the traced unit, the device's idle gaps
labelled by program stage (``bench.common.stages.idle_gaps``) and the
share of the idle under ``save`` that no stage labels.  It compares
nothing with the reference: ``bench/run.py`` decides ``correct``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
import tempfile
from pathlib import Path
from typing import Dict, List

CHECKOUT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(CHECKOUT))

STAGE_METRICS = ["save_fingerprint_s", "save_d2h_s", "save_d2h_bytes_per_s",
                 "save_pack_s", "save_drain_s", "save_commit_s",
                 "writer_encode_s", "writer_store_s"]
SAVE_CHILDREN = ["ckpt.save.snapshot", "ckpt.save.drain",
                 "ckpt.save.commit"]
SNAPSHOT_CHILDREN = ["ckpt.save.fingerprint", "ckpt.save.d2h",
                     "ckpt.save.pack"]


def _share(stats: List[Dict], parent: str, children: List[str]) -> float:
    return sum(s["stages"].get(c, 0.0) for s in stats for c in children) \
        / sum(s["stages"][parent] for s in stats)


def measure(files: Dict, *, seed: int, units: int = 3,
            traced_unit: int = 1) -> List[Dict]:
    from bench.common import harness, stages, trace

    work = Path(tempfile.mkdtemp(prefix="bench-stages-"))
    rec = harness.Recorder()
    d = harness.driver(files["traffic"]["kind"]).Driver(
        config=files["config"], traffic=files["traffic"], seed=seed,
        root=work / "ckpt", rec=rec)
    rows = []
    try:
        d.setup()
        for i in range(units):
            rec.spans.clear()
            first = len(d.save_stats)
            trace_dir = str(work / f"trace{i}") if i == traced_unit \
                else None
            harness.window(d.unit, seconds=0, trace_dir=trace_dir)
            stats = d.save_stats[first:]
            records = {"save_stats": stats}
            row = {
                "traced": trace_dir is not None,
                "save_call_s": statistics.fmean(rec.spans["save"]),
                "ckpt_save_s": statistics.fmean(
                    s["stages"]["ckpt.save"] for s in stats),
                "metrics": {m: harness.metric_reader(m).read(records)
                            for m in STAGE_METRICS},
                "save_children_share": _share(stats, "ckpt.save",
                                              SAVE_CHILDREN),
                "snapshot_children_share": _share(
                    stats, "ckpt.save.snapshot", SNAPSHOT_CHILDREN),
                "d2h_calls": statistics.fmean(s["d2h_calls"]
                                              for s in stats),
            }
            if trace_dir is not None:
                gaps = stages.idle_gaps(
                    stages.load(trace.find_xplane(trace_dir)), top=None)
                under = {n: s for n, s in gaps
                         if n == "save" or n.startswith("save/")}
                row["idle_gaps"] = gaps
                row["save_idle_unlabelled_share"] = (
                    under.get("save", 0.0) / sum(under.values())
                    if under else None)
            rows.append(row)
    finally:
        d.close()
        shutil.rmtree(work, ignore_errors=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--units", type=int, default=3)
    args = ap.parse_args(argv)

    from bench.common import device, harness, program

    files = harness.cell_files(args.workload)
    program.use_compile_cache()
    try:
        device.require_tpu(files["workload"]["chips"])
    except device.NoAccelerator as e:
        print(f"stage_trace: {e}; nothing was run", file=sys.stderr)
        return 3
    rows = measure(files, seed=args.seed, units=args.units)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "units": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
