#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  The run
loads, warms up every shape the cell uses, measures whole units of work
for at most ``--seconds``, checks what the timed path produced against
the plain reference, and prints one JSON object as the last line of
standard output (``--trace 0``: the cell's end-to-end metrics;
``--trace 1``: its per-layer metrics, from a profiler trace of the first
unit).  Each number compared is printed beside its limit as the last
lines of standard error.  Without the TPU chips the cell asks for the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(CHECKOUT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    logging.basicConfig(level=logging.WARNING)

    from bench.common import device, harness, program

    files = harness.cell_files(args.workload)
    chips = files["workload"]["chips"]
    program.use_compile_cache()
    try:
        device.require_tpu(chips)
    except device.NoAccelerator as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    result = harness.run_cell(args.workload, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              t_start=T_START, files=files, chips=chips)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
