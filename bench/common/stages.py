"""The program's save-path stages: its ``ckpt.*`` spans
(``repro.checkpoint.tracing``), read two ways.

- From ``last_save_stats``: each event's ``stages`` (seconds per span,
  a writer-lane span summed over the writer threads) and ``d2h_bytes``,
  as the ``train_parity`` kind records them in ``save_stats``.  The
  per-stage readers of ``bench/metrics/`` take a mean per event.
- From a profiler trace: ``load`` keeps the host events named ``ckpt.*``
  with the host line (thread) each ran on, beside what
  ``bench.common.trace.load`` reads, and ``idle_gaps`` labels each part
  of a device idle gap by the innermost benchmark span and, after a
  slash, the innermost program span open there on the thread that holds
  ``bench.window`` (``save/ckpt.save.d2h``).  Spans on other threads (the
  writer lanes) label nothing.  With no ``ckpt.*`` span in the trace the
  gaps are those of ``trace.reduce``, label for label.
"""
from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Dict, List, Optional

from bench.common import trace

PROGRAM_PREFIX = "ckpt."


def stage_mean(rec: Dict, stage: str) -> Optional[float]:
    """Mean seconds per save event of the window in the span ``stage``
    (an event that did not open it spent none there); None where the
    program records no stages or no event opened the span."""
    stats = rec.get("save_stats")
    if not stats or any("stages" not in s for s in stats):
        return None
    if not any(stage in s["stages"] for s in stats):
        return None
    return statistics.fmean(s["stages"].get(stage, 0.0) for s in stats)


def d2h_bytes_per_s(rec: Dict) -> Optional[float]:
    """Payload bytes copied device to host over the seconds spent in
    ``ckpt.save.d2h``, both means per event."""
    seconds = stage_mean(rec, "ckpt.save.d2h")
    if not seconds:
        return None
    return statistics.fmean(s["d2h_bytes"] for s in rec["save_stats"]) \
        / seconds


def load(path: str) -> Dict:
    """``trace.load`` plus ``"program"``: ``[(name, start, end, line)]``
    of the host events named ``ckpt.*``, and ``"window_line"``: the line
    of the ``bench.window`` event.  A line is ``(plane, index)``: the
    profiler gives each host thread a line of its own."""
    from jax.profiler import ProfileData

    tr = trace.load(path)
    program, window_line = [], None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    program.append((e.name, float(e.start_ns),
                                    float(e.end_ns), (plane.name, i)))
                elif e.name == trace.WINDOW_SPAN:
                    window_line = (plane.name, i)
    return dict(tr, program=program, window_line=window_line)


def _innermost_stage(stages, t: float) -> Optional[str]:
    best = None
    for n, s, e in stages:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else None


def idle_gaps(tr: Dict, *, top: Optional[int] = 10) -> List[List]:
    """``breakdown.idle_gaps`` of ``trace.reduce``, each part labelled by
    its benchmark span and the program stage open over it (see the
    module docstring); seconds averaged over the devices that ran
    operations."""
    lo, hi = trace.window_of(tr["spans"])
    lo, hi = lo - trace.SKEW_NS, hi + trace.SKEW_NS
    stages = [(n, s, e) for n, s, e, line in tr.get("program", [])
              if line == tr.get("window_line")]
    cuts = sorted({t for _, s, e in tr["spans"] + stages for t in (s, e)})
    idle: Dict[str, float] = defaultdict(float)
    n_dev = 0
    for ops in tr["devices"].values():
        inside = [(s, e) for _, s, e in ops if e > lo and s < hi]
        if not inside:
            continue
        n_dev += 1
        busy = trace.union(trace.clip(inside, lo, hi))
        for s, e in trace.gaps(busy, lo, hi):
            edges = [s] + [t for t in cuts if s < t < e] + [e]
            for a, b in zip(edges, edges[1:]):
                mid = (a + b) / 2
                label = trace.innermost(tr["spans"], mid)
                stage = _innermost_stage(stages, mid)
                if stage is not None:
                    label = f"{label}/{stage}"
                idle[label] += (b - a) / 1e9
    n_dev = max(n_dev, 1)
    ranked = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return [[n, s / n_dev] for n, s in ranked]
