"""Reduction of a ``jax.profiler`` trace to device busy time, idle gaps
and per-kernel device time.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``load`` reads it with ``jax.profiler.ProfileData`` into plain interval
lists, and everything else works on those lists, so the reduction is
checked on a small recorded trace without a chip.

- Device operations are the events of the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane, named by their HLO instruction (``%fusion.3``)
  and labelled with the jitted program (``XLA Modules`` line) they ran
  in.  Busy time is the union of their intervals within the traced
  window, averaged over the devices that ran any.
- A jitted program's device time is the summed duration of its
  ``XLA Modules`` events (``jit__fingerprint_many``: the fingerprint
  kernel with its wrapper).
- Host spans are the ``TraceAnnotation`` events whose name starts with
  ``bench.`` (the benchmark's own spans around each call into the
  program).  The traced window is the span ``bench.window``.
- An idle gap is a stretch of the window in which no operation ran on a
  device; each part of it is labelled by the innermost benchmark span
  open over that part (``idle`` when none is).
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the device's trace clock runs about a millisecond behind the host's
#: on a v5e (recorded sample: the first op starts 0.7 ms before the host
#: span that launched it); device events this far outside the window
#: still belong to it
SKEW_NS = 5e6


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``%fusion.3``."""
    return event_name.split(" = ", 1)[0]


def module_name(event_name: str) -> str:
    """``jit_train_step(1234)`` -> ``jit_train_step``."""
    return event_name.split("(", 1)[0]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Dict:
    """Read an ``.xplane.pb`` into ``{"devices": {plane: [(op, start,
    end)]}, "modules": {plane: [(program, start, end)]}, "spans":
    [(name, start, end)]}`` with times in ns."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    modules: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), float(e.start_ns), float(e.end_ns))
                        for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (module_name(e.name), float(e.start_ns),
                         float(e.end_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.end_ns)))
    return {"devices": {k: v for k, v in devices.items() if v},
            "modules": modules, "spans": spans}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(spans) -> Interval:
    ws = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not ws:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return min(s for s, _ in ws), max(e for _, e in ws)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def innermost(spans, t: float) -> str:
    best = None
    for n, s, e in spans:
        if n != WINDOW_SPAN and s <= t <= e and (best is None
                                                 or e - s < best[1]):
            best = (n[len(SPAN_PREFIX):], e - s)
    return best[0] if best else "idle"


def _label(ops, modules) -> List[Tuple[str, float, float]]:
    """Prefix every op with the program whose interval holds its start."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for n, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][0] if i >= 0 and mods[i][2] >= s else "?"
        out.append((f"{prog}/{n}", s, e))
    return out


def reduce(tr: Dict, *, top: int = 10) -> Dict:
    """busy_s and window_s (averaged over the devices that ran
    operations), the ``breakdown`` lists, and device seconds and event
    counts per op and per program."""
    lo, hi = window_of(tr["spans"])
    window_s = (hi - lo) / 1e9
    lo, hi = lo - SKEW_NS, hi + SKEW_NS
    busy_s, op_s, idle = [], defaultdict(float), defaultdict(float)
    mod_s, mod_n = defaultdict(float), defaultdict(int)
    cuts = sorted({t for _, s, e in tr["spans"] for t in (s, e)})
    n_dev = 0
    for plane, ops in tr["devices"].items():
        inside = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        if not inside:
            continue
        n_dev += 1
        mods = [m for m in tr.get("modules", {}).get(plane, [])
                if m[2] > lo and m[1] < hi]
        for n, s, e in mods:
            mod_s[n] += (min(e, hi) - max(s, lo)) / 1e9
            mod_n[n] += 1
        for n, s, e in _label(inside, mods):
            op_s[n] += (min(e, hi) - max(s, lo)) / 1e9
        busy = union(clip([(s, e) for _, s, e in inside], lo, hi))
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        for s, e in gaps(busy, lo, hi):
            # split the gap where a span opens or closes inside it
            edges = [s] + [t for t in cuts if s < t < e] + [e]
            for a, b in zip(edges, edges[1:]):
                idle[innermost(tr["spans"], (a + b) / 2)] += (b - a) / 1e9
    n_dev = max(n_dev, 1)
    ranked_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    ranked_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": min(sum(busy_s) / n_dev, window_s),
        "devices_busy": len(busy_s),
        "program_seconds": {n: s / n_dev for n, s in mod_s.items()},
        "program_counts": {n: c // n_dev for n, c in mod_n.items()},
        "breakdown": {
            "device_ops": [[n, s / n_dev] for n, s in ranked_ops],
            "idle_gaps": [[n, s / n_dev] for n, s in ranked_idle],
        },
    }


def program_seconds(reduced: Dict, prefix: str) -> Tuple[float, int]:
    """Summed device seconds of the jitted programs whose name starts
    with ``prefix``, and how many of their runs the window holds."""
    names = [n for n in reduced["program_seconds"] if n.startswith(prefix)]
    return (sum(reduced["program_seconds"][n] for n in names),
            sum(reduced["program_counts"][n] for n in names))
