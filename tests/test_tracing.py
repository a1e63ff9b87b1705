"""Save-path spans (``repro.checkpoint.tracing``, docs/perf.md): nesting
and parent ids, one event id across the train thread and the writer
lanes, the fold into ``last_save_stats`` at the commit, and the spans in
a profiler trace beside the device's."""
import gc
import glob
import sys
import threading
import weakref

import jax
import numpy as np
import pytest

from repro.checkpoint import tracing
from repro.checkpoint.async_io import TransferPool
from repro.checkpoint.overlap import OverlappedSaver
from repro.checkpoint.saver import CheckpointManager
from repro.configs import get_config
from repro.core import LayerRegistry, make_policy
from repro.launch import steps as steps_lib
from repro.models import build_model

BB = 4096
#: the spans the train thread opens directly under ``ckpt.save`` and
#: under ``ckpt.save.snapshot`` in a synchronous save
SAVE_CHILDREN = ("ckpt.save.snapshot", "ckpt.save.drain",
                 "ckpt.save.commit")
SNAPSHOT_CHILDREN = ("ckpt.save.fingerprint", "ckpt.save.d2h",
                     "ckpt.save.pack")


@pytest.fixture(scope="module")
def small():
    cfg = get_config("llama3.2-3b", reduced=True)
    model = build_model(cfg)
    s1 = steps_lib.init_state(model, jax.random.key(0))
    s2 = dict(s1, params=jax.tree.map(lambda x: np.asarray(x) + 1,
                                      s1["params"]))
    return model, LayerRegistry(model), [s1, s2]


@pytest.fixture
def folded(monkeypatch):
    """``(event id, spans)`` of every event as it was when folded."""
    seen = []
    fold = tracing.Event.fold

    def spy(self):
        seen.append((self.id, list(self.spans)))
        return fold(self)

    monkeypatch.setattr(tracing.Event, "fold", spy)
    return seen


def _mgr(root, model, registry, **kw):
    return CheckpointManager(root, registry,
                             make_policy("full", model.layer_units()),
                             fp_block_bytes=BB, **kw)


def _save(mode, mgr, state, step):
    if mode == "sync":
        mgr.save(state, step=step)
        return
    ov = OverlappedSaver(mgr, spread_steps=2)
    try:
        ov.begin(state, step)
        while ov.tick() is None:
            pass
    finally:
        ov.close()


def test_spans_nest_and_name_their_parent():
    ev = tracing.Event()
    with tracing.active(ev):
        with tracing.span("a"):
            with tracing.span("b", unit="u", kind="opt"):
                tracing.count("d2h_calls", 2)
            with tracing.span("c"):
                tracing.count("d2h_calls")
    with tracing.span("outside"):        # no active event: kept nowhere
        tracing.count("d2h_calls")
    spans = {s.name: s for s in ev.spans}
    assert set(spans) == {"a", "b", "c"}
    a, b, c = spans["a"], spans["b"], spans["c"]
    assert a.parent is None
    assert b.parent == a.span_id and c.parent == a.span_id
    assert {s.event for s in ev.spans} == {ev.id}
    assert {s.thread for s in ev.spans} == {threading.get_ident()}
    assert a.start_ns <= b.start_ns <= b.end_ns <= c.start_ns <= c.end_ns \
        <= a.end_ns
    stages, counters = ev.fold()
    assert counters["d2h_calls"] == 3
    assert stages["a"] >= stages["b"] + stages["c"]
    assert ev.spans == [] and not ev.counters


def test_a_pool_task_belongs_to_the_event_that_queued_it():
    pool = TransferPool(2)
    ev = tracing.Event()
    gate = threading.Event()

    def task():
        gate.wait(10)
        with tracing.span("task"):
            return threading.get_ident()

    try:
        with tracing.active(ev), tracing.span("submit"):
            pending = pool.submit("write", task)
        # the task runs after the submitting span and the event's
        # context have been left
        gate.set()
        pool.drain("write")
        worker = pending.result()
    finally:
        pool.close()
    spans = {s.name: s for s in ev.spans}
    assert spans["task"].parent == spans["submit"].span_id
    assert spans["task"].event == ev.id
    assert spans["task"].start_ns >= spans["submit"].end_ns
    assert spans["task"].thread == worker != threading.get_ident()


@pytest.mark.parametrize("mode", ["sync", "overlapped"])
def test_train_and_writer_spans_share_the_event_id(small, tmp_path, folded,
                                                    mode):
    model, registry, states = small
    mgr = _mgr(tmp_path, model, registry)
    try:
        _save(mode, mgr, states[0], 10)     # every unit written in full
        _save(mode, mgr, states[1], 20)     # every unit drifted
    finally:
        mgr.close()
    assert len(folded) == 2
    main = threading.get_ident()
    for event_id, spans in folded:
        assert spans and {s.event for s in spans} == {event_id}
        ids = {s.span_id: s for s in spans}
        writer = [s for s in spans if s.name.startswith("ckpt.write.")]
        assert {s.name for s in writer} == {"ckpt.write.encode",
                                            "ckpt.write.store"}
        assert all(s.thread != main for s in writer)
        # a writer span hangs off the train-thread span that queued it
        for s in writer:
            p = ids[s.parent]
            while p.thread != main:
                p = ids[p.parent]
            assert p.name == "ckpt.save.pack"
        assert all(s.thread == main for s in spans
                   if s.name.startswith("ckpt.save"))
    assert folded[0][0] != folded[1][0]


def test_save_timings_are_read_from_the_spans(small, tmp_path):
    model, registry, states = small
    mgr = _mgr(tmp_path, model, registry)
    try:
        mgr.save(states[0], step=10)
        first = mgr.last_save_stats
        mgr.save(states[0], step=20)        # unchanged: every unit dedups
        again = mgr.last_save_stats
    finally:
        mgr.close()
    for s in (first, again):
        st = s["stages"]
        assert s["snapshot_seconds"] == st["ckpt.save.snapshot"]
        assert s["writeback_seconds"] == st["ckpt.save.drain"]
        assert s["stall_seconds"] == s["total_seconds"] == st["ckpt.save"]
        assert "snapshot_bytes" not in s
        assert sum(st[n] for n in SAVE_CHILDREN) <= st["ckpt.save"]
        assert sum(st.get(n, 0.0) for n in SNAPSHOT_CHILDREN) \
            <= st["ckpt.save.snapshot"]
    # a full save makes one table transfer and one payload batch per
    # (unit, kind); a clean re-save fetches nothing
    n_units = 2 * len(registry.units)
    assert first["d2h_calls"] == 2 * n_units
    assert first["stages"]["ckpt.write.encode"] > 0
    assert again["d2h_calls"] == 0 and again["d2h_bytes"] == 0
    assert "ckpt.save.d2h" not in again["stages"]


def test_a_full_save_copies_its_payload_once(small, tmp_path):
    """Sync, fingerprint path, every unit in full: after the fetch the
    payload is copied once on the host, by the join that builds each
    stored object, so the event copies exactly the bytes it stores."""
    model, registry, states = small
    mgr = _mgr(tmp_path, model, registry)
    try:
        mgr.save(states[0], step=10)
        s = mgr.last_save_stats
    finally:
        mgr.close()
    assert s["full_chunks"] == 2 * len(registry.units)
    assert s["payload_copy_bytes"] == s["written_bytes"] > 0


def test_the_sync_full_path_calls_no_tobytes_on_a_payload(small, tmp_path):
    """The pack stage hands the writer lanes a view of each fetched leaf:
    no ``ndarray.tobytes`` of a payload leaf runs on the train thread
    (the fingerprint tables, ``uint32``, are packed with it)."""
    model, registry, states = small
    calls = []

    def spy(frame, event, arg):
        if event == "c_call" and getattr(arg, "__name__", "") == "tobytes":
            owner = getattr(arg, "__self__", None)
            if isinstance(owner, np.ndarray) and owner.dtype != np.uint32:
                calls.append((owner.dtype, owner.shape))

    mgr = _mgr(tmp_path, model, registry)
    try:
        sys.setprofile(spy)
        try:
            mgr.save(states[0], step=10)
        finally:
            sys.setprofile(None)
        s = mgr.last_save_stats
    finally:
        mgr.close()
    assert s["full_chunks"] == 2 * len(registry.units)
    assert calls == []


def test_overlapped_timings_are_read_from_the_spans(small, tmp_path):
    model, registry, states = small
    mgr = _mgr(tmp_path, model, registry)
    try:
        _save("overlapped", mgr, states[0], 10)
        s = mgr.last_save_stats
    finally:
        mgr.close()
    st = s["stages"]
    assert s["snapshot_seconds"] == st["ckpt.save.begin"]
    assert s["stage_seconds"] == st["ckpt.save.slice"]
    assert s["writeback_seconds"] == st["ckpt.save.drain"]
    assert s["stall_seconds"] == pytest.approx(
        st["ckpt.save.begin"] + st["ckpt.save.slice"] + st["ckpt.save"])
    assert s["total_seconds"] >= s["stall_seconds"]
    assert st["ckpt.save.fingerprint"] + st["ckpt.save.d2h"] \
        <= st["ckpt.save.begin"]
    assert st["ckpt.save.pack"] <= st["ckpt.save.slice"]
    assert s["d2h_calls"] > 0


@pytest.mark.parametrize("mode", ["sync", "overlapped"])
def test_events_are_dropped_at_the_commit(small, tmp_path, monkeypatch,
                                          mode):
    """20 events: each event's spans are folded and released, so memory
    does not grow with the run."""
    model, registry, states = small
    made = []
    init = tracing.Event.__init__

    def track(self):
        init(self)
        made.append(weakref.ref(self))

    monkeypatch.setattr(tracing.Event, "__init__", track)
    mgr = _mgr(tmp_path, model, registry, keep=2)
    try:
        names = set()
        for i in range(20):
            _save(mode, mgr, states[i % 2], 10 * (i + 1))
            names.add(frozenset(mgr.last_save_stats["stages"]))
    finally:
        mgr.close()
    gc.collect()
    assert len(made) == 20
    assert all(r() is None for r in made)
    assert len(set().union(*names)) <= 12


def test_spans_land_in_the_profiler_trace(small, tmp_path):
    """A save under ``jax.profiler``: the ``ckpt.save.*`` stages of the
    train thread lie inside ``ckpt.save`` on the trace's clock, and the
    writer lanes' spans are on threads of their own."""
    from jax.profiler import ProfileData

    model, registry, states = small
    mgr = _mgr(tmp_path / "ckpt", model, registry)
    try:
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            mgr.save(states[0], step=10)
        finally:
            jax.profiler.stop_trace()
    finally:
        mgr.close()
    (path,) = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                        recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):   # a line per thread
            events = [(e.name, e.start_ns, e.end_ns) for e in line.events
                      if e.name.startswith("ckpt.")]
            if events:
                lines[(plane.name, i)] = events
    (train,) = [ev for ev in lines.values()
                if any(n == "ckpt.save" for n, _, _ in ev)]
    (_, lo, hi), = [e for e in train if e[0] == "ckpt.save"]
    stages = {n for n, _, _ in train if n.startswith("ckpt.save.")}
    assert set(SAVE_CHILDREN + SNAPSHOT_CHILDREN) <= stages
    assert all(lo <= s <= e <= hi for n, s, e in train)
    writers = [ev for ev in lines.values() if ev is not train]
    assert {n for ev in writers for n, _, _ in ev} == {"ckpt.write.encode",
                                                       "ckpt.write.store"}
