"""Unified background transfer executor (CheckFreq-style compute/IO overlap).

One bounded thread pool — :class:`TransferPool` — carries every
asynchronous byte movement in the checkpoint subsystem: chunk writes
enqueued by the saver AND hot→durable spill copies enqueued by a tiered
backend.  Work is tagged with a *lane* name so producers can drain their
own lane without waiting on anyone else's: the saver's pre-manifest
barrier drains the ``"write"`` lane only, which is exactly why spill can
keep overlapping training after the manifest has committed.

Two worker backends sit underneath the lanes:

- ``worker_backend="thread"`` (default): tasks run on the pool threads
  themselves.  zstd and file IO release the GIL, but hashing, msgpack
  framing, and numpy delta math do not — "parallel" lanes serialize on
  the interpreter.
- ``worker_backend="process"``: the pool threads stay as coordinators,
  but every hot byte transform they run (blake2, codecs, XOR/BD02
  deltas, envelope decode, atomic file writes) is dispatched through
  :class:`IoDispatch` to a :class:`ProcessWorkerPool` of subprocess
  workers.  Payload-sized buffers travel via ``multiprocessing.
  shared_memory`` blocks from a free-list arena; small args and results
  ride a pickle pipe.  Workers load ``checkpoint/workers.py`` by file
  path and never import jax (see that module's docstring).

Worker death is detected, never hung on: a killed worker fails the
in-flight task with :class:`AsyncWriteError` (surfacing on the lane's
``drain()`` like any other transfer failure), the pool respawns a
replacement, and completed work is unaffected.

:class:`AsyncWriter` is the saver-facing facade over one lane.  Its API
(submit/drain/wait/close, errors surfacing on drain) is unchanged from
when it owned a private pool; it now either owns a TransferPool or
shares one the caller provides.

Errors surface on ``drain()`` of the lane that produced them — a failed
save must never be silently dropped (the manifest for that event is only
committed after every chunk of the event has landed), and a failed spill
must never fail an unrelated save barrier (it surfaces on the spill
lane's drain, i.e. the durability barrier or close).
"""
from __future__ import annotations

import contextvars
import glob
import os
import pickle
import queue
import re
import subprocess
import sys
import threading
from multiprocessing import shared_memory
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.checkpoint import tracing
from repro.checkpoint import workers as _workers
from repro.checkpoint.faults import crash_point

_SENTINEL = object()

# Payloads at or above this ride shared memory; below it, the pickle pipe
# is cheaper than an shm round-trip (segment + two syscalls).  Pool
# constructors accept an override so tests can force the shm path with
# tiny payloads.
SHM_MIN_BYTES = 32 * 1024

WORKER_BACKENDS = ("thread", "process")


class AsyncWriteError(RuntimeError):
    pass


class WorkerError(RuntimeError):
    """Raw failure marshalled back from a subprocess worker.

    ``kind`` is the worker's string classification ("corrupt", "codec",
    "missing", "error"); :class:`IoDispatch` maps it onto the parent-side
    exception the thread backend would have raised, so callers never see
    this type unless they use :class:`ProcessWorkerPool` directly.
    """

    def __init__(self, kind: str, message: str, tb: str = ""):
        super().__init__(message)
        self.kind = kind
        self.worker_traceback = tb


def _map_worker_error(e: WorkerError) -> BaseException:
    # Imported lazily: serial/compression sit above this module in some
    # import orders and the mapping only runs on a failure path.
    if e.kind == "corrupt":
        from repro.checkpoint.serial import ChunkCorruption
        return ChunkCorruption(str(e))
    if e.kind == "codec":
        from repro.checkpoint.compression import CodecUnavailable
        return CodecUnavailable(str(e))
    if e.kind == "missing":
        return FileNotFoundError(str(e))
    return AsyncWriteError(f"io worker task failed: {e}")


class PendingResult:
    """Return value of ``submit``: readable after the lane's drain (or
    ``wait()``).

    The content-addressed store only knows a chunk's digest once the writer
    thread has hashed the payload (or its fingerprint table), so the saver
    collects these and resolves them into manifest entries after the drain
    barrier.  ``wait()``/``done()`` allow waiting on a single result
    without draining the whole lane.
    """
    __slots__ = ("_value", "_error", "_event")

    def __init__(self) -> None:
        self._value = None
        self._error: Optional[BaseException] = None
        self._event = threading.Event()

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until this transfer finishes; True iff it did in time."""
        return self._event.wait(timeout)

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise AsyncWriteError(
                "result not ready; wait()/drain() the writer first")
        if self._error is not None:
            raise self._error
        return self._value


# Which lane's task the current pool thread is executing — lets nested
# dispatch calls (store code deep under a submitted fn) attribute their
# worker traffic to the right lane without threading a lane argument
# through every signature.
_ACTIVE_LANE = threading.local()


def current_lane(default: Optional[str] = None) -> Optional[str]:
    return getattr(_ACTIVE_LANE, "lane", None) or default


class _ShmArena:
    """Free-list allocator over parent-owned shared-memory segments.

    Segments are created on demand in power-of-two size classes and
    recycled between tasks (``put`` → worker reads → ``give_back``), so a
    steady-state save/restore touches a handful of segments instead of
    creating one per payload.  The parent is the sole owner: it creates,
    recycles, and — on ``close()`` — unlinks every segment.  Workers read
    the backing ``/dev/shm`` files directly and never attach, so no other
    process can unlink a segment out from under us (see
    ``workers._read_shm``).
    """

    def __init__(self, prefix: str):
        self.prefix = prefix
        self._lock = threading.Lock()
        self._free: Dict[int, List[str]] = {}
        self._segs: Dict[str, Tuple[shared_memory.SharedMemory, int]] = {}
        self._seq = 0
        self._closed = False

    @staticmethod
    def _size_class(n: int) -> int:
        return max(SHM_MIN_BYTES, 1 << max(1, n - 1).bit_length())

    def put(self, data: _workers.Buffer) -> Tuple[str, int]:
        """Stage ``data`` into a segment; returns (name, length)."""
        size = self._size_class(len(data))
        with self._lock:
            if self._closed:
                raise AsyncWriteError("shared-memory arena is closed")
            bucket = self._free.get(size)
            if bucket:
                name = bucket.pop()
                shm = self._segs[name][0]
            else:
                self._seq += 1
                name = f"{self.prefix}-{self._seq:x}"
                shm = shared_memory.SharedMemory(
                    name=name, create=True, size=size)
                # SharedMemory may round size up to a page; track the
                # requested class so give_back refiles correctly.
                self._segs[shm.name] = (shm, size)
                name = shm.name
        shm.buf[:len(data)] = data
        tracing.count("payload_copy_bytes", len(data))
        return name, len(data)

    def give_back(self, name: str) -> None:
        with self._lock:
            if self._closed or name not in self._segs:
                return
            self._free.setdefault(self._segs[name][1], []).append(name)

    def segment_names(self) -> List[str]:
        with self._lock:
            return sorted(self._segs)

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            segs = list(self._segs.values())
            self._segs.clear()
            self._free.clear()
        for shm, _ in segs:
            try:
                shm.close()
                shm.unlink()  # also unregisters from the resource tracker
            except FileNotFoundError:  # pragma: no cover - already gone
                pass


class StagingSlot:
    """One pinned-host staging buffer (a ``/dev/shm`` segment): the
    landing zone a checkpoint unit's gathered payload is packed into
    before the writer consumes it.  ``pack`` appends bytes and returns a
    zero-copy memoryview; views stay valid until the slot is released
    back to its arena (the writer converts to ``bytes`` on ITS thread —
    off the training thread's stall path)."""

    def __init__(self, name: str, shm):
        self.name = name
        self._shm = shm
        self._used = 0

    @property
    def capacity(self) -> int:
        return self._shm.size

    def reset(self) -> None:
        self._used = 0

    def pack(self, data) -> memoryview:
        """Append ``data`` (bytes/memoryview/buffer) and return the view
        of where it landed."""
        n = data.nbytes if hasattr(data, "nbytes") else len(data)
        end = self._used + n
        assert end <= self._shm.size, (end, self._shm.size)
        self._shm.buf[self._used:end] = memoryview(data).cast("B")
        view = self._shm.buf[self._used:end]
        self._used = end
        return view


class StagingArena:
    """Double-buffered staging area for the overlapped save pipeline
    (docs/perf.md).

    ``slots`` initial ``/dev/shm`` segments named
    ``repro-io-<pid:x>-stage-<n>`` — the same owner-pid convention as the
    worker arena, so :func:`sweep_dead_owner_shm` and the test-suite /
    ``check.sh`` leak guards cover them for free.  ``acquire(nbytes)``
    hands out a free slot, minting a new one when all are checked out
    and ``max_slots`` allows — so a slow writeback never stalls staging,
    and the staged footprint tops out at one event's payload (exactly
    what the synchronous saver queues in RAM).  With ``max_slots`` set,
    acquire blocks instead once the bound is reached — the hard
    backpressure form.  Slots are recycled across events and grow
    monotonically to the largest unit seen (recreated, not copied)."""

    def __init__(self, slots: int = 2, min_bytes: int = SHM_MIN_BYTES,
                 max_slots: Optional[int] = None):
        self._lock = threading.Lock()
        self._available = threading.Condition(self._lock)
        self._free: List[StagingSlot] = []
        self._all: List[StagingSlot] = []
        self._closed = False
        self._next = 0
        self.min_bytes = int(min_bytes)
        self.max_slots = max_slots
        self._prefix = f"repro-io-{os.getpid():x}-stage"
        for _ in range(max(1, int(slots))):
            self._free.append(self._mint())

    def _mint(self) -> StagingSlot:
        """Create one segment (caller holds the lock or is __init__)."""
        shm = shared_memory.SharedMemory(
            name=f"{self._prefix}-{self._next:x}", create=True,
            size=self.min_bytes)
        self._next += 1
        slot = StagingSlot(shm.name, shm)
        self._all.append(slot)
        return slot

    def acquire(self, nbytes: int, timeout: float = 120.0) -> StagingSlot:
        with self._available:
            while not self._free:
                if self._closed:
                    raise AsyncWriteError("staging arena is closed")
                if (self.max_slots is None
                        or len(self._all) < self.max_slots):
                    self._free.append(self._mint())
                    break
                if not self._available.wait(timeout):
                    raise AsyncWriteError(
                        f"no staging slot freed in {timeout}s "
                        "(writeback stalled?)")
            if self._closed:
                raise AsyncWriteError("staging arena is closed")
            slot = self._free.pop()
        if slot.capacity < nbytes:
            slot = self._grow(slot, nbytes)
        slot.reset()
        return slot

    def _grow(self, slot: StagingSlot, nbytes: int) -> StagingSlot:
        size = 1 << max(1, int(nbytes) - 1).bit_length()
        size = max(size, self.min_bytes)
        name = slot.name
        slot._shm.close()
        try:
            slot._shm.unlink()
        except FileNotFoundError:  # pragma: no cover - already swept
            pass
        shm = shared_memory.SharedMemory(name=name, create=True, size=size)
        new = StagingSlot(shm.name, shm)
        with self._lock:
            self._all[self._all.index(slot)] = new
        return new

    def release(self, slot: StagingSlot) -> None:
        """Return a slot once the unit's write resolved (its memoryviews
        must no longer be referenced)."""
        with self._available:
            if self._closed or slot not in self._all:
                return
            self._free.append(slot)
            self._available.notify()

    def segment_names(self) -> List[str]:
        with self._lock:
            return sorted(s.name for s in self._all)

    def close(self) -> None:
        with self._available:
            if self._closed:
                return
            self._closed = True
            slots = list(self._all)
            self._all.clear()
            self._free.clear()
            self._available.notify_all()
        for s in slots:
            try:
                s._shm.close()
                s._shm.unlink()
            except FileNotFoundError:  # pragma: no cover - already gone
                pass


# -c bootstrap for worker processes: load workers.py by *file path* under
# a private module name so the child never imports the repro package
# (whose __init__ chain pulls in jax).
_BOOTSTRAP = (
    "import importlib.util, sys\n"
    "spec = importlib.util.spec_from_file_location("
    "'repro_ckpt_workers', sys.argv[1])\n"
    "mod = importlib.util.module_from_spec(spec)\n"
    "sys.modules['repro_ckpt_workers'] = mod\n"
    "spec.loader.exec_module(mod)\n"
    "sys.exit(mod.worker_main())\n"
)


class _Worker:
    """One subprocess worker: a pickle request/response pipe pair plus a
    persistent ``/dev/shm`` scratch file the worker stages payload-sized
    response bytes into (offset markers over the pipe, bulk bytes via
    tmpfs — see ``workers.worker_main``)."""

    def __init__(self, workers_path: str, scratch_name: str):
        self.scratch_name = scratch_name
        self._scratch_fd: Optional[int] = None
        self.proc = subprocess.Popen(
            [sys.executable, "-c", _BOOTSTRAP, workers_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)

    def read_scratch(self, offset: int, length: int) -> bytes:
        if self._scratch_fd is None:
            self._scratch_fd = os.open(
                os.path.join(_workers.SHM_DIR, self.scratch_name),
                os.O_RDONLY)
        return os.pread(self._scratch_fd, length, offset)

    def close_scratch(self) -> None:
        if self._scratch_fd is not None:
            os.close(self._scratch_fd)
            self._scratch_fd = None
        try:
            os.unlink(os.path.join(_workers.SHM_DIR, self.scratch_name))
        except OSError:
            pass

    @property
    def pid(self) -> int:
        return self.proc.pid

    def call(self, fn_id: str, args: tuple,
             resp_spec: Optional[Tuple[str, int]] = None) -> Any:
        pickle.dump((fn_id, args, resp_spec), self.proc.stdin,
                    protocol=pickle.HIGHEST_PROTOCOL)
        self.proc.stdin.flush()
        return pickle.load(self.proc.stdout)

    def shutdown(self, timeout: float = 5.0) -> None:
        try:
            if self.proc.stdin and not self.proc.stdin.closed:
                self.proc.stdin.close()  # EOF -> worker_main returns
        except OSError:  # pragma: no cover - already broken pipe
            pass
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:  # pragma: no cover - stuck task
            self.proc.kill()
            self.proc.wait(timeout=timeout)
        self.close_scratch()


# Every /dev/shm file this module creates (arena segments, per-worker
# scratch) is named repro-io-<creator pid hex>-...
_SHM_OWNER_RE = re.compile(r"^repro-io-([0-9a-f]+)-")


def sweep_dead_owner_shm() -> List[str]:
    """Reclaim ``/dev/shm`` debris left by crashed processes.

    A SIGKILLed trainer can never unlink its own arena segments or
    worker scratch files, so — mirroring ``LocalFSBackend.sweep_tmp``
    for tmp files — every pool start sweeps ``repro-io-*`` files whose
    embedded creator pid is no longer alive.  Live pids (including pids
    we lack permission to signal) are left strictly alone.  Returns the
    names removed.
    """
    try:
        names = os.listdir(_workers.SHM_DIR)
    except OSError:  # pragma: no cover - no tmpfs on this host
        return []
    removed: List[str] = []
    for name in names:
        m = _SHM_OWNER_RE.match(name)
        if not m:
            continue
        pid = int(m.group(1), 16)
        if pid == os.getpid():
            continue
        try:
            os.kill(pid, 0)
            continue  # alive: its files are its own business
        except ProcessLookupError:
            pass
        except PermissionError:  # pragma: no cover - other-user pid
            continue
        try:
            os.unlink(os.path.join(_workers.SHM_DIR, name))
            removed.append(name)
        except OSError:  # pragma: no cover - raced another sweeper
            pass
    return removed


class ProcessWorkerPool:
    """Fixed-size pool of subprocess workers behind a pickle+shm protocol.

    ``call(fn_id, *args)`` checks a worker out of the idle queue, ships
    payload-sized bytes via the shm arena, blocks for the response, and
    returns the worker.  A worker that dies mid-task (crash, OOM-kill,
    SIGKILL) surfaces as :class:`AsyncWriteError` on the caller and is
    replaced immediately — a dead worker can fail its own task but can
    never hang another lane's drain.
    """

    def __init__(self, num_workers: int = 2, *,
                 shm_min_bytes: int = SHM_MIN_BYTES):
        sweep_dead_owner_shm()
        self.num_workers = max(1, int(num_workers))
        self.shm_min_bytes = int(shm_min_bytes)
        self._workers_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "workers.py")
        self.arena = _ShmArena(
            f"repro-io-{os.getpid():x}-{id(self) & 0xFFFFFF:x}")
        self._idle: "queue.Queue[_Worker]" = queue.Queue()
        self._lock = threading.Lock()
        self._open = True
        self._procs: List[_Worker] = []
        self.worker_restarts = 0
        self._sseq = 0
        self._lane_stats: Dict[str, Dict[str, int]] = {}
        for _ in range(self.num_workers):
            self._spawn()

    def _spawn(self) -> None:
        with self._lock:
            self._sseq += 1
            scratch = f"{self.arena.prefix}-s{self._sseq:x}"
        w = _Worker(self._workers_path, scratch)
        with self._lock:
            self._procs.append(w)
        self._idle.put(w)

    def worker_pids(self) -> List[int]:
        with self._lock:
            return [w.pid for w in self._procs if w.proc.poll() is None]

    def _marshal(self, obj: Any, names: List[str],
                 counted: List[int]) -> Any:
        if isinstance(obj, (bytes, bytearray, memoryview)) \
                and len(obj) >= self.shm_min_bytes:
            name, length = self.arena.put(obj)
            names.append(name)
            counted[0] += length
            return (_workers.SHM_MARK, name, length)
        if isinstance(obj, memoryview):
            # a small view (a view does not pickle) crosses as bytes
            tracing.count("payload_copy_bytes", len(obj))
            return obj.tobytes()
        if isinstance(obj, tuple):
            return tuple(self._marshal(v, names, counted) for v in obj)
        if isinstance(obj, list):
            return [self._marshal(v, names, counted) for v in obj]
        if isinstance(obj, dict):
            return {k: self._marshal(v, names, counted)
                    for k, v in obj.items()}
        return obj

    def _unstage(self, obj: Any, w: _Worker, counted: List[int]) -> Any:
        """Inverse of the worker's ``_stage_result``: swap ``(SHM_MARK,
        offset, length)`` markers inside a result back to bytes, read
        straight out of the worker's persistent scratch file.  Must run
        before the worker goes back to the idle queue — its next task
        reuses the scratch from offset 0."""
        if isinstance(obj, tuple):
            if len(obj) == 3 and obj[0] == _workers.SHM_MARK \
                    and isinstance(obj[1], int):
                data = w.read_scratch(obj[1], obj[2])
                tracing.count("payload_copy_bytes", obj[2])
                counted[0] += obj[2]
                return data
            return tuple(self._unstage(v, w, counted) for v in obj)
        if isinstance(obj, list):
            return [self._unstage(v, w, counted) for v in obj]
        if isinstance(obj, dict):
            return {k: self._unstage(v, w, counted)
                    for k, v in obj.items()}
        return obj

    def call(self, fn_id: str, *args, lane: Optional[str] = None) -> Any:
        lane = lane or current_lane("io")
        names: List[str] = []
        counted = [0]
        try:
            marshalled = self._marshal(args, names, counted)
            w = self._idle.get()
            try:
                if w.proc.poll() is not None:
                    # Died while idle (e.g. an earlier SIGKILL landed
                    # between tasks) — replace and fail only this checkout.
                    raise OSError(f"worker pid {w.pid} exited "
                                  f"{w.proc.returncode}")
                resp = w.call(fn_id, marshalled,
                              (w.scratch_name, self.shm_min_bytes))
            except (EOFError, OSError, BrokenPipeError,
                    pickle.UnpicklingError) as e:
                with self._lock:
                    self.worker_restarts += 1
                    try:
                        self._procs.remove(w)
                    except ValueError:  # pragma: no cover
                        pass
                    reopen = self._open
                try:
                    w.proc.kill()
                except OSError:  # pragma: no cover - already reaped
                    pass
                w.proc.wait()
                w.close_scratch()
                if reopen:
                    self._spawn()
                raise AsyncWriteError(
                    f"io worker pid {w.pid} died running {fn_id!r}: "
                    f"{e!r}") from e
            # Unstage while we still own the worker: the next task the
            # worker picks up rewrites its scratch from offset 0.
            try:
                if isinstance(resp, tuple) and resp and resp[0] == "ok":
                    resp = ("ok", self._unstage(resp[1], w, counted))
            finally:
                self._idle.put(w)
        finally:
            for name in names:
                self.arena.give_back(name)
            with self._lock:
                st = self._lane_stats.setdefault(
                    lane, {"tasks": 0, "bytes_shm": 0})
                st["tasks"] += 1
                st["bytes_shm"] += counted[0]
        if isinstance(resp, tuple) and resp and resp[0] == "ok":
            return resp[1]
        if isinstance(resp, tuple) and len(resp) == 4 and resp[0] == "err":
            raise WorkerError(resp[1], resp[2], resp[3])
        raise AsyncWriteError(
            f"malformed response from io worker for {fn_id!r}: {resp!r}")

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "workers": len(self._procs),
                "worker_restarts": self.worker_restarts,
                "lanes": {lane: dict(st)
                          for lane, st in self._lane_stats.items()},
            }

    def close(self) -> None:
        with self._lock:
            if not self._open:
                return
            self._open = False
            procs = list(self._procs)
            self._procs.clear()
        for w in procs:
            w.shutdown()
        self.arena.close()
        # Orphaned response files (a worker killed between staging a
        # result and the parent reading it) share the arena prefix.
        for path in glob.glob(os.path.join(
                _workers.SHM_DIR, self.arena.prefix + "-*")):
            try:
                os.unlink(path)
            except OSError:  # pragma: no cover - already gone
                pass


class IoDispatch:
    """Routes hot byte transforms inline or to a ProcessWorkerPool.

    The single seam the store/backends/restore code calls: with no pool
    (thread backend) ``call`` runs the worker fn in-process — same code,
    zero overhead; with a pool it ships the task out and maps worker
    error kinds back onto the exceptions the inline path would raise
    (``ChunkCorruption``/``CodecUnavailable``/``FileNotFoundError``), so
    callers cannot tell the backends apart by exception type.
    """

    def __init__(self, pool: Optional[ProcessWorkerPool] = None):
        self.pool = pool

    @property
    def is_process(self) -> bool:
        return self.pool is not None

    @property
    def backend(self) -> str:
        return "process" if self.pool is not None else "thread"

    def call(self, fn_id: str, *args, lane: Optional[str] = None) -> Any:
        if self.pool is None:
            return _workers.run(fn_id, *args)
        try:
            return self.pool.call(fn_id, *args, lane=lane)
        except WorkerError as e:
            raise _map_worker_error(e) from e

    def stats(self) -> Optional[Dict[str, Any]]:
        return None if self.pool is None else self.pool.stats()


#: Shared inline dispatch — what every store/backend uses unless a
#: process-backed TransferPool hands it something better.
INLINE_DISPATCH = IoDispatch()


class _LaneState:
    """Per-lane accounting; every field is guarded by TransferPool._cond.

    One object per lane (instead of the old parallel ``_outstanding``/
    ``_errors`` dicts) so a lane's counter, error list, and task count
    can only ever be read/written together under the single lock —
    ``outstanding()``/``drain()`` observe a consistent snapshot even
    while another lane is being flooded (see the lane-accounting
    regression test).
    """
    __slots__ = ("outstanding", "errors", "tasks")

    def __init__(self) -> None:
        self.outstanding = 0
        self.errors: List[BaseException] = []
        self.tasks = 0


class TransferPool:
    """Bounded thread pool with per-lane accounting.

    ``submit(lane, fn, ...)`` enqueues work; ``drain(lane)`` blocks until
    that lane's outstanding count hits zero and raises its collected
    errors.  Lanes are cheap strings — current users: ``"write"`` (saver
    chunk writes), ``"spill"``/``"remote_spill"`` (tiered hot→durable
    copies), ``"restore"`` (engine read stages), ``"io"`` (untagged).

    ``worker_backend="process"`` attaches a :class:`ProcessWorkerPool`
    and exposes it as ``self.dispatch``; the pool threads then act as
    coordinators while byte work runs in subprocess workers.
    """

    def __init__(self, num_threads: int = 2, max_queue: int = 0, *,
                 worker_backend: str = "thread",
                 io_workers: Optional[int] = None,
                 shm_min_bytes: int = SHM_MIN_BYTES):
        if worker_backend not in WORKER_BACKENDS:
            raise ValueError(
                f"worker_backend must be one of {WORKER_BACKENDS}, "
                f"got {worker_backend!r}")
        # Default unbounded: pool workers themselves enqueue follow-up
        # work (a chunk write on the "write" lane triggers a spill submit
        # on the "spill" lane), and a bounded queue could deadlock with
        # every worker blocked on a full put.  Producers that want
        # backpressure (the legacy AsyncWriter-owned pool, which never
        # nests submits) pass an explicit bound.
        self._q: "queue.Queue" = queue.Queue(maxsize=max_queue)
        # One lock/condition guards the open flag and every _LaneState:
        # a submit that won the open-check must have its increment
        # visible before close() starts waiting, or the item could land
        # behind the shutdown sentinels and never run.
        self._cond = threading.Condition()
        self._open = True
        self._lanes: Dict[str, _LaneState] = {}
        self.worker_backend = worker_backend
        self.workers: Optional[ProcessWorkerPool] = None
        if worker_backend == "process":
            self.workers = ProcessWorkerPool(
                io_workers if io_workers else max(2, num_threads),
                shm_min_bytes=shm_min_bytes)
        self.dispatch = IoDispatch(self.workers)
        self._threads = [
            threading.Thread(target=self._run, name=f"ckpt-transfer-{i}",
                             daemon=True)
            for i in range(max(1, num_threads))
        ]
        for t in self._threads:
            t.start()

    def _lane(self, lane: str) -> _LaneState:
        st = self._lanes.get(lane)
        if st is None:
            st = self._lanes[lane] = _LaneState()
        return st

    def _run(self) -> None:
        while True:
            item = self._q.get()
            try:
                if item is _SENTINEL:
                    return
                lane, ctx, fn, args, kwargs, pending = item
                _ACTIVE_LANE.lane = lane
                try:
                    # Fault-injection seam: ``pool:<lane>`` fires before
                    # each task of that lane executes (a worker-thread
                    # death; surfaces on the lane's drain like any other
                    # transfer failure).  No-op unless armed.
                    crash_point(f"pool:{lane}")
                    pending._value = ctx.run(fn, *args, **kwargs)
                except BaseException as e:  # noqa: BLE001
                    pending._error = e
                    with self._cond:
                        self._lane(lane).errors.append(e)
                finally:
                    _ACTIVE_LANE.lane = None
                    pending._event.set()
                    with self._cond:
                        st = self._lane(lane)
                        st.outstanding -= 1
                        st.tasks += 1
                        self._cond.notify_all()
            finally:
                self._q.task_done()

    def submit(self, lane: str, fn: Callable, *args, **kwargs
               ) -> PendingResult:
        pending = PendingResult()
        with self._cond:
            if not self._open:
                raise AsyncWriteError("transfer pool is closed")
            self._lane(lane).outstanding += 1
        # The put happens outside the lock so a full queue still drains
        # (workers never take the condition while executing user work for
        # longer than a counter update).  close() waits on the counters,
        # not the queue, so this item can never be stranded.  The task
        # runs in a copy of the submitter's context: a checkpoint event's
        # spans (repro.checkpoint.tracing) follow its writes to the lane.
        self._q.put((lane, contextvars.copy_context(), fn, args, kwargs,
                     pending))
        return pending

    def submit_task(self, lane: str, fn_id: str, *args) -> PendingResult:
        """Submit a raw worker fn (see ``workers.WORKER_FNS``) on a lane —
        runs in a subprocess under the process backend, inline on the
        pool thread under the thread backend."""
        return self.submit(lane, self.dispatch.call, fn_id, *args)

    def outstanding(self, lane: str) -> int:
        with self._cond:
            st = self._lanes.get(lane)
            return st.outstanding if st is not None else 0

    def drain(self, lane: str) -> None:
        """Block until ``lane`` has no outstanding work; raise its errors."""
        with self._cond:
            self._cond.wait_for(
                lambda: self._lane(lane).outstanding == 0)
            st = self._lane(lane)
            errs, st.errors = st.errors, []
        if errs:
            raise AsyncWriteError(
                f"{len(errs)} checkpoint transfer(s) failed on lane "
                f"{lane!r}: {errs[0]!r}") from errs[0]

    def drain_all(self) -> None:
        # Loop until a quiescent snapshot: draining lane A can enqueue
        # follow-up work on lane B (write -> spill), and a lane created
        # after the first snapshot must still be drained.
        while True:
            with self._cond:
                lanes = [name for name, st in self._lanes.items()
                         if st.outstanding or st.errors]
            if not lanes:
                return
            for lane in lanes:
                self.drain(lane)

    def lane_stats(self) -> Dict[str, Dict[str, int]]:
        with self._cond:
            return {name: {"tasks": st.tasks, "outstanding": st.outstanding}
                    for name, st in self._lanes.items()}

    def stats(self) -> Dict[str, Any]:
        """Merged per-lane pool/worker stats for save/restore reporting:
        {backend, worker_restarts, bytes_shm, lanes: {lane: {tasks,
        outstanding[, worker_tasks, bytes_shm]}}}."""
        out: Dict[str, Any] = {
            "backend": self.worker_backend,
            "worker_restarts": 0,
            "bytes_shm": 0,
            "lanes": self.lane_stats(),
        }
        if self.workers is not None:
            ws = self.workers.stats()
            out["worker_restarts"] = ws["worker_restarts"]
            for lane, st in ws["lanes"].items():
                d = out["lanes"].setdefault(
                    lane, {"tasks": 0, "outstanding": 0})
                d["worker_tasks"] = st["tasks"]
                d["bytes_shm"] = st["bytes_shm"]
                out["bytes_shm"] += st["bytes_shm"]
        return out

    def close(self) -> None:
        with self._cond:
            if not self._open:
                return
            self._open = False
            # Every accepted submit incremented its lane before we flipped
            # _open, so waiting the counters down waits ALL accepted work.
            self._cond.wait_for(
                lambda: all(st.outstanding == 0
                            for st in self._lanes.values()))
        for _ in self._threads:
            self._q.put(_SENTINEL)
        for t in self._threads:
            t.join(timeout=10)
        if self.workers is not None:
            self.workers.close()


class AsyncWriter:
    """Saver-facing facade over one TransferPool lane.

    ``AsyncWriter(n)`` owns a private pool (legacy shape, used by tests
    and standalone stores); ``AsyncWriter(pool=shared)`` rides a shared
    pool and ``close()`` then only seals this writer's lane — the pool
    (and other lanes, e.g. tiered spill) keeps running.
    """

    LANE = "write"

    def __init__(self, num_threads: int = 2, max_queue: int = 64, *,
                 pool: Optional[TransferPool] = None, lane: str = LANE):
        self._owns_pool = pool is None
        self.pool = pool if pool is not None \
            else TransferPool(num_threads, max_queue)
        self.lane = lane
        self._state_lock = threading.Lock()
        self._open = True

    def submit(self, fn: Callable, *args, **kwargs) -> PendingResult:
        with self._state_lock:
            if not self._open:
                raise AsyncWriteError("writer is closed")
            return self.pool.submit(self.lane, fn, *args, **kwargs)

    def drain(self) -> None:
        """Block until all queued writes finish; raise collected errors."""
        self.pool.drain(self.lane)

    def wait(self) -> None:
        """Alias of ``drain()`` — the barrier the docstrings promise."""
        self.drain()

    def close(self) -> None:
        with self._state_lock:
            if not self._open:
                return
            self._open = False
        if self._owns_pool:
            self.pool.close()
        else:
            self.pool.drain(self.lane)

    def __enter__(self) -> "AsyncWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
