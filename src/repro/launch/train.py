"""End-to-end trainer with LLMTailor selective checkpointing + recovery.

    python -m repro.launch.train --arch llama3.2-3b --smoke --steps 300 \
        --policy parity --ckpt-interval 50 --ckpt-dir /tmp/run1

Fault-tolerance surface exercised here:
- selective checkpoints every ``ckpt_interval`` steps (policy-driven),
- async write overlap (training continues while chunks land),
- ``--fail-at N`` raises a simulated failure at a step boundary;
  ``--fail-at N@point`` arms a named crash point (see
  repro.checkpoint.faults) at step N so the death happens *mid-save*
  inside that pipeline stage (``--fail-mode exit`` hard-kills instead of
  raising — the supervisor's crash drills),
- ``--handle-sigterm`` turns SIGTERM into a preemption: an immediate
  full-capture hot save (durability barrier waived), then the spill
  backlog drains during the grace period and the process exits with
  code ``EXIT_PREEMPTED`` — no committed work is lost and no queued
  write is abandoned (docs/resiliency.md),
- ``--progress-file`` appends machine-readable progress lines
  (``start/step/ckpt/preempt/done,<n>,<unix-time>``) the supervisor
  tails to time interruptions and compute goodput,
- ``--resume`` restores the implicit Frankenstein merge and continues with
  byte-identical data (the data state rides in the manifest meta),
- loss log written as CSV for trajectory-overlay comparisons (Table 1/4).
"""
from __future__ import annotations

import argparse
import json
import logging
import signal
import sys
import threading
import time
from pathlib import Path
from typing import Dict, Optional, Union

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import TrainConfig
from repro.core import DeltaTracker, LayerRegistry, make_policy
from repro.checkpoint import faults
from repro.checkpoint.overlap import OverlappedSaver
from repro.checkpoint.saver import CheckpointManager
from repro.checkpoint.sharded import ShardedCheckpointer
from repro.data.synthetic import SyntheticTokens
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model

log = logging.getLogger("repro.train")

#: Exit code of a clean preemption (SIGTERM handled, hot save committed):
#: the supervisor restarts the run but does not count it as a crash.
EXIT_PREEMPTED = 17


class SimulatedFailure(RuntimeError):
    pass


class _Progress:
    """Append-only machine-readable progress feed for the supervisor:
    one ``kind,step,unix-time`` line per event, flushed per line (the
    reader is another process and the writer may die at any moment)."""

    def __init__(self, path: Optional[str]):
        self._f = None
        if path:
            Path(path).parent.mkdir(parents=True, exist_ok=True)
            self._f = open(path, "a", buffering=1)

    def emit(self, kind: str, step: int) -> None:
        if self._f is not None:
            self._f.write(f"{kind},{step},{time.time():.6f}\n")
            # line buffering is not guaranteed to flush on every platform
            # / stream type; the supervisor schedules injections off this
            # feed, so force each line out as it happens.
            self._f.flush()

    def close(self) -> None:
        if self._f is not None:
            self._f.close()


def make_batch_fn(model, data: SyntheticTokens):
    cfg = model.cfg

    def to_batch(raw: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        batch = {"tokens": raw["tokens"]}
        b = raw["tokens"].shape[0]
        if cfg.family == "vlm":
            rng = np.random.RandomState(raw["tokens"][0, 0] % 65521)
            batch["patch_embeds"] = rng.standard_normal(
                (b, cfg.vlm.num_patches, cfg.vlm.patch_embed_dim)).astype(
                    np.float32) * 0.1
        if cfg.family == "encdec":
            rng = np.random.RandomState(raw["tokens"][0, 0] % 65521)
            batch["frames"] = rng.standard_normal(
                (b, raw["tokens"].shape[1], cfg.d_model)).astype(np.float32) * 0.1
        return batch

    return to_batch


def train(
    *,
    arch: str,
    reduced: bool = True,
    total_steps: int = 200,
    batch: int = 8,
    seq_len: int = 64,
    policy_name: str = "full",
    ckpt_interval: int = 50,
    ckpt_dir: str = "/tmp/repro_train",
    ckpt_async: bool = True,
    ckpt_fingerprint: bool = True,
    ckpt_spread_steps: int = 0,
    codec: str = "auto",
    store_backend: str = "local",
    io_backend: str = "thread",
    io_workers: Optional[int] = None,
    writer_threads: int = 2,
    spill_threads: int = 2,
    hot_budget_mb: Optional[int] = None,
    spill_barrier: bool = False,
    remote_opts: Optional[Dict] = None,
    scrub_on_start: bool = False,
    shard_participants: int = 1,
    resume: bool = False,
    fail_at: Optional[Union[int, str]] = None,
    fail_mode: str = "raise",
    handle_sigterm: bool = False,
    progress_file: Optional[str] = None,
    seed: int = 0,
    log_csv: Optional[str] = None,
    lr: float = 1e-3,
) -> Dict:
    fail_step, fail_point, fail_hit = (None, None, 1)
    if fail_at is not None:
        fail_step, fail_point, fail_hit = faults.parse_fail_at(fail_at)
    cfg = get_config(arch, reduced=reduced)
    model = build_model(cfg)
    tcfg = TrainConfig(learning_rate=lr, warmup_steps=20,
                       total_steps=total_steps, ckpt_interval=ckpt_interval,
                       seed=seed)
    registry = LayerRegistry(model, weight_decay=tcfg.weight_decay)
    policy = make_policy(policy_name, model.layer_units())
    mgr = CheckpointManager(Path(ckpt_dir), registry, policy,
                            codec=codec, async_save=ckpt_async,
                            fingerprint=ckpt_fingerprint,
                            store_backend=store_backend,
                            io_backend=io_backend,
                            io_workers=io_workers,
                            writer_threads=writer_threads,
                            spill_threads=spill_threads,
                            hot_budget_bytes=(hot_budget_mb * 2**20
                                              if hot_budget_mb else None),
                            spill_barrier=spill_barrier,
                            remote_opts=remote_opts)
    tracker = DeltaTracker(registry) if policy_name == "topk_delta" else None
    # Shard-native save path: N virtual participants (threads) each
    # gather/fingerprint only their owned slices and the manifest commits
    # through the two-phase barrier (docs/storage.md).  ``saver`` keeps
    # the CheckpointManager.save signature either way.
    saver = (ShardedCheckpointer(mgr, shard_participants)
             if shard_participants > 1 else mgr)
    # Zero-stall pipeline (docs/perf.md): checkpoint events begin at the
    # step boundary but run their host-side gather/encode/write across
    # the next ``ckpt_spread_steps`` steps, overlapped with compute.
    ov = None
    if ckpt_spread_steps > 0:
        if shard_participants > 1:
            raise ValueError("--ckpt-spread-steps is incompatible with "
                             "--shard-participants > 1")
        ov = OverlappedSaver(mgr, spread_steps=ckpt_spread_steps)

    data = SyntheticTokens(vocab_size=cfg.vocab_size, batch=batch,
                           seq_len=seq_len, seed=seed)
    to_batch = make_batch_fn(model, data)
    train_step = jax.jit(steps_lib.make_train_step(model, tcfg),
                         donate_argnums=0)

    # Preemption: SIGTERM only sets a flag — the save happens on the
    # training thread at the next step boundary, where the state is
    # consistent (mid-train_step state is donated/partial).
    preempt_flag = threading.Event()
    if handle_sigterm:
        def _on_sigterm(signum, frame):  # noqa: ARG001
            preempt_flag.set()
        try:
            signal.signal(signal.SIGTERM, _on_sigterm)
        except ValueError:
            # Not the main thread (in-process test harness): the caller
            # can still set the flag by sending SIGTERM to the process
            # group or calling train with its own orchestration.
            log.warning("cannot install SIGTERM handler off the main "
                        "thread; preemption handling disabled")

    progress = _Progress(progress_file)

    scrub_report = None
    if scrub_on_start:
        # fsck before touching the store: repair bit-rot from any good
        # tier copy and quarantine the unrecoverable so a resume's
        # restore plan skips demoted manifests up front.
        scrub_report = mgr.scrub()
        log.info("scrub-on-start: %d object(s) checked, %d repaired, "
                 "%d unrecoverable", scrub_report["checked_objects"],
                 len(scrub_report["repaired"]),
                 len(scrub_report["unrecoverable"]))

    if resume:
        like = steps_lib.state_specs(model)
        state = mgr.restore(like)
        meta = mgr.restore_meta()
        if "data_state" in meta:
            data.load_state(meta["data_state"])
        start = int(state["step"])
        log.info("resumed at step %d (policy=%s)", start, policy.name)
    else:
        state = steps_lib.init_state(model, jax.random.key(seed))
        start = 0
        if tracker:
            tracker.reset(state["params"])

    losses = []
    # host wall time per step, dispatch to loss on host (the first one
    # includes the compile; sync saves fall outside it)
    step_seconds = []
    t0 = time.time()
    save_seconds = 0.0
    d2h_bytes = 0
    d2h_calls = 0
    payload_copy_bytes = 0
    hashed_bytes = 0
    dirty_fracs = []
    save_timing = {"snapshot_seconds": 0.0, "stage_seconds": 0.0,
                   "writeback_seconds": 0.0, "stall_seconds": 0.0}
    # seconds per save-path span, summed over the run's events
    save_stages: Dict[str, float] = {}
    overlap_slices = 0
    overflow_redispatches = 0
    save_events = []
    preempted_at: Optional[int] = None
    progress.emit("start", start)

    def event_meta():
        return {"data_state": data.state_dict(), "arch": arch,
                "reduced": reduced, "tcfg": tcfg.model_dump()}

    def absorb_event(manifest):
        """Account one committed checkpoint event (either mode) from the
        manager's stats, and advance the tracker references."""
        nonlocal save_seconds, d2h_bytes, d2h_calls, hashed_bytes
        nonlocal payload_copy_bytes
        nonlocal overlap_slices, overflow_redispatches
        s = mgr.last_save_stats
        save_events.append(dict(s))
        for k in save_timing:
            save_timing[k] += s.get(k, 0.0)
        for k, v in s.get("stages", {}).items():
            save_stages[k] = save_stages.get(k, 0.0) + v
        d2h_bytes += s.get("d2h_bytes", 0)
        d2h_calls += s.get("d2h_calls", 0)
        payload_copy_bytes += s.get("payload_copy_bytes", 0)
        hashed_bytes += s.get("hashed_bytes", 0)
        dirty_fracs.append(s.get("dirty_block_frac", 1.0))
        progress.emit("ckpt", manifest.step)
        if ov is not None:
            # The loop only ever blocked for the stall portion: that is
            # what save_seconds means in both modes (docs/perf.md).
            save_seconds += s.get("stall_seconds", 0.0)
            overlap_slices += s.get("spread_slices", 0)
            overflow_redispatches += s.get("overflow_redispatches", 0)
            if tracker:
                # References advance to the SNAPSHOT-time fingerprints:
                # by commit the live params have drifted past what this
                # event captured, and that drift belongs to the next
                # event's scores.
                for u in manifest.saved_units:
                    if u in ov.last_snapshot_fps:
                        tracker.set_reference(u, ov.last_snapshot_fps[u])

    for step in range(start, total_steps):
        t_step = time.time()
        raw = data.peek(step)
        data.state.step = step + 1
        state, metrics = train_step(state, to_batch(raw))
        if ov is not None and ov.active:
            # One spread slice per step, between dispatching the step and
            # syncing its loss: the host stages/writes while the device
            # computes.
            done = ov.tick()
            if done is not None:
                absorb_event(done)
        loss = float(metrics["loss"])
        step_seconds.append(time.time() - t_step)
        losses.append((step, loss))
        progress.emit("step", step + 1)
        if fail_step is not None and step + 1 == fail_step:
            if fail_point is None:
                if ov is not None:
                    ov.close()
                mgr.close()
                raise SimulatedFailure(
                    f"injected failure at step {fail_step}")
            # Arm the named pipeline crash point: the death happens
            # inside the save machinery (possibly on a writer/spill
            # thread, surfacing on a drain), not at this step boundary.
            faults.arm(fail_point, hit=fail_hit, mode=fail_mode)
            log.info("armed crash point %r (hit=%d mode=%s) at step %d",
                     fail_point, fail_hit, fail_mode, fail_step)
        if preempt_flag.is_set():
            # Preemption save: capture EVERY unit (cheap — unchanged
            # units dedup with zero payload movement) so resume is
            # bit-exact regardless of policy, and skip the durable spill
            # barrier so the manifest commits immediately; the grace
            # period below is spent draining the spill backlog instead
            # of gathering.
            if ov is not None and ov.active:
                # Events are FIFO: the mid-spread event commits (its
                # manifest is older than the hot save's) before the
                # direct save below may move the chain.
                done = ov.finish()
                if done is not None:
                    absorb_event(done)
            manifest = saver.save(state, step=step + 1, meta=event_meta(),
                                  units=mgr.policy.all_units(),
                                  durability_barrier=False)
            preempted_at = step + 1
            progress.emit("preempt", step + 1)
            log.info("preempted: hot save committed at step %d "
                     "(durable_on=%s)", step + 1,
                     manifest.meta["storage"]["durable_on"])
            break
        if (step + 1) % ckpt_interval == 0:
            scores = tracker.scores(state["params"]) if tracker else None
            if ov is not None:
                if ov.active:
                    # the previous event is still spreading: it commits
                    # first (events are FIFO) and is accounted here
                    done = ov.finish()
                    if done is not None:
                        absorb_event(done)
                # Snapshot + decisions now (this is the last moment the
                # pre-donation state is intact); staging, writes, and the
                # commit ride the next ticks.
                ov.begin(state, step + 1, meta=event_meta(),
                         drift_scores=scores)
            else:
                t_save = time.time()
                manifest = saver.save(
                    state, step=step + 1, meta=event_meta(),
                    drift_scores=scores)
                if tracker:
                    tracker.mark_saved(state["params"],
                                       manifest.saved_units)
                save_seconds += time.time() - t_save
                absorb_event(manifest)
    if ov is not None:
        # Run end: the last event may still be mid-spread — finish it so
        # its manifest commits before accounting/close.
        done = ov.finish()
        if done is not None:
            absorb_event(done)
    total = time.time() - t0

    if fail_point is not None and fail_point in faults.pending():
        # The armed point was never reached (e.g. a dedup hit skipped the
        # stage, or the step had no checkpoint event): fail loudly — a
        # crash drill that silently didn't drill is worse than a failure.
        faults.disarm(fail_point)
        if ov is not None:
            ov.close()
        mgr.close()
        raise SimulatedFailure(
            f"crash point {fail_point!r} armed at step {fail_step} was "
            "never reached before the run ended")

    if log_csv:
        Path(log_csv).parent.mkdir(parents=True, exist_ok=True)
        with open(log_csv, "w") as f:
            f.write("step,loss\n")
            for s, l in losses:
                f.write(f"{s},{l}\n")
    # Spill-backlog drain: how far durability lagged the hot tier at the
    # end of training (0.0 for single-tier backends).  After a preemption
    # this is the grace period put to work: the hot-committed manifest
    # becomes durable-tier-backed before the process exits — queued
    # writes are drained, never abandoned.
    t_drain = time.time()
    mgr.drain_spill()
    spill_drain_seconds = time.time() - t_drain
    tier_stats = mgr.store.tier_stats()
    if ov is not None:
        ov.close()
    mgr.close()
    usage = mgr.disk_usage()
    progress.emit("preempt_durable" if preempted_at is not None else "done",
                  preempted_at if preempted_at is not None
                  else total_steps)
    progress.close()
    return {
        "preempted": preempted_at is not None,
        "preempted_at": preempted_at,
        "final_loss": losses[-1][1] if losses else float("nan"),
        "losses": losses,
        "step_seconds": step_seconds,
        "train_seconds": total,
        "save_seconds": save_seconds,
        "ckpt_time_fraction": save_seconds / total if total else 0.0,
        # four-way event-time split summed over events (docs/perf.md):
        # stall is what save_seconds/ckpt_time_fraction measure in both
        # modes; snapshot/stage/writeback locate where the time went.
        **save_timing,
        "save_stages": save_stages,
        "save_mode": "overlapped" if ov is not None else "sync",
        "ckpt_spread_steps": ckpt_spread_steps,
        "overlap_slices": overlap_slices,
        "overflow_redispatches": overflow_redispatches,
        "ckpt_bytes": usage["total"],
        # fingerprint-pipeline accounting, summed over save events
        "d2h_bytes": d2h_bytes,
        "d2h_calls": d2h_calls,
        "payload_copy_bytes": payload_copy_bytes,
        "hashed_bytes": hashed_bytes,
        "dirty_block_frac": (float(np.mean(dirty_fracs))
                             if dirty_fracs else 0.0),
        "steps": total_steps - start,
        # tier accounting (see docs/storage.md)
        "store_backend": store_backend,
        "io_backend": io_backend,
        "spill_drain_seconds": spill_drain_seconds,
        "tier_stats": tier_stats,
        # fsck report of the scrub-on-start pass (None when not run)
        "scrub_report": scrub_report,
        # sharded-save accounting (1 = classic global-array save)
        "shard_participants": shard_participants,
        # every committed event's last_save_stats, in commit order
        "save_events": save_events,
        # the resume's last_restore_stats (None for a fresh start)
        "restore": dict(mgr.last_restore_stats) if resume else None,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3.2-3b")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--policy", default="full",
                    choices=["full", "parity", "filtered", "interval",
                             "topk_delta"])
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--codec", default="auto",
                    choices=["auto", "zstd", "none", "int8"])
    ap.add_argument("--store-backend", default="local",
                    choices=["local", "memory", "tiered", "remote",
                             "remote3"],
                    help="object IO tier: local POSIX tree, volatile RAM, "
                         "RAM hot tier with async spill to disk, simulated "
                         "remote object store, or the three-tier "
                         "RAM -> disk -> remote composition")
    ap.add_argument("--remote-latency", type=float, default=0.0,
                    help="remote/remote3: simulated per-op latency (s)")
    ap.add_argument("--remote-error-rate", type=float, default=0.0,
                    help="remote/remote3: seeded probabilistic per-op "
                         "fault rate of the simulated service")
    ap.add_argument("--remote-seed", type=int, default=0,
                    help="remote/remote3: fault-schedule seed (a given "
                         "seed replays the same transient faults)")
    ap.add_argument("--scrub-on-start", action="store_true",
                    help="run the store-wide integrity scrub (fsck) "
                         "before training/resume: repair corrupt tier "
                         "copies from any good one, quarantine the "
                         "unrecoverable")
    ap.add_argument("--io-backend", default="thread",
                    choices=["thread", "process"],
                    help="IO lane worker backend: 'process' runs the hot "
                         "byte work (hashing, codecs, atomic writes) in "
                         "subprocess workers over shared memory, escaping "
                         "the GIL; 'thread' keeps it in-process")
    ap.add_argument("--io-workers", type=int,
                    help="process backend: number of subprocess IO "
                         "workers (default max(2, pool threads))")
    ap.add_argument("--writer-threads", type=int, default=2,
                    help="async writeback lanes; raise to widen the "
                         "writeback pipe against a high-latency store")
    ap.add_argument("--spill-threads", type=int, default=2,
                    help="tiered backend: threads on the spill lane of "
                         "the shared transfer pool")
    ap.add_argument("--hot-budget-mb", type=int,
                    help="tiered backend: hot-tier byte budget; spilled "
                         "objects are LRU-evicted beyond it")
    ap.add_argument("--spill-barrier", action="store_true",
                    help="tiered backend: wait for durable-tier spill "
                         "before each manifest commit")
    ap.add_argument("--shard-participants", type=int, default=1,
                    help="shard-native save: N virtual participants each "
                         "persist only their owned slices; the manifest "
                         "commits through the two-phase barrier")
    ap.add_argument("--sync-save", action="store_true")
    ap.add_argument("--ckpt-spread-steps", type=int, default=0,
                    help="zero-stall pipeline: slice each checkpoint "
                         "event's host-side gather/encode/write across N "
                         "training steps, overlapped with compute "
                         "(0 = classic synchronous save; requires the "
                         "fingerprint pipeline)")
    ap.add_argument("--no-fingerprint", action="store_true",
                    help="legacy full-gather save path (no device-side "
                         "block fingerprinting)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at",
                    help="simulated failure: a bare step number N dies at "
                         "that step boundary; N@<point> (e.g. 12@spill) "
                         "arms the named crash point at step N so the "
                         "death happens mid-save inside that pipeline "
                         "stage; N@<point>:K fires on the Kth hit")
    ap.add_argument("--fail-mode", default="raise",
                    choices=["raise", "exit"],
                    help="armed crash points raise InjectedCrash (clean "
                         "traceback) or os._exit (hard kill, no cleanup)")
    ap.add_argument("--handle-sigterm", action="store_true",
                    help="treat SIGTERM as a preemption: immediate "
                         "full-capture hot save, drain queued/spilling "
                         "writes, exit with code %d" % EXIT_PREEMPTED)
    ap.add_argument("--progress-file",
                    help="append kind,step,time progress lines here (the "
                         "supervisor's monitoring feed)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-csv")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    use_compile_cache()
    out = train(arch=args.arch, reduced=args.smoke, total_steps=args.steps,
                batch=args.batch, seq_len=args.seq_len,
                policy_name=args.policy, ckpt_interval=args.ckpt_interval,
                ckpt_dir=args.ckpt_dir, ckpt_async=not args.sync_save,
                ckpt_fingerprint=not args.no_fingerprint,
                ckpt_spread_steps=args.ckpt_spread_steps,
                codec=args.codec, store_backend=args.store_backend,
                io_backend=args.io_backend, io_workers=args.io_workers,
                writer_threads=args.writer_threads,
                spill_threads=args.spill_threads,
                hot_budget_mb=args.hot_budget_mb,
                spill_barrier=args.spill_barrier,
                remote_opts={"latency": args.remote_latency,
                             "error_rate": args.remote_error_rate,
                             "seed": args.remote_seed},
                scrub_on_start=args.scrub_on_start,
                shard_participants=args.shard_participants,
                resume=args.resume, fail_at=args.fail_at,
                fail_mode=args.fail_mode,
                handle_sigterm=args.handle_sigterm,
                progress_file=args.progress_file,
                seed=args.seed, log_csv=args.log_csv)
    out.pop("losses")
    print(json.dumps(out, indent=2))
    if out["preempted"]:
        sys.exit(EXIT_PREEMPTED)


if __name__ == "__main__":
    main()
