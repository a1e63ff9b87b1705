"""Resiliency supervisor: run the trainer as a child, survive its deaths.

The TierCheck/DataStates-LLM orchestration layer this repo was missing:
PRs 1-5 built crash-safe commits, tiered durability, and elastic
resharded restore as *latent* properties — this module is the loop that
exercises them continuously and accounts for what failures actually
cost.

    sup = Supervisor("/ckpt", steps=48, interval=8, participants=(2, 1),
                     injections=[Injection("kill", at_step=11),
                                 Injection("sigterm", at_step=30)],
                     run_dir="/tmp/run")
    report = sup.run()          # -> goodput / MTTR / lost-step report

Lifecycle per attempt:

1. Launch ``python -m repro.launch.train`` as a subprocess with
   ``--handle-sigterm`` and a ``--progress-file`` feed; ``--resume`` is
   added iff the checkpoint root already has a committed manifest.  Each
   attempt may run on a *smaller* participant count than the last
   (``participants`` is the per-attempt plan) — the elastic-restart path:
   chunks store global arrays, so the restore reshards onto whatever is
   left.
2. Tail the progress feed.  If this attempt carries an injection:
   ``kill`` sends SIGKILL at the target step (a hard node loss — no
   flushing, no goodbye), ``sigterm`` sends SIGTERM (a preemption notice:
   the trainer commits an immediate full-capture HOT save — the durable
   spill barrier is waived — drains the spill backlog during the grace
   period, and exits ``EXIT_PREEMPTED``), ``crash`` passes
   ``--fail-at N@point --fail-mode exit`` so the child kills itself
   *inside* a named save-pipeline stage (repro.checkpoint.faults).
3. Classify the exit: 0 = run complete; ``EXIT_PREEMPTED`` = clean
   preemption (lost steps must be 0); anything else = crash.  For every
   interruption, read the checkpoint root's LATEST pointer — whatever
   the previous manifest was, it is authoritative — and account:

   - ``lost_steps``   = last step the child executed - last committed
     step (bounded by the checkpoint cadence for crashes, 0 for
     preemptions),
   - ``lost_seconds`` = wall time between the last commit and the death,
   - ``mttr_seconds`` = death -> next attempt's first progress line
     (restart + restore + re-JIT; the optional pre-launch restore probe
     is counted in here too).

4. Optionally scrub the store and probe restorability first
   (:func:`elastic.probe_restore` on a single-host mesh), then relaunch.
   Both run in a short child that exits before the relaunch: the
   supervisor itself never imports JAX, so on an accelerator host every
   trainer attempt can take the chip.  Stop after ``max_restarts``
   unscheduled deaths (injections don't count against it).

``run()`` returns the goodput report; the CLI (and
scripts/supervisor_smoke.py) writes it to ``BENCH_resiliency.json`` via
``benchmarks/_util.write_bench_json``:

- ``goodput_steps`` = total_steps / step_executions — the fraction of
  executed train steps that contributed to the finished run (re-executed
  tails after each crash are the waste),
- ``goodput_wall``  = 1 - (lost + restart time) / total wall — the
  DataStates-LLM wall-clock form.

See docs/resiliency.md for the full protocol and metric definitions.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import signal
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

log = logging.getLogger("repro.supervisor")

#: Keep in sync with repro.launch.train.EXIT_PREEMPTED (imported lazily
#: there to keep this module import-light for the CLI).
EXIT_PREEMPTED = 17


@dataclasses.dataclass
class Injection:
    """One scheduled failure drill.  ``kind``:

    - ``"kill"``    — SIGKILL once the child reports step >= at_step,
    - ``"sigterm"`` — SIGTERM ditto (preemption notice),
    - ``"crash"``   — the child arms ``at_step@crash_point`` with
      ``--fail-mode exit`` and dies inside that pipeline stage on its
      own (no supervisor signal involved).
    """
    kind: str
    at_step: int
    crash_point: Optional[str] = None

    def __post_init__(self) -> None:
        if self.kind not in ("kill", "sigterm", "crash"):
            raise ValueError(f"unknown injection kind {self.kind!r}")
        if self.kind == "crash" and not self.crash_point:
            raise ValueError("kind='crash' needs a crash_point")


def _read_progress(path: Path) -> List[Tuple[str, int, float]]:
    """Parse a trainer ``--progress-file`` feed; tolerant of a torn last
    line (the writer may have died mid-write)."""
    out: List[Tuple[str, int, float]] = []
    if not path.is_file():
        return out
    for line in path.read_text().splitlines():
        parts = line.strip().split(",")
        if len(parts) != 3:
            continue
        try:
            out.append((parts[0], int(parts[1]), float(parts[2])))
        except ValueError:
            continue
    return out


def _latest_committed(ckpt_dir: Path) -> Optional[int]:
    # LATEST is the commit pointer (manifest-last protocol): whatever it
    # names is authoritative, regardless of how the writer died.
    p = ckpt_dir / "LATEST"
    if not p.is_file():
        return None
    try:
        return int(p.read_text().strip())
    except ValueError:
        return None


class Supervisor:
    def __init__(
        self,
        ckpt_dir: str | Path,
        *,
        steps: int,
        interval: int,
        run_dir: str | Path,
        arch: str = "llama3.2-3b",
        batch: int = 2,
        seq_len: int = 16,
        policy: str = "full",
        store_backend: str = "local",
        io_backend: str = "thread",
        io_workers: Optional[int] = None,
        participants: Sequence[int] = (1,),
        injections: Sequence[Injection] = (),
        verify_restore: bool = False,
        scrub_on_restart: bool = False,
        max_restarts: int = 2,
        attempt_timeout: float = 600.0,
        poll: float = 0.05,
        seed: int = 0,
        extra_args: Sequence[str] = (),
    ) -> None:
        self.ckpt_dir = Path(ckpt_dir)
        self.run_dir = Path(run_dir)
        self.steps = int(steps)
        self.interval = int(interval)
        self.arch = arch
        self.batch = batch
        self.seq_len = seq_len
        self.policy = policy
        self.store_backend = store_backend
        self.io_backend = io_backend
        self.io_workers = io_workers
        self.participants = [int(p) for p in participants] or [1]
        self.injections = list(injections)
        self.verify_restore = verify_restore
        self.scrub_on_restart = scrub_on_restart
        self.max_restarts = int(max_restarts)
        self.attempt_timeout = float(attempt_timeout)
        self.poll = float(poll)
        self.seed = seed
        self.extra_args = list(extra_args)

    # ----------------------------------------------------------- plumbing
    def _participants_for(self, attempt: int) -> int:
        plan = self.participants
        return plan[attempt] if attempt < len(plan) else plan[-1]

    def _child_env(self) -> Dict[str, str]:
        env = dict(os.environ)
        src = str(Path(__file__).resolve().parents[2])
        env["PYTHONPATH"] = src + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        return env

    def _argv(self, attempt: int, injection: Optional[Injection],
              progress: Path, losses: Path) -> List[str]:
        argv = [
            sys.executable, "-m", "repro.launch.train",
            "--arch", self.arch,
            "--steps", str(self.steps),
            "--batch", str(self.batch),
            "--seq-len", str(self.seq_len),
            "--policy", self.policy,
            "--ckpt-interval", str(self.interval),
            "--ckpt-dir", str(self.ckpt_dir),
            "--store-backend", self.store_backend,
            "--io-backend", self.io_backend,
            "--shard-participants", str(self._participants_for(attempt)),
            "--seed", str(self.seed),
            "--handle-sigterm",
            "--progress-file", str(progress),
            "--log-csv", str(losses),
        ]
        if self.io_workers is not None:
            argv += ["--io-workers", str(self.io_workers)]
        if _latest_committed(self.ckpt_dir) is not None:
            argv.append("--resume")
        if injection is not None and injection.kind == "crash":
            argv += ["--fail-at",
                     f"{injection.at_step}@{injection.crash_point}",
                     "--fail-mode", "exit"]
        argv += self.extra_args
        return argv

    def _in_child(self, module: str, func: str, **kwargs) -> Dict[str, Any]:
        """``module.func(**kwargs)`` in a short child process that exits
        before the next attempt starts; returns its JSON result.  Scrub
        and probe import JAX, and this process must stay off the
        accelerator: a parent holding the chip would keep every relaunched
        trainer from it."""
        code = ("import json, sys\n"
                f"from {module} import {func} as fn\n"
                "out = fn(**json.loads(sys.argv[1]))\n"
                "print(json.dumps(out, default=str))\n")
        res = subprocess.run([sys.executable, "-c", code, json.dumps(kwargs)],
                             env=self._child_env(), capture_output=True,
                             text=True, check=False)
        if res.returncode != 0:
            raise RuntimeError(f"{module}.{func} failed in its child process "
                               f"(exit {res.returncode}):\n{res.stderr}")
        return json.loads(res.stdout.strip().splitlines()[-1])

    def _probe(self) -> Optional[Dict[str, Any]]:
        """Pre-relaunch restorability check (counted into MTTR)."""
        if not self.verify_restore:
            return None
        if _latest_committed(self.ckpt_dir) is None:
            # Death before the first commit: nothing to probe, and the
            # relaunch (without --resume) starts from scratch anyway.
            return None
        return self._in_child("repro.launch.elastic", "probe_restore",
                              ckpt_root=str(self.ckpt_dir), arch=self.arch,
                              store_backend=self.store_backend)

    def _scrub(self) -> Optional[Dict[str, Any]]:
        """Pre-relaunch integrity scrub (fsck): a crash is exactly when
        bit-rot or a torn tier copy surfaces, so repair/quarantine BEFORE
        the next attempt plans its restore.  The scrub runs in a child
        between attempts against the tiers that survive the dead trainer
        ("local" disk view for RAM-hot backends — a child's hot tier died
        with it)."""
        if not self.scrub_on_restart:
            return None
        if _latest_committed(self.ckpt_dir) is None:
            return None
        backend = (self.store_backend
                   if self.store_backend in ("remote", "remote3")
                   else "local")
        rep = self._in_child("repro.checkpoint.scrub", "scrub_root",
                             root=str(self.ckpt_dir), backend=backend)
        return {"checked_objects": rep["checked_objects"],
                "repaired": len(rep["repaired"]),
                "unrecoverable": len(rep["unrecoverable"]),
                "demoted_manifests": rep["demoted_manifests"]}

    # ---------------------------------------------------------------- run
    def run(self) -> Dict[str, Any]:
        self.run_dir.mkdir(parents=True, exist_ok=True)
        t_run0 = time.time()
        interruptions: List[Dict[str, Any]] = []
        attempts: List[Dict[str, Any]] = []
        injection_queue = list(self.injections)
        unscheduled_deaths = 0
        attempt = 0
        completed = False

        while not completed:
            injection = injection_queue.pop(0) if injection_queue else None
            progress = self.run_dir / f"progress-{attempt}.log"
            losses = self.run_dir / f"losses-{attempt}.csv"
            child_log = self.run_dir / f"attempt-{attempt}.log"
            argv = self._argv(attempt, injection, progress, losses)
            n_parts = self._participants_for(attempt)
            log.info("attempt %d: participants=%d injection=%s",
                     attempt, n_parts, injection)
            t_launch = time.time()
            with open(child_log, "wb") as lf:
                proc = subprocess.Popen(argv, env=self._child_env(),
                                        stdout=lf, stderr=subprocess.STDOUT)
                exit_code, t_death = self._monitor(proc, progress, injection)
            lines = _read_progress(progress)
            steps_executed = sum(1 for k, _, _ in lines if k == "step")
            reached = max((s for k, s, _ in lines if k == "step"), default=0)
            t_start_line = next((t for k, _, t in lines if k == "start"),
                                t_launch)
            attempts.append({
                "attempt": attempt,
                "participants": n_parts,
                "exit_code": exit_code,
                "steps_executed": steps_executed,
                "reached_step": reached,
                "launch_to_first_progress": t_start_line - t_launch,
                "seconds": t_death - t_launch,
            })

            if exit_code == 0:
                completed = True
                break

            committed = _latest_committed(self.ckpt_dir) or 0
            # Wall time from the last commit-ish event (a ckpt/preempt
            # line, else the attempt start) to the death: the work that
            # existed only in the lost process.
            t_last_commit = max(
                (t for k, s, t in lines
                 if k in ("ckpt", "preempt") and s <= committed),
                default=t_start_line)
            interruption = {
                "attempt": attempt,
                "kind": (injection.kind if injection is not None
                         else "unscheduled"),
                "injected_at_step": (injection.at_step
                                     if injection is not None else None),
                "crash_point": (injection.crash_point
                                if injection is not None else None),
                "exit_code": exit_code,
                "preempted": exit_code == EXIT_PREEMPTED,
                "reached_step": reached,
                "committed_step": committed,
                "lost_steps": max(0, reached - committed),
                "lost_seconds": max(0.0, t_death - t_last_commit),
            }
            if injection is None:
                unscheduled_deaths += 1
                if unscheduled_deaths > self.max_restarts:
                    interruptions.append(interruption)
                    raise RuntimeError(
                        f"{unscheduled_deaths} unscheduled child deaths "
                        f"(exit {exit_code}) exceed max_restarts="
                        f"{self.max_restarts}; last attempt log: "
                        f"{child_log}")
            scrub = self._scrub()
            if scrub is not None:
                interruption["scrub"] = scrub
            probe = self._probe()
            if probe is not None:
                interruption["restore_probe"] = probe
            # MTTR closes when the NEXT attempt emits its first progress
            # line; filled in after relaunch.
            interruption["_t_death"] = t_death
            interruptions.append(interruption)
            attempt += 1

        # Close open MTTR windows against each following attempt's first
        # progress timestamp.
        for inter in interruptions:
            t_death = inter.pop("_t_death", None)
            if t_death is None:
                continue
            nxt = inter["attempt"] + 1
            lines = _read_progress(self.run_dir / f"progress-{nxt}.log")
            t_up = next((t for k, _, t in lines if k == "start"), None)
            inter["mttr_seconds"] = (max(0.0, t_up - t_death)
                                     if t_up is not None else None)

        total_wall = time.time() - t_run0
        step_executions = sum(a["steps_executed"] for a in attempts)
        lost_total = sum(i["lost_steps"] for i in interruptions)
        lost_seconds = sum(i["lost_seconds"] for i in interruptions)
        mttrs = [i["mttr_seconds"] for i in interruptions
                 if i.get("mttr_seconds") is not None]
        report = {
            "completed": completed,
            "total_steps": self.steps,
            "ckpt_interval": self.interval,
            "policy": self.policy,
            "store_backend": self.store_backend,
            "participants_plan": self.participants,
            "attempts": attempts,
            "interruptions": [
                {k: v for k, v in i.items() if not k.startswith("_")}
                for i in interruptions],
            "n_interruptions": len(interruptions),
            "lost_steps_total": lost_total,
            "lost_seconds_total": lost_seconds,
            "mttr_seconds_mean": (sum(mttrs) / len(mttrs)
                                  if mttrs else None),
            "step_executions": step_executions,
            # scrub-on-restart accounting (fsck between attempts)
            "scrubs_run": sum(1 for i in interruptions if "scrub" in i),
            "scrub_repaired_total": sum(
                i["scrub"]["repaired"] for i in interruptions
                if "scrub" in i),
            "scrub_unrecoverable_total": sum(
                i["scrub"]["unrecoverable"] for i in interruptions
                if "scrub" in i),
            "goodput_steps": (self.steps / step_executions
                              if step_executions else None),
            "goodput_wall": (max(0.0, 1.0 - (lost_seconds + sum(mttrs))
                                 / total_wall)
                             if total_wall > 0 else None),
            "total_wall_seconds": total_wall,
        }
        (self.run_dir / "report.json").write_text(
            json.dumps(report, indent=2, default=str))
        return report

    def _monitor(self, proc: subprocess.Popen, progress: Path,
                 injection: Optional[Injection]
                 ) -> Tuple[int, float]:
        """Poll the child + its progress feed; fire the injection's
        signal at the target step.  Returns (exit_code, death_time)."""
        deadline = time.time() + self.attempt_timeout
        sig = None
        if injection is not None and injection.kind in ("kill", "sigterm"):
            sig = (signal.SIGKILL if injection.kind == "kill"
                   else signal.SIGTERM)
        fired = sig is None
        while True:
            rc = proc.poll()
            if rc is not None:
                return rc, time.time()
            if time.time() > deadline:
                proc.kill()
                proc.wait()
                raise TimeoutError(
                    f"trainer exceeded attempt_timeout="
                    f"{self.attempt_timeout}s (progress: {progress})")
            if not fired:
                lines = _read_progress(progress)
                reached = max((s for k, s, _ in lines if k == "step"),
                              default=-1)
                if reached >= injection.at_step:
                    log.info("firing %s at step %d (pid %d)",
                             injection.kind, reached, proc.pid)
                    proc.send_signal(sig)
                    fired = True
            time.sleep(self.poll)


def merged_losses(run_dir: str | Path) -> Dict[int, float]:
    """Merge every attempt's loss CSV into one step->loss map.

    Later attempts win on overlap — after a crash, the steps beyond the
    last commit are re-executed by the next attempt; under bit-exact
    resume both values are identical anyway, which is exactly what the
    acceptance tests assert against an uninterrupted reference run."""
    out: Dict[int, float] = {}
    run_dir = Path(run_dir)
    for path in sorted(run_dir.glob("losses-*.csv"),
                       key=lambda p: int(p.stem.split("-")[1])):
        for line in path.read_text().splitlines()[1:]:
            s, l = line.split(",")
            out[int(s)] = float(l)
    return out


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=16)
    ap.add_argument("--policy", default="full")
    ap.add_argument("--ckpt-interval", type=int, default=8)
    ap.add_argument("--store-backend", default="local")
    ap.add_argument("--io-backend", default="thread",
                    choices=["thread", "process"],
                    help="trainer IO lane worker backend (forwarded to "
                         "repro.launch.train --io-backend)")
    ap.add_argument("--io-workers", type=int,
                    help="process backend: subprocess IO worker count")
    ap.add_argument("--participants", default="1",
                    help="comma-separated per-attempt plan, e.g. 2,1")
    ap.add_argument("--inject", action="append", default=[],
                    help="kind:step[:point], e.g. kill:11, sigterm:30, "
                         "crash:12:spill (repeatable; one per attempt)")
    ap.add_argument("--verify-restore", action="store_true")
    ap.add_argument("--scrub-on-restart", action="store_true",
                    help="run the store-wide integrity scrub (fsck) "
                         "between attempts: repair corrupt tier copies, "
                         "quarantine the unrecoverable before relaunch")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)

    injections = []
    for spec in args.inject:
        parts = spec.split(":")
        injections.append(Injection(
            parts[0], int(parts[1]),
            crash_point=parts[2] if len(parts) > 2 else None))
    sup = Supervisor(
        args.ckpt_dir, run_dir=args.run_dir, arch=args.arch,
        steps=args.steps, batch=args.batch, seq_len=args.seq_len,
        policy=args.policy, interval=args.ckpt_interval,
        store_backend=args.store_backend,
        io_backend=args.io_backend, io_workers=args.io_workers,
        participants=[int(p) for p in args.participants.split(",")],
        injections=injections, verify_restore=args.verify_restore,
        scrub_on_restart=args.scrub_on_restart,
        seed=args.seed)
    report = sup.run()
    try:
        repo_root = Path(__file__).resolve().parents[3]
        if str(repo_root) not in sys.path:
            sys.path.insert(0, str(repo_root))
        from benchmarks._util import write_bench_json
        write_bench_json("resiliency", report)
    except ImportError:
        # Installed-package layout (no benchmarks/ sibling): the report
        # is still on disk in run_dir.
        print(json.dumps(report, indent=2, default=str))


if __name__ == "__main__":
    main()
