#!/usr/bin/env python3
"""On-chip smoke of the checkpointing main path at full model width.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # the four chips of one TPU host

One chip: ``mamba2-370m`` unreduced (48 layers, d_model 1024, 368.3M
parameters, 50 layer units, a 4.80 GiB train state) through the entry
points a user calls, ``repro.launch.train.train`` and
``repro.launch.serve.serve``, in this order:

a. the devices JAX sees;
b. bit-exact resume: a ``full``-policy run with sync saves dies with
   ``fail_at``, resumes, and its losses equal an uninterrupted run's;
c. selective saves: a ``parity`` run whose later events write fewer bytes
   than the first;
d. overlapped saves (``ckpt_spread_steps=2``, the ``block_gather``
   kernel): the checkpoint restores equal to the sync save of phase b;
e. the fingerprint and gather kernels on real leaves (bf16 and fp32, the
   50280x1024 embedding with its padded tail block) equal the numpy
   oracles;
f. serving: a hot swap from an older manifest to the newest generates
   the same tokens as a cold load of the newest.

Four chips: the mamba2-370m state sharded on a 1x4 mesh, saved by four
shard participants, restored as four participants on a 2x2 mesh; the
stitched result equals the single-layout restore bit for bit, and every
participant reads fewer bytes than the full restore.  No other phase runs.

Everything runs in this one process (a chip belongs to one process at a
time).  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
it is printed only when every phase passed.  Without a TPU the script
exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import logging
import shutil
import statistics
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint.fingerprint import KERNEL_LEAF_KEYS  # noqa: E402
from repro.checkpoint.saver import CheckpointManager  # noqa: E402
from repro.checkpoint.serial import flatten_with_paths  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import LayerRegistry, make_policy  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import serve  # noqa: E402
from repro.launch.train import SimulatedFailure, train  # noqa: E402
from repro.models import build_model  # noqa: E402

GIB = 2 ** 30


class SmokeFailure(AssertionError):
    pass


def expect(cond, *info) -> None:
    """A check that stays under ``python -O`` (plain asserts do not)."""
    if not cond:
        raise SmokeFailure(*info)


@dataclasses.dataclass(frozen=True)
class Smoke:
    """The model and the shapes the one-chip phases run at."""
    arch: str = "mamba2-370m"
    reduced: bool = False
    batch: int = 4
    seq_len: int = 1024
    seed: int = 0
    serve_batch: int = 4
    prompt_len: int = 128
    new_tokens: int = 16

    def train_kw(self, **kw):
        return dict(arch=self.arch, reduced=self.reduced, batch=self.batch,
                    seq_len=self.seq_len, seed=self.seed, **kw)


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + json.dumps(fields, default=str), flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> dict:
    """Peak device memory per device, where the backend reports it."""
    return {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()}


def step_times(out: dict) -> dict:
    s = out["step_seconds"]
    return {"first_step_seconds": s[0],
            "median_step_seconds": statistics.median(s[1:]) if s[1:]
            else None,
            "steps": len(s)}


def event_summary(e: dict) -> dict:
    keys = ("step", "selected_units", "written_bytes", "d2h_bytes",
            "dirty_block_frac", "snapshot_seconds", "stall_seconds",
            "writeback_seconds", *KERNEL_LEAF_KEYS)
    return {k: e.get(k) for k in keys}


def kernel_counts(events) -> dict:
    return {k: sum(e.get(k, 0) for e in events) for k in KERNEL_LEAF_KEYS}


def state_digest(tree) -> str:
    """Digest of every leaf's path and exact bytes."""
    h = hashlib.blake2b(digest_size=16)
    for path, x in flatten_with_paths(tree):
        h.update(path.encode())
        h.update(np.ascontiguousarray(jax.device_get(x)).reshape(-1)
                 .view(np.uint8))
    return h.hexdigest()


def restore(model, root: Path, step: int):
    mgr = CheckpointManager(root, LayerRegistry(model),
                            make_policy("full", model.layer_units()),
                            async_save=False)
    try:
        state = mgr.restore(steps_lib.state_specs(model), step=step)
        return state, dict(mgr.last_restore_stats)
    finally:
        mgr.close()


def restore_summary(stats: dict) -> dict:
    return {k: stats.get(k) for k in ("step", "seconds", "bytes_read",
                                      "objects_read", "h2d_bytes")}


# ------------------------------------------------------------ one chip
def phase_model(cfg: Smoke):
    model = build_model(get_config(cfg.arch, reduced=cfg.reduced))
    specs = steps_lib.state_specs(model)
    n = sum(x.size for x in jax.tree.leaves(specs["params"]))
    say("model", arch=model.cfg.name, layers=model.cfg.num_layers,
        d_model=model.cfg.d_model, vocab=model.cfg.vocab_size, params=n,
        params_millions=round(n / 1e6, 1), units=len(model.layer_units()),
        state_gib=sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(specs)) / GIB)
    return model


def phase_resume(cfg: Smoke, model, tmp: Path):
    """b: full-policy sync saves, a failure, a resume; the resumed losses
    equal an uninterrupted run's.  Returns (reference losses, digest of
    the sync-saved state at the last step)."""
    kw = cfg.train_kw(total_steps=4, policy_name="full", ckpt_interval=2)
    ref = train(ckpt_dir=str(tmp / "reference"),
                **{**kw, "ckpt_interval": 5})  # no save event
    say("b reference", **step_times(ref), losses=ref["losses"])
    root = tmp / "resume"
    try:
        train(ckpt_dir=str(root), fail_at=3, **kw)
    except SimulatedFailure:
        pass
    else:
        raise SmokeFailure("the run with fail_at=3 did not fail")
    out = train(ckpt_dir=str(root), resume=True, **kw)
    ref_losses = dict(ref["losses"])
    expect(out["steps"] == 2, out["steps"])
    for step, loss in out["losses"]:
        expect(np.isfinite(loss) and loss == ref_losses[step],
               step, loss, ref_losses[step])
    state, stats = restore(model, root, step=4)
    digest = state_digest(state)
    del state
    say("b resume", bit_exact=True, resumed_losses=out["losses"],
        resume_restore=restore_summary(out["restore"]),
        events=[event_summary(e) for e in out["save_events"]],
        ckpt_bytes=out["ckpt_bytes"], step4_restore=restore_summary(stats),
        step4_digest=digest)
    shutil.rmtree(tmp / "reference")
    shutil.rmtree(root)
    return ref_losses, digest, out["save_events"]


def phase_parity(cfg: Smoke, tmp: Path):
    """c: parity saves; every event after the first writes less."""
    root = tmp / "parity"
    out = train(ckpt_dir=str(root), **cfg.train_kw(
        total_steps=3, policy_name="parity", ckpt_interval=1))
    written = [e["written_bytes"] for e in out["save_events"]]
    expect(len(written) >= 3, written)
    expect(all(w < written[0] for w in written[1:]), written)
    say("c parity", written_bytes=written,
        events=[event_summary(e) for e in out["save_events"]],
        ckpt_bytes=out["ckpt_bytes"])
    shutil.rmtree(root)
    return out["save_events"]


def phase_overlap(cfg: Smoke, model, tmp: Path, ref_losses, sync_digest):
    """d: overlapped saves restore equal to the sync save of the same
    step.  Returns the checkpoint root (kept for serving), the restored
    state and the save events."""
    root = tmp / "overlap"
    out = train(ckpt_dir=str(root), ckpt_spread_steps=2, **cfg.train_kw(
        total_steps=4, policy_name="full", ckpt_interval=2))
    expect(out["save_mode"] == "overlapped")
    for step, loss in out["losses"]:
        expect(loss == ref_losses[step], step, loss, ref_losses[step])
    counts = kernel_counts(out["save_events"])
    expect(counts["gather_leaves_pallas"] + counts["gather_leaves_xla"] > 0)
    state, stats = restore(model, root, step=4)
    digest = state_digest(state)
    expect(digest == sync_digest, digest, sync_digest)
    say("d overlap", restores_equal_sync_save=True, **step_times(out),
        events=[event_summary(e) for e in out["save_events"]],
        overflow_redispatches=out["overflow_redispatches"],
        ckpt_bytes=out["ckpt_bytes"], restore=restore_summary(stats),
        peak_bytes_in_use=peak_bytes())
    return root, state, out["save_events"]


def _pick_leaves(state):
    """Real leaves of each dtype: the embedding (its last block is
    padded), one layer's projection, and a tiny per-head vector."""
    p, o = state["params"], state["opt"]
    return {
        "params/embed/w": p["embed"]["w"],
        "opt/master/embed/w": o["master"]["embed"]["w"],
        "params/blocks/mixer/w_x[0]": p["blocks"]["mixer"]["w_x"][0],
        "opt/v/blocks/mixer/w_x[0]": o["v"]["blocks"]["mixer"]["w_x"][0],
        "opt/m/blocks/mixer/A_log[0]": o["m"]["blocks"]["mixer"]["A_log"][0],
    }


def phase_fingerprints(state, interpret=None):
    """e: per-block fingerprints and a dirty-block gather on device, each
    equal to its numpy oracle."""
    from repro.kernels import block_fp as bfp
    from repro.kernels import block_gather as bgather
    from repro.kernels.block_fp.ref import fingerprint_bytes
    from repro.kernels.block_gather.ref import gather_dirty_oracle

    rows = []
    for name, x in _pick_leaves(state).items():
        host = np.asarray(jax.device_get(x))
        want = fingerprint_bytes(host.tobytes())
        fp, _ = bfp.block_fingerprint(x, interpret=interpret)
        expect(np.array_equal(np.asarray(fp), want), name)
        # mark the first, a middle and the last block dirty
        nb = want.shape[0]
        ref = want.copy()
        dirty = sorted({0, nb // 2, nb - 1})
        ref[dirty, 0] += np.uint32(1)
        g = bgather.gather_dirty(x, ref, capacity=len(dirty),
                                 interpret=interpret)
        _, idx, blocks, count = gather_dirty_oracle(host, ref,
                                                    capacity=g.capacity)
        expect(int(g.count) == count == len(dirty), name, int(g.count))
        expect(np.array_equal(np.asarray(g.idx), idx), name)
        expect(np.array_equal(np.asarray(g.blocks).view(np.uint8),
                              blocks.view(np.uint8)), name)
        rows.append({"leaf": name, "shape": list(x.shape),
                     "dtype": str(x.dtype), "blocks": nb,
                     "padded_tail": host.nbytes % 65536 != 0})
    path = bfp.kernel_path(interpret)
    say("e fingerprints", path=path, leaves=rows, equal_to_oracle=True)
    return {f"fp_leaves_{path}": len(rows),
            f"gather_leaves_{path}": len(rows)}


def phase_serve(cfg: Smoke, root: Path):
    """f: hot swap from the older manifest to the newest generates what a
    cold load of the newest generates."""
    kw = dict(arch=cfg.arch, reduced=cfg.reduced, batch=cfg.serve_batch,
              prompt_len=cfg.prompt_len, new_tokens=cfg.new_tokens,
              from_ckpt=str(root), seed=cfg.seed)
    hot = serve(from_step=2, hot_swap=True, **kw)
    cold = serve(from_step=4, **kw)
    expect(hot["served_step"] == cold["served_step"] == 4,
           hot["served_step"], cold["served_step"])
    expect(hot["tokens_digest"] == cold["tokens_digest"],
           hot["tokens_digest"], cold["tokens_digest"])
    swap = hot["swap"]
    say("f serve", tokens_digest=hot["tokens_digest"], swap_equals_cold=True,
        swap={k: swap.get(k) for k in ("step_from", "step_to", "seconds",
                                       "bytes_read", "units_swapped",
                                       "units_skipped")},
        hot_restore=restore_summary(hot["restore"]),
        cold_restore=restore_summary(cold["restore"]),
        prefill_seconds=cold["prefill_seconds"],
        decode_tokens_per_s=cold["decode_tokens_per_s"])


def one_chip(cfg: Smoke, tmp: Path, *, interpret=None,
             require_pallas: bool = True) -> None:
    say("a devices", **device_info(), free_disk_gib=shutil.disk_usage(
        tmp).free / GIB)
    model = phase_model(cfg)
    ref_losses, sync_digest, ev_b = phase_resume(cfg, model, tmp)
    ev_c = phase_parity(cfg, tmp)
    root, state, ev_d = phase_overlap(cfg, model, tmp, ref_losses,
                                      sync_digest)
    on_chip = phase_fingerprints(state, interpret=interpret)
    del state
    phase_serve(cfg, root)
    shutil.rmtree(root)
    counts = kernel_counts(ev_b + ev_c + ev_d)
    say("kernels", save_events=counts, fingerprint_phase=on_chip,
        peak_bytes_in_use=peak_bytes())
    if require_pallas:
        expect(counts["fp_leaves_pallas"] > 0, counts)
        expect(counts["fp_leaves_xla"] == 0, counts)


# ---------------------------------------------------------- four chips
def four_chips(cfg: Smoke, tmp: Path) -> None:
    """Sharded save on a 1x4 mesh, resharded restore on 2x2."""
    from repro.checkpoint.sharded import (
        ShardedCheckpointer,
        combine_states,
        participant_wanted,
    )
    from repro.launch.elastic import restore_on_mesh
    from repro.launch.mesh import make_debug_mesh

    info = device_info()
    say("a devices", **info)
    expect(info["count"] == 4, info)
    model = phase_model(cfg)
    reg = LayerRegistry(model)
    like = steps_lib.state_specs(model)
    sh = steps_lib.state_shardings(model, make_debug_mesh(1, 4))

    def populated(key):
        # init state plus non-zero optimizer moments, as after training
        s = steps_lib.init_state(model, key)
        master = s["opt"]["master"]
        s["opt"]["m"] = jax.tree.map(lambda x: x * 1e-3, master)
        s["opt"]["v"] = jax.tree.map(lambda x: x * x * 1e-6, master)
        return s

    def weights_and_moments(s):
        return {"params": s["params"], "opt": s["opt"]}

    state = jax.jit(populated, out_shardings=sh)(jax.random.key(cfg.seed))
    leaves = jax.tree.leaves(weights_and_moments(state))
    expect(all(len(x.sharding.device_set) == 4 for x in leaves))
    partitioned = sum(not x.sharding.is_fully_replicated for x in leaves)
    expect(partitioned > 0)
    want = state_digest(weights_and_moments(state))

    root = tmp / "sharded"
    mgr = CheckpointManager(root, reg, make_policy("full",
                                                   model.layer_units()))
    ShardedCheckpointer(mgr, 4, shardings=sh).save(state, step=1)
    save = mgr.last_save_stats
    del state
    single = mgr.restore(like)
    full = dict(mgr.last_restore_stats)
    mgr.close()
    expect(state_digest(weights_and_moments(single)) == want)
    expect(int(single["step"]) == 1)
    del single
    say("sharded save", participants=4, leaves=len(leaves),
        partitioned_leaves=partitioned, **event_summary(save),
        shard_objects=save["shard_objects"],
        single_layout_restore=restore_summary(full))

    mesh = make_debug_mesh(2, 2)
    sh2 = steps_lib.state_shardings(model, mesh)
    results, wanteds, reads = [], [], []
    for pid in range(4):
        stats: dict = {}
        res = restore_on_mesh(root, model, mesh, participant=(pid, 4),
                              stats=stats)
        expect(all(len(x.sharding.device_set) == 4
                   for x in jax.tree.leaves(weights_and_moments(res))))
        expect(stats["bytes_read"] < full["bytes_read"],
               pid, stats["bytes_read"], full["bytes_read"])
        results.append(res)
        wanteds.append(participant_wanted(reg, pid, 4, shardings=sh2))
        reads.append(restore_summary(stats) | {
            "shards_skipped": stats["shards_skipped"]})
    combined = combine_states(like, reg, results, wanteds)
    expect(state_digest(weights_and_moments(combined)) == want)
    expect(int(combined["step"]) == 1)
    say("resharded restore", mesh="2x2", participants=reads,
        bit_exact=True, full_restore_bytes=full["bytes_read"],
        peak_bytes_in_use=peak_bytes())
    shutil.rmtree(root)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded save and resharded "
                         "restore across the four chips of one host")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING)
    cache_dir = use_compile_cache()
    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU ({info}); nothing was run",
              file=sys.stderr)
        return 1
    say("compile cache", dir=cache_dir)
    tmp = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    try:
        if args.chips == 4:
            four_chips(Smoke(), tmp)
        else:
            one_chip(Smoke(), tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
