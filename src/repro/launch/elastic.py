"""Elastic restart: restore any checkpoint onto any mesh.

Chunks store *global* arrays (device-count independent), so recovery after
losing nodes — or scaling up — is just a restore with the new mesh's
shardings.  ``restore_on_mesh`` builds the target NamedShardings from the
model's logical axes and hands them to the streaming restore engine,
which places every unit on the mesh as it comes off disk (H2D overlaps
the remaining reads — see docs/restore.md).

    state = restore_on_mesh(ckpt_root, model, mesh)
    weights = restore_on_mesh(ckpt_root, model, mesh, parts=("params",))

Exercised by tests/test_mesh_subprocess.py and tests/test_restore_engine.py
in subprocesses with 8 host devices (save on 1x1, restore on 2x4 / 4x2).
"""
from __future__ import annotations

from pathlib import Path
from typing import Any, Dict, Optional, Sequence, Tuple

from jax.sharding import Mesh

from repro.core import LayerRegistry, make_policy
from repro.checkpoint.restore import PARTS_ALL
from repro.checkpoint.saver import CheckpointManager
from repro.launch import steps as steps_lib
from repro.models.model_api import BaseLM

PyTree = Any


def restore_on_mesh(ckpt_root: str | Path, model: BaseLM, mesh: Mesh,
                    *, step: Optional[int] = None,
                    parts: Tuple[str, ...] = PARTS_ALL,
                    units: Optional[Sequence[str]] = None,
                    pipelined: bool = True,
                    store_backend: str = "local",
                    participant: Optional[Tuple[int, int]] = None,
                    stats: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, PyTree]:
    """Restore a checkpoint sharded onto ``mesh``; thin wrapper over
    ``CheckpointManager.restore`` (``parts``/``units``/``pipelined``
    pass straight through to the restore engine).  ``store_backend``
    selects the IO tier stack — a restarted process reads the durable
    ``objects/`` tree either way (RAM tiers start empty), but "tiered"
    promotes every read object into the hot tier for subsequent
    restores in this process.

    ``participant=(pid, n)`` makes this call one restore participant of
    ``n``: against a *sharded* checkpoint (see docs/storage.md) the plan
    schedules only the shard objects overlapping the slices owned by
    this participant's cut of ``mesh`` — the save-on-MxN →
    restore-on-PxQ resharding path that reads strictly fewer bytes than
    a full-array restore whenever the shardings overlap partially.  The
    returned state is only guaranteed correct on the participant's owned
    slices (elsewhere zeros for sharded units).

    ``stats``, when given, receives the restore's ``last_restore_stats``
    (``bytes_read``, ``shards_skipped``, ...)."""
    registry = LayerRegistry(model)
    mgr = CheckpointManager(Path(ckpt_root), registry,
                            make_policy("full", model.layer_units()),
                            async_save=False,
                            store_backend=store_backend)
    try:
        like = steps_lib.state_specs(model)
        shardings = steps_lib.state_shardings(model, mesh)
        owned = None
        if participant is not None:
            from repro.checkpoint.sharded import participant_wanted
            pid, nparts = participant
            owned = participant_wanted(registry, pid, nparts,
                                       shardings=shardings)
        state = mgr.restore(like, step=step, shardings=shardings,
                            parts=parts, units=units, pipelined=pipelined,
                            owned=owned)
        if stats is not None:
            stats.update(mgr.last_restore_stats)
        return state
    finally:
        mgr.close()


def probe_restore(ckpt_root: str | Path, arch: str, *,
                  reduced: bool = True,
                  parts: Tuple[str, ...] = ("params",),
                  store_backend: str = "local") -> Dict[str, Any]:
    """Restorability check without a training process: rebuild the model
    from its arch id, restore ``parts`` onto a fresh single-host mesh,
    and report what the plan had to do.  The supervisor runs this between
    a death and the relaunch (the cost lands inside MTTR) so a checkpoint
    a restarted trainer would choke on is caught *before* the restart
    burns a JIT warmup — and the returned ``fallback_units`` exposes
    units that had to fall back to an older manifest (e.g. a hot-only
    preemption commit whose spill never finished)."""
    import time

    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model

    t0 = time.time()
    cfg = get_config(arch, reduced=reduced)
    model = build_model(cfg)
    mesh = make_host_mesh()
    registry = LayerRegistry(model)
    mgr = CheckpointManager(Path(ckpt_root), registry,
                            make_policy("full", model.layer_units()),
                            async_save=False, store_backend=store_backend)
    try:
        like = steps_lib.state_specs(model)
        shardings = steps_lib.state_shardings(model, mesh)
        state = mgr.restore(like, shardings=shardings, parts=parts)
        stats = dict(mgr.last_restore_stats)
        return {
            "step": int(state["step"]) if "step" in state
            else mgr.manifests.latest_step(),
            "parts": list(parts),
            "bytes_read": stats.get("bytes_read"),
            "fallback_units": stats.get("fallback_units", []),
            "seconds": time.time() - t0,
        }
    finally:
        mgr.close()
