"""The one seam between the harness and the system under test.

Everything the benchmark calls in ``repro`` goes through here: building
the model a configuration file describes, the jitted train step as
``repro.launch.train.train`` builds it, the checkpoint manager and its
store, the weight service, and the serving steps.

The store is the program's RAM tier (``store_backend="memory"``), one
instance shared by every manager of a run, so a run writes its
checkpoints to no disk: the object bytes, their encoding, hashing and
verification are the program's own, and only the file write is left
out.
"""
from __future__ import annotations

import sys
from pathlib import Path
from typing import Dict, List

CHECKOUT = Path(__file__).resolve().parents[2]
if str(CHECKOUT / "src") not in sys.path:
    sys.path.insert(0, str(CHECKOUT / "src"))

import jax  # noqa: E402

from repro.checkpoint.backends.memory import MemoryBackend  # noqa: E402
from repro.checkpoint.saver import CheckpointManager  # noqa: E402
from repro.checkpoint.swap import WeightService  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import TrainConfig  # noqa: E402
from repro.core import LayerRegistry, make_policy  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402,F401
from repro.models import build_model  # noqa: E402

from bench.common.composite import Unit  # noqa: E402

#: keys of a configuration file that describe it rather than size it
DESCRIPTIVE = ("name", "arch", "source", "reduced", "published", "deployment",
               "departures", "assumed", "ref", "routed")


def build(cfg: Dict):
    """The program's model for a configuration file: the registered
    architecture with every size the file states."""
    base = get_config(cfg["arch"])
    update = {}
    for key, value in cfg.items():
        if key in DESCRIPTIVE:
            continue
        if key not in type(base).model_fields:
            raise KeyError(f"{cfg['name']}: {key!r} is not a size of "
                           f"{cfg['arch']}")
        cur = getattr(base, key)
        update[key] = (cur.model_copy(update=value)
                       if isinstance(value, dict) else value)
    model_cfg = base.model_copy(update=update)
    for key, value in cfg.items():       # the file is the config as run
        if key in DESCRIPTIVE:
            continue
        got = getattr(model_cfg, key)
        got = got.model_dump() if hasattr(got, "model_dump") else got
        want = value if not isinstance(value, dict) else {**got, **value}
        if got != want:
            raise ValueError(f"{cfg['name']}: {key} runs as {got}, "
                             f"file says {value}")
    return build_model(model_cfg)


def state_specs(model):
    return steps_lib.state_specs(model)


def units(model, config: str) -> List[Unit]:
    """The model's checkpoint units, each block with its depth: its place
    among the blocks as the model lists them, which has to be the number
    in its name (``block_000``, ``block_001``, ...), so that the depth
    the composite rule reads is the model's own.  ``config``: the
    configuration's name, for the error."""
    out: List[Unit] = []
    for u in model.layer_units():
        depth = None
        if u.kind == "block":
            depth = sum(v.depth is not None for v in out)
            if u.name != f"block_{depth:03d}":
                raise ValueError(
                    f"{config}: block unit {u.name!r} stands at depth "
                    f"{depth}; the harness takes blocks named block_000, "
                    f"block_001, ... in the model's order")
        out.append(Unit(u.name, tuple(u.path), u.index, depth))
    return out


def stacked_roots(model):
    return sorted({tuple(u.path) for u in model.layer_units()
                   if u.index is not None})


def train_config(opt: Dict) -> TrainConfig:
    return TrainConfig(**opt)


def jit_train_step(model, tcfg: TrainConfig):
    """Donated, as ``train()`` jits it."""
    return jax.jit(steps_lib.make_train_step(model, tcfg),
                   donate_argnums=0)


def ram_store() -> MemoryBackend:
    return MemoryBackend()


def store_bytes(root, store: MemoryBackend) -> int:
    """Bytes of a chain: its objects in the store and its manifests."""
    total = sum(store.size(k) for k in store.keys())
    for f in Path(root).rglob("*"):
        if f.is_file():
            total += f.stat().st_size
    return total


def manager(root, model, policy: str, store: MemoryBackend, *,
            codec: str = "auto", opt: Dict = None) -> CheckpointManager:
    """A manager as ``train()`` builds one (sync saves, writer threads,
    fingerprints on) over ``store``."""
    wd = (opt or {}).get("weight_decay", 0.1)
    registry = LayerRegistry(model, weight_decay=wd)
    return CheckpointManager(Path(root), registry,
                             make_policy(policy, model.layer_units()),
                             codec=codec, store_backend=store)


def warm_fingerprint_compare(mgr: CheckpointManager, state) -> None:
    """Compile the save path's on-device fingerprint comparison for every
    unit structure.  Event 0 has no previous fingerprints, so only a
    later event would otherwise compile it, inside the window."""
    from repro.kernels import block_fp

    seen = set()
    for u in mgr.registry.units:
        key = (u.path, u.index is None)
        if key in seen:
            continue
        seen.add(key)
        for tree in (mgr.registry.extract_unit(state["params"], u.name),
                     mgr.registry.extract_opt_unit(state["opt"], u.name)):
            fps = block_fp.fingerprint_tree(tree,
                                            block_bytes=mgr.fp_block_bytes)
            block_fp.leaves_match(fps, fps)


def reader(root, model, store: MemoryBackend) -> CheckpointManager:
    """A manager on an existing chain, as a relaunch or a server opens
    it (``serve()``: full policy, no writer threads)."""
    return CheckpointManager(Path(root), LayerRegistry(model),
                             make_policy("full", model.layer_units()),
                             async_save=False, store_backend=store)


def weight_service(mgr, model, step: int) -> WeightService:
    return WeightService(mgr, state_specs(model), step=step)


def serve_steps(model):
    """(prefill, decode) jitted as ``serve()`` jits them."""
    return (jax.jit(model.prefill),
            jax.jit(model.decode_step, donate_argnums=1))
