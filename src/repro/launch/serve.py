"""Batched serving driver: prefill a prompt batch, then autoregressive
decode against the KV/state cache — with delta-push weight promotion and
variant serving from one store (docs/serving.md).

    python -m repro.launch.serve --arch llama3.2-3b --batch 4 \
        --prompt-len 64 --new-tokens 32 [--from-ckpt /tmp/run1] [--full]

Weights can come from any LLMTailor checkpoint root — including a merged
Frankenstein — because the bf16 weight chunks are servable without the
optimizer chunks (the paper's consolidated-model-file analogue).  The
loader uses the restore engine's partial restore (``parts=("params",)``,
see docs/restore.md): optimizer objects are never read off disk, so
serve-time weight loading costs a fraction of a full-state restore.

On top of the cold load this driver exposes the serving-fleet surface:

- ``--from-step N`` pins the initial restore to a specific manifest;
- ``--hot-swap`` polls the manifest chain after loading and promotes
  the newest checkpoint by digest diff (``checkpoint/swap.py``) —
  unchanged units are zero-read/zero-H2D, block-delta units scatter
  only their dirty blocks onto the live device buffers; the result
  dict's ``swap`` key carries ``last_swap_stats``;
- ``--cache-mb N`` attaches a digest-keyed host-RAM ``BlockCache``
  under the store's backend reads (``--cache-shm`` backs it with
  /dev/shm segments covered by the repo's leak guards);
- ``--variant-select "PATTERNS@STEP"`` (repeatable, with
  ``--variant-base-step``) serves a zero-copy composite variant
  assembled by ``core.tailor.variant_manifest`` instead of a committed
  manifest.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core import LayerRegistry, make_policy
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model


def _pad_cache_to(cache, model, batch, target):
    """Grow a prefill cache's sequence dim to the decode cache length."""
    spec = model.cache_spec(batch, target)

    def grow(c, s):
        c = jnp.asarray(c)
        if c.shape == s.shape:
            return c.astype(s.dtype)
        pads = [(0, st - sc) for sc, st in zip(c.shape, s.shape)]
        return jnp.pad(c, pads).astype(s.dtype)

    return jax.tree.map(grow, cache, spec,
                        is_leaf=lambda x: hasattr(x, "shape"))


def parse_variant_select(specs: Sequence[str]) -> List[Tuple[List[str], int]]:
    """``"block_000..block_003@900"`` -> ``([patterns], step)`` pairs;
    comma separates multiple patterns in one spec."""
    out: List[Tuple[List[str], int]] = []
    for spec in specs:
        pats, sep, step = spec.rpartition("@")
        if not sep or not pats:
            raise ValueError(
                f"variant select {spec!r} must look like PATTERNS@STEP")
        out.append(([p.strip() for p in pats.split(",") if p.strip()],
                    int(step)))
    return out


def serve(*, arch: str, reduced: bool = True, batch: int = 4,
          prompt_len: int = 64, new_tokens: int = 32,
          from_ckpt: Optional[str] = None, store_backend: str = "local",
          io_backend: str = "thread", io_workers: Optional[int] = None,
          seed: int = 0, greedy: bool = True,
          from_step: Optional[int] = None, hot_swap: bool = False,
          swap_wait: float = 30.0, swap_poll: float = 0.2,
          cache_mb: Optional[int] = None, cache_shm: bool = False,
          variant_base_step: Optional[int] = None,
          variant_select: Optional[Sequence[str]] = None) -> dict:
    cfg = get_config(arch, reduced=reduced)
    model = build_model(cfg)
    served_step: Optional[int] = None
    swap_stats: Optional[Dict[str, Any]] = None
    restore_stats: Optional[Dict[str, Any]] = None
    cache_stats: Optional[Dict[str, int]] = None

    if from_ckpt:
        from repro.checkpoint.saver import CheckpointManager
        from repro.checkpoint.swap import WeightService
        from repro.core.tailor import variant_manifest
        registry = LayerRegistry(model)
        # store_backend="tiered" warms the RAM tier while loading
        # (promotion-on-read): later loads of the same root in this
        # process serve weights from memory.
        mgr = CheckpointManager(Path(from_ckpt), registry,
                                make_policy("full", model.layer_units()),
                                async_save=False,
                                store_backend=store_backend,
                                io_backend=io_backend,
                                io_workers=io_workers,
                                block_cache_bytes=(cache_mb << 20)
                                if cache_mb else None,
                                block_cache_shm=cache_shm)
        like = steps_lib.state_specs(model)
        manifest = None
        if variant_select:
            manifest = variant_manifest(
                mgr.manifests, base_step=variant_base_step,
                select=parse_variant_select(variant_select), name="cli")
        # Weights-only partial restore behind the digest diff service:
        # optimizer objects are never read.
        svc = WeightService(mgr, like, step=from_step, manifest=manifest)
        restore_stats = dict(svc.restore_stats)
        if hot_swap:
            # Follow the manifest chain until a newer checkpoint lands
            # (the promotion this replica is waiting to receive), then
            # apply it as dirty-block deltas onto the live buffers.
            deadline = time.time() + swap_wait
            while True:
                swap_stats = svc.poll()
                if swap_stats is not None:
                    break
                if time.time() >= deadline:
                    raise RuntimeError(
                        f"--hot-swap: no newer manifest than step "
                        f"{svc.step} appeared within {swap_wait:.0f}s")
                time.sleep(swap_poll)
        params = svc.current()
        served_step = svc.step
        if mgr.block_cache is not None:
            cache_stats = mgr.block_cache.snapshot()
        mgr.close()
    else:
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                              model.init(jax.random.key(seed)))

    rng = np.random.RandomState(seed)
    prompts = rng.randint(0, cfg.vocab_size, (batch, prompt_len)).astype(np.int32)
    extra = {}
    if cfg.family == "vlm":
        extra["patch_embeds"] = jnp.asarray(
            rng.standard_normal((batch, cfg.vlm.num_patches,
                                 cfg.vlm.patch_embed_dim)) * 0.1, jnp.bfloat16)
    if cfg.family == "encdec":
        extra["frames"] = jnp.asarray(
            rng.standard_normal((batch, prompt_len, cfg.d_model)) * 0.1,
            jnp.bfloat16)

    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step, donate_argnums=1)

    t0 = time.time()
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompts), **extra})
    cache_len = prompt_len + new_tokens
    if cfg.family == "vlm":
        cache_len += cfg.vlm.num_patches
    cache = _pad_cache_to(cache, model, batch, cache_len)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    out_tokens = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    pos0 = prompt_len + (cfg.vlm.num_patches if cfg.family == "vlm" else 0)
    t1 = time.time()
    for i in range(new_tokens):
        out_tokens.append(np.asarray(tok))
        logits, cache = decode(params, cache,
                               {"tokens": tok[:, None],
                                "pos": jnp.int32(pos0 + i)})
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = time.time() - t1

    gen = np.stack(out_tokens, axis=1)
    return {
        "arch": arch,
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "prefill_seconds": t_prefill,
        "decode_seconds": t_decode,
        "decode_tokens_per_s": batch * new_tokens / max(t_decode, 1e-9),
        "sample_tokens": gen[0, :8].tolist(),
        # Bit-exactness handle for fleet comparisons: every replica (and
        # the cold-restored reference) serving identical weights must
        # produce an identical digest over ALL generated tokens.
        "tokens_digest": hashlib.blake2b(
            np.ascontiguousarray(gen).tobytes(), digest_size=16).hexdigest(),
        # serving-fleet provenance: which manifest the weights came from
        # and what the promotion/cold-load cost (the train-side
        # last_restore_stats plumbing, mirrored reader-side)
        "served_step": served_step,
        "restore": restore_stats,
        "swap": swap_stats,
        "cache": cache_stats,
    }


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3.2-3b")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="serve the published full-size config")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--from-ckpt")
    ap.add_argument("--from-step", type=int,
                    help="pin the initial restore to this manifest step "
                         "(default: LATEST)")
    ap.add_argument("--hot-swap", action="store_true",
                    help="after loading, poll the manifest chain and "
                         "promote the newest checkpoint by digest diff "
                         "(dirty-block scatter onto live device buffers) "
                         "before generating")
    ap.add_argument("--swap-wait", type=float, default=30.0,
                    help="--hot-swap: seconds to wait for a newer "
                         "manifest before giving up")
    ap.add_argument("--swap-poll", type=float, default=0.2,
                    help="--hot-swap: manifest poll interval (seconds)")
    ap.add_argument("--cache-mb", type=int,
                    help="attach a digest-keyed host-RAM block cache of "
                         "this many MiB under the store's backend reads "
                         "(multi-variant serving reads each shared "
                         "digest once)")
    ap.add_argument("--cache-shm", action="store_true",
                    help="back the block cache with /dev/shm segments "
                         "(repro-io-<pid>-cache-*, covered by the "
                         "repo-wide leak guard)")
    ap.add_argument("--variant-base-step", type=int,
                    help="variant serving: base manifest step for units "
                         "no --variant-select rule names")
    ap.add_argument("--variant-select", action="append", default=None,
                    metavar="PATTERNS@STEP",
                    help="serve a zero-copy composite variant: take "
                         "units matching PATTERNS (comma-separated "
                         "recipe patterns, e.g. block_000..block_003) "
                         "from manifest STEP; repeatable, later rules "
                         "win")
    ap.add_argument("--store-backend", default="local",
                    choices=["local", "memory", "tiered", "remote",
                             "remote3"],
                    help="IO tier for --from-ckpt weight loading (tiered/"
                         "remote3 promote read objects into the RAM tier; "
                         "remote3 re-warms a lost disk copy from the "
                         "remote tier)")
    ap.add_argument("--io-backend", default="thread",
                    choices=["thread", "process"],
                    help="IO worker backend for --from-ckpt loading: "
                         "'process' decodes/verifies objects in "
                         "subprocess workers (GIL-free restore)")
    ap.add_argument("--io-workers", type=int,
                    help="process backend: subprocess IO worker count")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()
    print(json.dumps(serve(arch=args.arch, reduced=args.smoke,
                           batch=args.batch,
                           prompt_len=args.prompt_len,
                           new_tokens=args.new_tokens,
                           from_ckpt=args.from_ckpt,
                           store_backend=args.store_backend,
                           io_backend=args.io_backend,
                           io_workers=args.io_workers,
                           seed=args.seed,
                           from_step=args.from_step,
                           hot_swap=args.hot_swap,
                           swap_wait=args.swap_wait,
                           swap_poll=args.swap_poll,
                           cache_mb=args.cache_mb,
                           cache_shm=args.cache_shm,
                           variant_base_step=args.variant_base_step,
                           variant_select=args.variant_select),
                     indent=2))


if __name__ == "__main__":
    main()
