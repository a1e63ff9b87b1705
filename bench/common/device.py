"""The devices a run measures on, as JAX reports them."""
from __future__ import annotations

from typing import Dict, Optional


class NoAccelerator(RuntimeError):
    pass


def info(chips: int) -> Dict:
    """Platform, kind and count of the devices the cell uses."""
    import jax
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips: int) -> Dict:
    """The cell's devices; raises unless JAX sees at least ``chips``
    TPU chips.  A measurement never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)")
    return info(chips)


def peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device, where reported."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
