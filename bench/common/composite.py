"""What a restore of a selective checkpoint chain has to return.

The rule is written here from the LLMTailor paper's parity strategy, not
taken from the program: the first event saves every unit; after it, an
even event saves the blocks at even depth and every auxiliary unit except
the embedding, an odd event saves the blocks at odd depth and the
embedding, and the small final norm rides with every event.  A block's
depth is its layer's position in the model, whichever stacked array (or
none) holds it: odd and even layers alternate.  A unit restores to the
state it had at the last event that saved it.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

TINY_AUX = ("final_norm",)


class Unit(NamedTuple):
    name: str
    path: Tuple[str, ...]      # subtree of the params tree
    index: Optional[int]       # slice of a stacked subtree, or None
    depth: Optional[int]       # a block's layer in the model; None: aux


def saves(policy: str, event: int, u: Unit) -> bool:
    if event == 0 or policy == "full":
        return True
    if policy != "parity":
        raise ValueError(f"no composite rule for policy {policy!r}")
    even = event % 2 == 0
    if u.depth is not None:
        return (u.depth % 2 == 0) == even
    if u.name in TINY_AUX:
        return True
    return (u.name == "embed") != even


def last_saved(policy: str, last_event: int, u: Unit) -> int:
    return max(e for e in range(last_event + 1) if saves(policy, e, u))


def _unit_of(leaf: str, row: int, units: Sequence[Unit]) -> Unit:
    parts = tuple(leaf.split("/"))
    for u in units:
        n = len(u.path)
        for i in (1, 2):        # params/<path>..., opt/<kind>/<path>...
            if parts[i:i + n] == u.path and (u.index is None
                                             or u.index == row):
                return u
    raise KeyError(f"no unit holds {leaf}[{row}]")


def expected(policy: str, last_event: int,
             per_event: Dict[int, Dict[str, np.ndarray]],
             units: Sequence[Unit], *, skip: Sequence[str] = ("step",)
             ) -> Dict[str, np.ndarray]:
    """Checksums the restore of ``last_event`` must give, assembled from
    the checksums taken of the live state at each event."""
    out: Dict[str, np.ndarray] = {}
    for leaf, rows in per_event[last_event].items():
        if leaf in skip:
            continue
        want = np.empty_like(rows)
        for r in range(rows.shape[0]):
            u = _unit_of(leaf, r, units)
            want[r] = per_event[last_saved(policy, last_event, u)][leaf][r]
        out[leaf] = want
    return out


def assemble_params(policy: str, last_event: int, per_event: Dict[int, Dict],
                    units: Sequence[Unit]) -> Dict:
    """The params tree the restore of ``last_event`` must give, from the
    params trees the events saved (nested dicts of arrays)."""
    import jax.numpy as jnp

    from bench.common.checksum import leaves_with_paths

    flat = {e: dict(leaves_with_paths(t)) for e, t in per_event.items()}
    out: Dict = {}
    for path, x in flat[last_event].items():
        key = "params/" + "/".join(path)
        stacked = any(u.index is not None and path[:len(u.path)] == u.path
                      for u in units)
        if stacked:
            value = jnp.stack([
                flat[last_saved(policy, last_event,
                                _unit_of(key, r, units))][path][r]
                for r in range(x.shape[0])])
        else:
            value = flat[last_saved(policy, last_event,
                                    _unit_of(key, 0, units))][path]
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = value
    return out
