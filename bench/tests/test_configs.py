"""Each configuration file builds the program's model at the sizes it
states, and differs from the program's registered config only in keys
it lists as ``reduced`` or sets to the published value."""
from __future__ import annotations

import jax
import pytest

from bench.common import harness, program

SIZES = {
    # params, layer units, train-state bytes (bf16 params + f32 master/m/v)
    "mamba2-370m": (368_346_624, 50, 5_156_852_740),
}


def published(cfg, key):
    """The published value of ``key``; a vocabulary counts as its
    embedding holds it, padded to the published multiple."""
    pub = cfg["published"]
    value = pub[key]
    if key == "vocab_size" and "pad_vocab_size_multiple" in pub:
        mult = pub["pad_vocab_size_multiple"]
        value = -(-value // mult) * mult
    return value


@pytest.mark.parametrize("name", sorted(SIZES))
def test_config_builds_the_sizes_it_states(name):
    bench = harness.spec()
    entry = {c["name"]: c for c in bench["configs"]}[name]
    cfg = harness.read_json(harness.CHECKOUT / entry["file"])
    assert cfg["name"] == name and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    for key in ("deployment", "departures", "published"):
        assert cfg[key]
    model = program.build(cfg)
    specs = program.state_specs(model)
    params = sum(x.size for x in jax.tree.leaves(specs["params"]))
    state = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(specs))
    assert (params, len(model.layer_units()), state) == SIZES[name]

    from repro.configs import get_config
    base = get_config(cfg["arch"])
    changed = [k for k in type(base).model_fields
               if getattr(base, k) != getattr(model.cfg, k)]
    for key in changed:
        assert key in cfg["reduced"] or cfg[key] == published(cfg, key)
    for key in cfg["published"]:
        if key in type(base).model_fields and key not in cfg["reduced"]:
            assert getattr(model.cfg, key) == published(cfg, key), key


def test_a_size_the_program_does_not_run_is_refused():
    cfg = dict(harness.read_json(harness.BENCH / "configs"
                                 / "mamba2-370m.json"), d_mdl=3)
    with pytest.raises(KeyError):
        program.build(cfg)
