"""The composite rule of ``bench.common.composite`` against the paper's
parity strategy: blocks alternate by their depth in the model, whichever
stacked array holds them."""
from __future__ import annotations

import ast
from types import SimpleNamespace

import pytest

from bench.common import composite, program
from bench.tests.conftest import TINY

EVENTS = range(4)


def saved(units, event):
    return {u.name for u in units if composite.saves("parity", event, u)}


def fake_model(layer_units):
    return SimpleNamespace(layer_units=lambda: layer_units)


@pytest.mark.parametrize("arch", sorted(TINY))
def test_parity_agrees_with_the_programs_policy(arch):
    """Event 0 saves every unit (the program's first event is full);
    later events save what the program's ``ParityPolicy`` selects."""
    from repro.core.policies import ParityPolicy, PolicyContext

    model = program.build(TINY[arch])
    units = program.units(model, TINY[arch]["name"])
    policy = ParityPolicy(model.layer_units())
    for e in EVENTS:
        want = (set(policy.all_units()) if e == 0 else
                set(policy.select(PolicyContext(event_index=e, step=e))))
        assert saved(units, e) == want, e


def test_parity_of_three_block_stacks_goes_by_depth():
    """``MEMEM*E``: Mamba blocks at depths 0, 2, 4 (rows 0-2 of their
    stack), MoE at 1, 3, 6 and attention at 5.  By rows, depths 1, 2 and
    5 would land on the wrong events."""
    from repro.models.model_api import LayerUnit

    stacks = {"M": "mamba_blocks", "E": "moe_blocks", "*": "attn_blocks"}
    rows = dict.fromkeys(stacks, 0)
    layers = [LayerUnit("embed", ("embed",), kind="aux")]
    for depth, kind in enumerate("MEMEM*E"):
        layers.append(LayerUnit(f"block_{depth:03d}", (stacks[kind],),
                                index=rows[kind]))
        rows[kind] += 1
    layers += [LayerUnit("final_norm", ("final_norm",), kind="aux"),
               LayerUnit("lm_head", ("lm_head",), kind="aux")]
    units = program.units(fake_model(layers), "nemotron-cut")
    assert [u.depth for u in units] == [None, 0, 1, 2, 3, 4, 5, 6, None,
                                        None]
    even = {"block_000", "block_002", "block_004", "block_006",
            "final_norm", "lm_head"}
    odd = {"block_001", "block_003", "block_005", "embed", "final_norm"}
    assert saved(units, 0) == {u.name for u in units}
    for e in EVENTS[1:]:
        assert saved(units, e) == (even if e % 2 == 0 else odd), e


@pytest.mark.parametrize("names", [
    ["block_001", "block_000"],           # out of depth order
    ["block_000", "block_002"],           # a depth skipped
    ["enc_block_000", "enc_block_001"],   # not the harness's names
])
def test_units_refuses_blocks_out_of_depth_order(names):
    from repro.models.model_api import LayerUnit

    layers = [LayerUnit("embed", ("embed",), kind="aux")]
    layers += [LayerUnit(n, ("blocks",), index=i)
               for i, n in enumerate(names)]
    with pytest.raises(ValueError, match="^tiny-x: block unit"):
        program.units(fake_model(layers), "tiny-x")


def test_composite_imports_nothing_of_the_program():
    tree = ast.parse(open(composite.__file__).read())
    imported = [n.module for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)]
    imported += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
    assert not [m for m in imported if m and m.split(".")[0] == "repro"]
