"""Roofline machinery: HLO parsing (trip counts, dots, collectives) against
programs with known costs, and the cost_analysis facts the methodology
relies on."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import account, analyze_compiled, hw
from repro.roofline.flops import count_active_params, model_flops
from repro.configs import SHAPES, get_config
from repro.models import build_model


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_dot_flops_exact():
    n = 64
    x = jax.ShapeDtypeStruct((n, n), jnp.float32)
    c = _compile(lambda a: a @ a, x)
    acc = account(c.as_text(), num_devices=1)
    # 2n^3 matmul + small elementwise slack
    assert abs(acc.flops - 2 * n ** 3) / (2 * n ** 3) < 0.05


def test_scan_trip_count_multiplies():
    n, layers = 32, 7
    w = jax.ShapeDtypeStruct((layers, n, n), jnp.float32)
    x0 = jax.ShapeDtypeStruct((n,), jnp.float32)

    def f(w, x):
        def body(h, wi):
            return wi @ h, None
        h, _ = jax.lax.scan(body, x, w)
        return h

    c = _compile(f, w, x0)
    acc = account(c.as_text(), num_devices=1)
    expected = layers * 2 * n * n
    assert abs(acc.flops - expected) / expected < 0.2, acc.flops
    # raw cost_analysis counts the body once (the known undercount)
    raw = c.cost_analysis()["flops"]
    assert raw < expected / 2


def test_nested_scan_trips_compose():
    n, inner, outer = 16, 3, 5
    w = jax.ShapeDtypeStruct((outer, inner, n, n), jnp.float32)
    x0 = jax.ShapeDtypeStruct((n,), jnp.float32)

    def f(w, x):
        def outer_body(h, wo):
            def inner_body(hh, wi):
                return wi @ hh, None
            h2, _ = jax.lax.scan(inner_body, h, wo)
            return h2, None
        h, _ = jax.lax.scan(outer_body, x, w)
        return h

    c = _compile(f, w, x0)
    acc = account(c.as_text(), num_devices=1)
    expected = outer * inner * 2 * n * n
    assert acc.dot_count == outer * inner
    assert abs(acc.dot_flops - expected) / expected < 1e-6, acc.dot_flops


def test_collective_parse_smoke():
    text = """
ENTRY %main_spmd (p: f32[4,8]) -> f32[4,8] {
  %x = f32[4,8]{1,0} parameter(0)
  %ag = f32[4,32]{1,0} all-gather(%x), channel_id=1, replica_groups=[2,4]<=[8], dimensions={1}
  ROOT %ar = f32[4,8]{1,0} all-reduce(%x), channel_id=2, replica_groups=[1,8]<=[8], to_apply=%sum
}
"""
    acc = account(text, num_devices=8)
    ag = acc.collectives["all-gather"]
    ar = acc.collectives["all-reduce"]
    assert ag["count"] == 1 and ar["count"] == 1
    assert ag["bytes"] == 4 * 32 * 4
    np.testing.assert_allclose(ag["wire_bytes"], 4 * 32 * 4 * 3 / 4)
    np.testing.assert_allclose(ar["wire_bytes"], 2 * 4 * 8 * 4 * 7 / 8)


def test_active_params_moe_discount():
    model = build_model(get_config("deepseek-v2-lite-16b"))
    total, active = count_active_params(model)
    assert active < 0.45 * total  # 64 experts, top-6 + shared
    dense = build_model(get_config("yi-9b"))
    t2, a2 = count_active_params(dense)
    assert a2 > 0.9 * t2


def test_model_flops_conventions():
    model = build_model(get_config("yi-9b"))
    tr = model_flops(model, SHAPES["train_4k"])
    pf = model_flops(model, SHAPES["prefill_32k"])
    de = model_flops(model, SHAPES["decode_32k"])
    # train = 3x prefill per token; decode = prefill per token
    tokens_tr = 4096 * 256
    tokens_pf = 32768 * 32
    assert abs(tr / tokens_tr - 3 * pf / tokens_pf) / (tr / tokens_tr) < 1e-6
    assert abs(de / 128 - pf / tokens_pf) < 1e-3 * pf / tokens_pf


def test_report_terms_and_dominance():
    r = analyze_compiled(
        arch="x", shape="train_4k", mesh_name="16x16", chips=256,
        hlo_text="ENTRY %m (p: f32[2]) -> f32[2] {\n ROOT %t = f32[2]{0} tanh(%p)\n}",
        model_flops=1e12,
        hbm_model={"total": hw.HBM_BW},  # 1 second of HBM traffic
    )
    assert abs(r.memory_s - 1.0) < 1e-9
    assert r.dominant == "memory"
    assert r.step_time_s == r.memory_s
