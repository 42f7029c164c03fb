"""HLO analyzer: shape parsing, collective accounting, trip-count correction."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo import _shape_bytes, analyze_module
from repro.analysis.roofline import HW, model_flops, roofline_terms
from repro.config import SHAPES
from repro.configs import get_config


def test_shape_bytes():
    assert _shape_bytes("f32[128,128]{1,0}") == 128 * 128 * 4
    assert _shape_bytes("bf16[16,4096]") == 16 * 4096 * 2
    assert _shape_bytes("(f32[8], s32[])") == 36
    assert _shape_bytes("pred[10]") == 10
    assert _shape_bytes("f32[]") == 4


def test_trip_count_correction_exact():
    """A scanned matmul must report trip × per-iteration flops."""

    def scanned(w, x):
        def body(c, wi):
            return jnp.tanh(c @ wi), None

        out, _ = jax.lax.scan(body, x, w)
        return out

    W = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
    X = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    co = jax.jit(scanned).lower(W, X).compile()
    st = analyze_module(co.as_text(), 1)
    assert st.flops == 8 * 2 * 64**3
    # raw cost_analysis counts the body once — our whole reason to exist
    assert co.cost_analysis()["flops"] < st.flops


def test_nested_scan_multiplies():
    def nested(w, x):
        def outer(c, wi):
            def inner(ci, _):
                return jnp.tanh(ci @ wi), None

            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None

        out, _ = jax.lax.scan(outer, x, w)
        return out

    W = jax.ShapeDtypeStruct((4, 32, 32), jnp.float32)
    X = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    co = jax.jit(nested).lower(W, X).compile()
    st = analyze_module(co.as_text(), 1)
    assert st.flops == 4 * 3 * 2 * 32**3


def test_traffic_excludes_fusion_internals():
    """Fused elementwise chains must not inflate HBM traffic."""

    def chain(x):
        for _ in range(10):
            x = jnp.tanh(x) * 1.1 + 0.5
        return x

    X = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    co = jax.jit(chain).lower(X).compile()
    st = analyze_module(co.as_text(), 1)
    nbytes = 1024 * 1024 * 4
    # in + out (+ small slack); NOT 10 roundtrips
    assert st.traffic_bytes <= 4 * nbytes, st.traffic_bytes


def test_model_flops_formulas():
    cfg = get_config("qwen3_8b")
    train = model_flops(cfg, SHAPES["train_4k"])
    prefill = model_flops(cfg, SHAPES["prefill_32k"])
    assert train == 6 * cfg.active_param_count() * 256 * 4096
    assert prefill == 2 * cfg.active_param_count() * 32 * 32768


def test_moe_uses_active_params():
    cfg = get_config("grok1_314b")
    assert cfg.active_param_count() < 0.3 * cfg.param_count()


def test_roofline_dominant_pick():
    cfg = get_config("qwen3_8b")
    r = roofline_terms(
        cfg, SHAPES["train_4k"],
        per_device_flops=1e12, per_device_bytes=1e9, per_device_coll_bytes=1e9,
        n_chips=256,
    )
    # 1e12/197e12 ≈ 5e-3 vs 1e9/819e9 ≈ 1.2e-3 vs 1e9/50e9 = 2e-2
    assert r.dominant == "collective"
    assert r.collective_s > r.compute_s > r.memory_s
