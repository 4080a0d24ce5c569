"""HLO cost-walker validation: agrees with XLA's builtin analysis on
loop-free graphs and correctly multiplies while-loop trip counts (which
the builtin does not)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline.hlo_cost import HloCostModel, analyze_text, _parse_assign
from repro.roofline.analysis import roofline_terms, HW


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_matches_builtin_on_loop_free():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    c = _compile(lambda x, w: x @ w, x, w)
    ours = analyze_text(c.as_text())["flops"]
    builtin = c.cost_analysis()["flops"]
    np.testing.assert_allclose(ours, builtin, rtol=1e-6)


def test_scan_multiplied_by_trip_count():
    def scanned(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=8)
        return y

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c8 = _compile(scanned, x, w)
    c1 = _compile(lambda x, w: x @ w, x, w)
    f8 = analyze_text(c8.as_text())["flops"]
    f1 = analyze_text(c1.as_text())["flops"]
    assert abs(f8 / f1 - 8.0) < 0.01
    # builtin undercounts: documents why the walker exists
    assert c8.cost_analysis()["flops"] == c1.cost_analysis()["flops"]


def test_nested_scan():
    def nested(x, w):
        def inner(c, _):
            return c @ w, None

        def outer(c, _):
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None

        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    f = analyze_text(_compile(nested, x, w).as_text())["flops"]
    single = analyze_text(_compile(lambda x, w: x @ w, x, w).as_text())["flops"]
    assert abs(f / single - 15.0) < 0.05


def test_parse_assign_tuple_with_index_comments():
    line = ('  %while.135 = (s32[], bf16[8,16]{1,0}, pred[4]{0}, f32[2]{0}, '
            'f32[3]{0}, /*index=5*/f32[8,16]{1,0}) while(%tuple.1), '
            'condition=%c, body=%b, backend_config={"known_trip_count":{"n":"30"}}')
    parsed = _parse_assign(line)
    assert parsed is not None
    name, shape, kind, rest = parsed
    assert kind == "while" and "index=5" in shape


def test_roofline_terms_dominance():
    t = roofline_terms(HW["peak_flops"], 0.0, 0.0)
    assert t["dominant"] == "compute" and abs(t["compute_s"] - 1.0) < 1e-9
    t = roofline_terms(0.0, HW["hbm_bw"], 1.0)
    assert t["dominant"] == "memory"
    t = roofline_terms(1.0, 1.0, HW["ici_bw"])
    assert t["dominant"] == "collective"
