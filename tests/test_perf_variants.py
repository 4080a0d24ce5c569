"""Equivalence tests for the §Perf optimization paths: every optimized
formulation must match its baseline bit-for-bit (up to float assoc)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as A
from repro.config import ExecConfig


def _with_host_devices(flags: str, n: int = 8) -> str:
    """Append the host-device-count flag, preserving caller XLA_FLAGS."""
    if "xla_force_host_platform_device_count" in flags:
        return flags
    return (flags + f" --xla_force_host_platform_device_count={n}").strip()


def test_grouped_decode_matches_repeat_kv():
    key = jax.random.PRNGKey(0)
    B, H, Hkv, L, D = 2, 8, 2, 64, 16
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    kc = jax.random.normal(ks[1], (B, Hkv, L, D))
    vc = jax.random.normal(ks[2], (B, Hkv, L, D))
    for cl in (1, 17, 64):
        a = A.decode_attention(q, kc, vc, jnp.int32(cl),
                               ExecConfig(decode_grouped=True))
        b = A.decode_attention(q, kc, vc, jnp.int32(cl),
                               ExecConfig(decode_grouped=False))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.slow
def test_expert_parallel_multidevice_subprocess(tmp_path):
    """expert_parallel (shard_map) == scatter == dense on a real
    multi-device mesh, including gradients."""
    prog = tmp_path / "prog.py"
    prog.write_text("""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import reduced_config
from repro.models import moe as M
from repro.models import params as PM
from repro.config import ExecConfig

if jax.device_count() < 8:
    print("SKIP: only", jax.device_count(), "devices visible")
    raise SystemExit(0)

cfg = reduced_config("qwen2-moe-a2.7b")
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
p = PM.init_tree(M.moe_param_spec(cfg), jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
with jax.set_mesh(mesh):
    y_ep, _ = jax.jit(lambda p, x: M.moe_ffn(p, x, cfg, ExecConfig(moe_impl="expert_parallel")))(p, x)
y_dn, _ = M.moe_ffn(p, x, cfg, ExecConfig(moe_impl="dense"))
err = float(jnp.abs(y_ep - y_dn).max())
assert err < 1e-4, err

def loss(p):
    y, aux = M.moe_ffn(p, x, cfg, ExecConfig(moe_impl="expert_parallel"))
    return jnp.sum(y ** 2) + aux
with jax.set_mesh(mesh):
    g = jax.jit(jax.grad(loss))(p)
assert all(bool(jnp.isfinite(v).all()) for v in jax.tree_util.tree_leaves(g))
print("OK")
""")
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS=_with_host_devices(os.environ.get("XLA_FLAGS", "")))
    res = subprocess.run([sys.executable, str(prog)], capture_output=True,
                         text=True, env=env, cwd=os.getcwd(), timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    if "SKIP" in res.stdout:
        pytest.skip(res.stdout.strip())
    assert "OK" in res.stdout


def test_slstm_unroll_invariance():
    """unroll changes scheduling, never values."""
    from repro.configs import reduced_config
    from repro.models import xlstm as XL
    from repro.models import params as PM
    cfg = reduced_config("xlstm-125m")
    p = PM.init_tree(XL.slstm_param_spec(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y1, st1 = XL.slstm_forward(p, x, cfg, ExecConfig(slstm_unroll=1))
    y8, st8 = XL.slstm_forward(p, x, cfg, ExecConfig(slstm_unroll=8))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y8),
                               atol=1e-6, rtol=1e-6)


def test_model_level_pallas_decode():
    """serve path with the Pallas decode-attention kernel (interpret) must
    match the XLA path — model-level integration of kernels/ops.py."""
    from repro.configs import reduced_config
    from repro.models import transformer as T
    key = jax.random.PRNGKey(0)
    cfg = reduced_config("granite-3-8b")
    ec_x = ExecConfig(compute_dtype="float32")
    ec_k = ExecConfig(compute_dtype="float32", use_pallas=True, interpret=True)
    params = T.init_params(cfg, key, ec_x)
    toks = jax.random.randint(key, (2, 4), 0, cfg.vocab)
    outs = {}
    for name, ec in (("xla", ec_x), ("pallas", ec_k)):
        cache = T.init_cache(cfg, ec, 2, 8)
        logits = []
        for t in range(4):
            lg, cache = T.decode_step(cfg, ec, params, cache, toks[:, t:t+1])
            logits.append(lg)
        outs[name] = jnp.concatenate(logits, axis=1)
    np.testing.assert_allclose(np.asarray(outs["xla"]),
                               np.asarray(outs["pallas"]),
                               atol=2e-4, rtol=2e-4)
