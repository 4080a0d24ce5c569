"""Categorical (C51) Bellman projection — the distributional-RL op.

C51 (Bellemare et al. 2017) represents Q(s, a) as a categorical
distribution over K fixed support atoms z_j = v_min + jΔ,
Δ = (v_max - v_min)/(K-1). The distributional Bellman update shifts the
support, Tz_j = clip(r + γⁿ(1-done)·z_j, v_min, v_max), and the result
must be projected back onto the fixed atoms before the cross-entropy
loss: each source atom's mass p_j splits linearly between the two
neighbouring target atoms of b_j = (Tz_j - v_min)/Δ.

The XLA oracle (``ref.categorical_projection``) is the classic per-atom
clamp/scatter: l = ⌊b⌋, u = l+1, masses p·(1-(b-l)) and p·(b-l)
scatter-added at l and u. Batched scatters are gather-heavy on the VPU,
so both Pallas schedules use the equivalent *gather-interpolate*
formulation over target atoms: m_i = Σ_j p_j · max(0, 1 - |b_j - i|)
(the triangular hat kernel; identical to the scatter for every b in
[0, K-1], including integer b where the naive two-sided scatter drops
the mass). Because rewards/dones are per-sample scalars, b_j is a
(block, 1) column computed straight from r, d and the static z_j — no
per-lane gathers at all:

TPU Mosaic — grid over batch blocks (8 sublanes each); atoms live on
the 128-lane axis; a static loop over the K target atoms accumulates
hat-weighted lane reductions. VMEM per step at K=51: the (8, 128)
probs tile plus two (8, 128) temporaries ≈ 12 KiB.

GPU Triton — same structure, one program per 32-row batch block; the
atom axis is padded to the next power of two for Triton's block layout.

Exactness: both schedules agree with the scatter oracle to float
rounding (the hat weight 1-|b-i| vs the scatter's (l+1)-b differ only
in association); the op is used under ``stop_gradient`` (it projects
the *target* distribution), so like ``segment_tree`` it registers no
VJP.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend as kb
from repro.kernels.segment_tree import next_pow2


def support(num_atoms: int, v_min: float, v_max: float) -> jax.Array:
    """The (K,) fixed atom grid z_j = v_min + jΔ shared by model heads,
    losses and this op. K == 1 degenerates to the single atom v_min."""
    if num_atoms == 1:
        return jnp.asarray([v_min], jnp.float32)
    return jnp.linspace(v_min, v_max, num_atoms, dtype=jnp.float32)


def _delta(num_atoms: int, v_min: float, v_max: float) -> float:
    """Static atom spacing; 0 collapses (K==1 or v_min==v_max) — the
    kernels then divide by 1 instead, sending every b_j to atom 0."""
    return (v_max - v_min) / (num_atoms - 1) if num_atoms > 1 else 0.0


def _hat_accumulate(p, r, d, i_lane, *, K: int, v_min: float, v_max: float,
                    gamma_n: float, delta: float):
    """Shared schedule body: gather-interpolate m over target atoms.

    p: (bb, Kp) source masses (lane-padded with 0); r/d: (bb, 1);
    i_lane: (bb, Kp) f32 lane iota. Returns (bb, Kp) projected masses.
    """
    db = delta if delta > 0.0 else 1.0
    acc = jnp.zeros_like(p)
    for j in range(K):
        z_j = v_min + delta * j
        tz = jnp.clip(r + gamma_n * (1.0 - d) * z_j, v_min, v_max)
        b = (tz - v_min) / db                               # (bb, 1)
        w = jnp.maximum(1.0 - jnp.abs(b - i_lane), 0.0)     # (bb, Kp)
        p_j = jnp.sum(jnp.where(i_lane == j, p, 0.0), axis=1, keepdims=True)
        acc = acc + p_j * w
    return jnp.where(i_lane < K, acc, 0.0)


# ---------------------------------------------------------------------------
# TPU Mosaic schedule
# ---------------------------------------------------------------------------

def _proj_kernel(p_ref, r_ref, d_ref, o_ref, *, K: int, v_min: float,
                 v_max: float, gamma_n: float, delta: float):
    p = p_ref[...].astype(jnp.float32)                      # (bb, Kp)
    r = r_ref[...].astype(jnp.float32)                      # (bb, 1)
    d = d_ref[...].astype(jnp.float32)
    # Mosaic's iota is integer-only: build it as int32, then cast
    i_lane = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1).astype(
        jnp.float32)
    o_ref[...] = _hat_accumulate(p, r, d, i_lane, K=K, v_min=v_min,
                                 v_max=v_max, gamma_n=gamma_n, delta=delta)


@kb.register("categorical_projection", kb.MOSAIC)
def categorical_projection_kernel(probs: jax.Array, rewards: jax.Array,
                                  dones: jax.Array, *, v_min: float,
                                  v_max: float, gamma_n: float,
                                  block: int = 8,
                                  interpret: bool = False) -> jax.Array:
    """probs: (B, K) f32; rewards/dones: (B,) f32. Returns (B, K) f32."""
    B, K = probs.shape
    assert K <= 512, f"atom count {K} beyond the unrolled-schedule bound"
    Kp = max(-(-K // 128) * 128, 128)                 # lane pad
    bb = block
    Bp = -(-B // bb) * bb                             # sublane pad
    p = jnp.pad(probs.astype(jnp.float32), ((0, Bp - B), (0, Kp - K)))
    r = jnp.pad(rewards.astype(jnp.float32), (0, Bp - B)).reshape(Bp, 1)
    d = jnp.pad(dones.astype(jnp.float32), (0, Bp - B)).reshape(Bp, 1)

    kernel = functools.partial(
        _proj_kernel, K=K, v_min=float(v_min), v_max=float(v_max),
        gamma_n=float(gamma_n), delta=_delta(K, v_min, v_max))
    out = pl.pallas_call(
        kernel,
        grid=(Bp // bb,),
        in_specs=[
            pl.BlockSpec((bb, Kp), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, Kp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, Kp), jnp.float32),
        compiler_params=kb.compiler_params(
            kb.MOSAIC, interpret=interpret, dimension_semantics=("parallel",)),
        interpret=interpret,
    )(p, r, d)
    return out[:B, :K]


# ---------------------------------------------------------------------------
# GPU-Triton schedule
# ---------------------------------------------------------------------------

def _proj_kernel_gpu(p_ref, r_ref, d_ref, o_ref, *, K: int, v_min: float,
                     v_max: float, gamma_n: float, delta: float):
    p = p_ref[...].astype(jnp.float32)                      # (tb, Kp2)
    r = r_ref[...].astype(jnp.float32)                      # (tb, 1)
    d = d_ref[...].astype(jnp.float32)
    i_lane = jax.lax.broadcasted_iota(jnp.float32, p.shape, 1)
    o_ref[...] = _hat_accumulate(p, r, d, i_lane, K=K, v_min=v_min,
                                 v_max=v_max, gamma_n=gamma_n, delta=delta)


@kb.register("categorical_projection", kb.TRITON)
def categorical_projection_kernel_gpu(probs: jax.Array, rewards: jax.Array,
                                      dones: jax.Array, *, v_min: float,
                                      v_max: float, gamma_n: float,
                                      tb: int = 32,
                                      interpret: bool = False) -> jax.Array:
    """Same contract as :func:`categorical_projection_kernel`, Triton
    schedule (power-of-two block layout, one program per batch block)."""
    B, K = probs.shape
    assert K <= 512, f"atom count {K} beyond the unrolled-schedule bound"
    Kp2 = next_pow2(max(K, 16))
    tb = min(tb, next_pow2(B))
    Bp = -(-B // tb) * tb
    p = jnp.pad(probs.astype(jnp.float32), ((0, Bp - B), (0, Kp2 - K)))
    r = jnp.pad(rewards.astype(jnp.float32), (0, Bp - B)).reshape(Bp, 1)
    d = jnp.pad(dones.astype(jnp.float32), (0, Bp - B)).reshape(Bp, 1)

    kernel = functools.partial(
        _proj_kernel_gpu, K=K, v_min=float(v_min), v_max=float(v_max),
        gamma_n=float(gamma_n), delta=_delta(K, v_min, v_max))
    out = pl.pallas_call(
        kernel,
        grid=(Bp // tb,),
        in_specs=[
            pl.BlockSpec((tb, Kp2), lambda i: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
            pl.BlockSpec((tb, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, Kp2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, Kp2), jnp.float32),
        compiler_params=kb.compiler_params(
            kb.TRITON, interpret=interpret, num_warps=4, num_stages=2),
        interpret=interpret,
    )(p, r, d)
    return out[:B, :K]
