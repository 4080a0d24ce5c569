"""Single-token KV-cache attention (decode) — Pallas kernels (TPU + GPU).

TPU schedule — grid (B, H, n_l_blocks); the cache-length dimension is
innermost and sequential, carrying online-softmax state in VMEM scratch
(flash-decoding style, one pass over the cache). ``cache_len`` arrives
via scalar prefetch (SMEM) so block masking is resolved on-core.

VMEM per step (bl = 256, D = 128): k,v blocks (2 x 64 KiB bf16) + q
(32 KiB, broadcast over its 8-sublane tile) + f32 scratch ≈ 0.2 MiB.

GPU schedule — grid (B, H), one program per (batch, head): the cache is
walked with an on-chip ``fori_loop`` whose upper bound is clamped to
``ceil(cache_len / bl)`` so blocks past the valid prefix are never read;
(m, l, acc) ride in registers (Triton grids have no sequential axis).
``cache_len`` is a (1,)-shaped array input (no SMEM on GPU Pallas).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend as kb

MASK_VALUE = float("-inf")
M_INIT = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale: float, bl: int, n_l_blocks: int):
    li = pl.program_id(2)

    @pl.when(li == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, M_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cache_len = len_ref[0]
    l_start = li * bl

    @pl.when(l_start < cache_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (1, D) row
        k = k_ref[0, 0].astype(jnp.float32)               # (bl, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = l_start + jax.lax.broadcasted_iota(jnp.int32, (1, bl), 1)
        s = jnp.where(pos < cache_len, s, MASK_VALUE)     # (1, bl)
        m_prev = m_scr[:1, :1]
        l_prev = l_scr[:1, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(li == n_l_blocks - 1)
    def _finalize():
        l = l_scr[:1, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@kb.register("decode_attention", kb.MOSAIC)
def decode_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                            cache_len: jax.Array, *, bl: int = 256,
                            scale=None, interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k/v: (B, Hkv, L, D); cache_len: () int32.
    Returns (B, H, D)."""
    B, H, D = q.shape
    Hkv, L = k.shape[1], k.shape[2]
    group = H // Hkv
    bl = min(bl, L)
    assert L % bl == 0, (L, bl)
    n_l = L // bl
    if scale is None:
        scale = D ** -0.5

    kernel = functools.partial(_decode_kernel, scale=scale, bl=bl, n_l_blocks=n_l)
    q4 = q[:, :, None, :]                                  # (B, H, 1, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, n_l),
        in_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, li, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bl, D), lambda b, h, li, lens: (b, h // group, li, 0)),
            pl.BlockSpec((1, 1, bl, D), lambda b, h, li, lens: (b, h // group, li, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, li, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.float32),   # m
            pltpu.VMEM((8, 128), jnp.float32),   # l
            pltpu.VMEM((1, D), jnp.float32),     # acc
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        compiler_params=kb.compiler_params(
            kb.MOSAIC, interpret=interpret,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len.reshape(1).astype(jnp.int32), q4, k, v)
    return out[:, :, 0, :]


# ---------------------------------------------------------------------------
# GPU-Triton variant
# ---------------------------------------------------------------------------

def _decode_kernel_gpu(len_ref, q_ref, k_ref, v_ref, o_ref, *, scale: float,
                       bl: int, n_l_blocks: int):
    cache_len = len_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)                # (1, D)
    D = q.shape[-1]
    hi = jnp.minimum(n_l_blocks, (cache_len + bl - 1) // bl)

    def body(li, carry):
        m_prev, l_prev, acc = carry
        l_start = li * bl
        k = k_ref[0, 0, pl.ds(l_start, bl), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(l_start, bl), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = l_start + jax.lax.broadcasted_iota(jnp.int32, (1, bl), 1)
        s = jnp.where(pos < cache_len, s, MASK_VALUE)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((1, 1), M_INIT, jnp.float32),
            jnp.zeros((1, 1), jnp.float32),
            jnp.zeros((1, D), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, hi, body, init)
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@kb.register("decode_attention", kb.TRITON)
def decode_attention_kernel_gpu(q: jax.Array, k: jax.Array, v: jax.Array,
                                cache_len: jax.Array, *, bl: int = 256,
                                scale=None,
                                interpret: bool = False) -> jax.Array:
    """Same contract as :func:`decode_attention_kernel`, Triton schedule."""
    B, H, D = q.shape
    Hkv, L = k.shape[1], k.shape[2]
    group = H // Hkv
    bl = min(bl, L)
    assert L % bl == 0, (L, bl)
    n_l = L // bl
    if scale is None:
        scale = D ** -0.5

    kernel = functools.partial(_decode_kernel_gpu, scale=scale, bl=bl,
                               n_l_blocks=n_l)
    q4 = q[:, :, None, :]                                  # (B, H, 1, D)

    out = pl.pallas_call(
        kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h: (0,)),
            pl.BlockSpec((1, 1, 1, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, L, D), lambda b, h: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, L, D), lambda b, h: (b, h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        compiler_params=kb.compiler_params(
            kb.TRITON, interpret=interpret, num_warps=4, num_stages=2),
        interpret=interpret,
    )(cache_len.reshape(1).astype(jnp.int32), q4, k, v)
    return out[:, :, 0, :]
