"""Causal GQA flash attention — Pallas kernels (TPU Mosaic + GPU Triton).

TPU schedule — grid (B, H, n_q_blocks, n_k_blocks); the k-block dimension
is innermost and iterated sequentially on a TPU core, carrying the
online-softmax state (m, l, acc) in VMEM scratch across k-steps — the
classic TPU flash schedule. Causal (and sliding-window) k-blocks that are
fully masked are skipped with ``pl.when``.

VMEM working set per grid step (bq = bk = 128, D = 128, bf16 in / f32 acc):
  q (128x128x2B = 32 KiB) + k,v (64 KiB) + acc/m/l scratch (f32: 64 KiB +
  2x512 B) + out (32 KiB) ≈ 0.2 MiB — far under the ~16 MiB v5e VMEM,
  leaving headroom for double-buffered pipelines.

MXU alignment: bq, bk, D are multiples of 128 (ops.py pads head_dim).

GPU schedule — grid (B, H, n_q_blocks), every program independent (Triton
has no sequential grid axis): each program owns one q block and walks the
k blocks in an on-chip ``fori_loop``, carrying (m, l, acc) in registers
and slicing K/V out of the full per-head tile with ``pl.ds``. Causal
masking additionally clamps the loop's upper bound so fully-masked tail
blocks are never read.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend as kb

MASK_VALUE = float("-inf")
M_INIT = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, bq: int, bk: int, causal: bool,
                  window: Optional[int], n_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, M_INIT)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk

    # static-shape block skip conditions (dynamic on grid ids only)
    needed = jnp.bool_(True)
    if causal:
        needed &= k_start <= q_start + bq - 1          # below/at diagonal
    if window is not None:
        needed &= k_start + bk - 1 > q_start - window  # inside the window

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_scr[:, :1]                          # (bq, 1)
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                         # masked -> exp(-inf)=0
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_k_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@kb.register("flash_attention", kb.MOSAIC)
def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: Optional[int] = None,
                           bq: int = 128, bk: int = 128,
                           scale: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, Hkv, S, D) with H % Hkv == 0.
    S must be divisible by bq and bk; D should be 128-aligned — ops.py pads
    head_dim and passes the true (unpadded) scale."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    n_q, n_k = S // bq, S // bk
    if scale is None:
        scale = D ** -0.5

    kernel = functools.partial(
        _flash_kernel, scale=scale, bq=bq, bk=bk, causal=causal,
        window=window, n_k_blocks=n_k)

    grid = (B, H, n_q, n_k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # m (lane-padded)
            pltpu.VMEM((bq, 128), jnp.float32),   # l
            pltpu.VMEM((bq, D), jnp.float32),     # acc
        ],
        compiler_params=kb.compiler_params(
            kb.MOSAIC, interpret=interpret,
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out


# ---------------------------------------------------------------------------
# GPU-Triton variant
# ---------------------------------------------------------------------------

def _flash_kernel_gpu(q_ref, k_ref, v_ref, o_ref, *, scale: float, bq: int,
                      bk: int, causal: bool, window: Optional[int],
                      n_k_blocks: int):
    qi = pl.program_id(2)
    q_start = qi * bq
    q = q_ref[0, 0].astype(jnp.float32)                # (bq, D)
    D = q.shape[-1]

    hi = n_k_blocks
    if causal:
        # last k block that intersects the diagonal of this q block
        hi = jnp.minimum(n_k_blocks, (q_start + bq + bk - 1) // bk)
    lo = 0
    if window is not None:
        lo = jnp.maximum(0, (q_start - window + 1) // bk)

    def body(ki, carry):
        m_prev, l_prev, acc = carry
        k_start = ki * bk
        k = k_ref[0, 0, pl.ds(k_start, bk), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(k_start, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, MASK_VALUE)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((bq, 1), M_INIT, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32),
            jnp.zeros((bq, D), jnp.float32))
    _, l, acc = jax.lax.fori_loop(lo, hi, body, init)
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@kb.register("flash_attention", kb.TRITON)
def flash_attention_kernel_gpu(q: jax.Array, k: jax.Array, v: jax.Array, *,
                               causal: bool = True,
                               window: Optional[int] = None,
                               bq: int = 128, bk: int = 128,
                               scale: Optional[float] = None,
                               interpret: bool = False) -> jax.Array:
    """Same contract as :func:`flash_attention_kernel`, Triton schedule."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    n_q, n_k = S // bq, S // bk
    if scale is None:
        scale = D ** -0.5

    kernel = functools.partial(
        _flash_kernel_gpu, scale=scale, bq=bq, bk=bk, causal=causal,
        window=window, n_k_blocks=n_k)

    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, qi: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, S, D), lambda b, h, qi: (b, h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, qi: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        compiler_params=kb.compiler_params(
            kb.TRITON, interpret=interpret, num_warps=4, num_stages=2),
        interpret=interpret,
    )(q, k, v)
