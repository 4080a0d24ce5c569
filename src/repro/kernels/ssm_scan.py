"""Chunked Mamba2 SSD scan — Pallas kernels (TPU Mosaic + GPU Triton).

TPU schedule — grid (B, H, n_chunks); chunks are innermost and
sequential, carrying the (P, N) SSM state in VMEM scratch across chunk
steps — the inter-chunk recurrence. Within a chunk the kernel computes
the quadratic intra-chunk term (an (L, L) decay-weighted attention-like
matmul on the MXU) plus the contribution of the carried state, then
updates the state.

VMEM per step (L = 128, P = 64, N = 64, f32): x (32 KiB) + B/C (2x32 KiB)
+ (L, L) decay/score mats (2 x 64 KiB) + state scratch (16 KiB) ≈ 0.3 MiB.

GPU schedule — grid (B, H), one program per sequence: Triton grids have
no sequential axis, so the chunk loop runs on-chip in a ``fori_loop``
carrying the (P, N) state in registers; chunk slices of x/dt/B/C are cut
with ``pl.ds`` and each chunk's y is stored as the loop advances.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend as kb


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_out_ref, h_scr,
                *, L: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (L, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)     # (L,)
    A = a_ref[0]                                  # scalar for this head
    Bm = b_ref[0].astype(jnp.float32)             # (L, N)
    Cm = c_ref[0].astype(jnp.float32)             # (L, N)

    a = dt * A                                    # (L,) log-decay
    cum = jnp.cumsum(a)                           # inclusive
    # intra-chunk: W[i,j] = (C_i.B_j) exp(cum_i - cum_j) dt_j for j<=i
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    D = jnp.where(jj <= ii, jnp.exp(cum[:, None] - cum[None, :]), 0.0)
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, L)
    W = G * D * dt[None, :]
    y = jax.lax.dot_general(W, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, P)
    # cross-chunk: y_i += exp(cum_i) * C_i @ h_prev^T   (h: (P, N))
    h = h_scr[...]
    ycross = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (L, P)
    y = y + ycross * jnp.exp(cum)[:, None]
    # state update: h_new = exp(total) h + sum_j exp(total - cum_j) dt_j x_j B_j^T
    total = cum[L - 1]
    sdec = jnp.exp(total - cum) * dt              # (L,)
    h_in = jax.lax.dot_general(x * sdec[:, None], Bm, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)    # (P, N)
    h_scr[...] = h * jnp.exp(total) + h_in

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        h_out_ref[0, 0] = h_scr[...]


@kb.register("ssm_scan", kb.MOSAIC)
def ssm_scan_kernel(x: jax.Array, dt: jax.Array, A: jax.Array,
                    Bm: jax.Array, Cm: jax.Array, *, chunk: int = 128,
                    interpret: bool = False):
    """x: (B, H, S, P); dt: (B, H, S); A: (H,); Bm/Cm: (B, S, N).
    Returns y (B, H, S, P), final state (B, H, P, N)."""
    B, H, S, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    n_chunks = S // L

    kernel = functools.partial(_ssd_kernel, L=L, n_chunks=n_chunks)
    dt3 = dt.reshape(B, H, n_chunks, L)

    y, h = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, L), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, L, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, L, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=kb.compiler_params(
            kb.MOSAIC, interpret=interpret,
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt3, A.astype(jnp.float32), Bm, Cm)
    return y, h


# ---------------------------------------------------------------------------
# GPU-Triton variant
# ---------------------------------------------------------------------------

def _ssd_kernel_gpu(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_out_ref, *,
                    L: int, P: int, N: int, n_chunks: int):
    A = a_ref[0]

    def chunk_step(ci, h):
        sl = pl.ds(ci * L, L)
        x = x_ref[0, 0, sl, :].astype(jnp.float32)       # (L, P)
        dt = dt_ref[0, 0, sl].astype(jnp.float32)        # (L,)
        Bm = b_ref[0, sl, :].astype(jnp.float32)         # (L, N)
        Cm = c_ref[0, sl, :].astype(jnp.float32)         # (L, N)

        a = dt * A
        cum = jnp.cumsum(a)
        ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
        D = jnp.where(jj <= ii, jnp.exp(cum[:, None] - cum[None, :]), 0.0)
        G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        W = G * D * dt[None, :]
        y = jax.lax.dot_general(W, x, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        ycross = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        y = y + ycross * jnp.exp(cum)[:, None]
        total = cum[L - 1]
        sdec = jnp.exp(total - cum) * dt
        h_in = jax.lax.dot_general(x * sdec[:, None], Bm,
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        y_ref[0, 0, sl, :] = y.astype(y_ref.dtype)
        return h * jnp.exp(total) + h_in

    h = jax.lax.fori_loop(0, n_chunks, chunk_step,
                          jnp.zeros((P, N), jnp.float32))
    h_out_ref[0, 0] = h


@kb.register("ssm_scan", kb.TRITON)
def ssm_scan_kernel_gpu(x: jax.Array, dt: jax.Array, A: jax.Array,
                        Bm: jax.Array, Cm: jax.Array, *, chunk: int = 128,
                        interpret: bool = False):
    """Same contract as :func:`ssm_scan_kernel`, Triton schedule."""
    B, H, S, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    n_chunks = S // L

    kernel = functools.partial(_ssd_kernel_gpu, L=L, P=P, N=N,
                               n_chunks=n_chunks)

    y, h = pl.pallas_call(
        kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, S, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda b, h: (b, h, 0)),
            pl.BlockSpec((1,), lambda b, h: (h,)),
            pl.BlockSpec((1, S, N), lambda b, h: (b, 0, 0)),
            pl.BlockSpec((1, S, N), lambda b, h: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, S, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        compiler_params=kb.compiler_params(
            kb.TRITON, interpret=interpret, num_warps=4, num_stages=1),
        interpret=interpret,
    )(x, dt, A.astype(jnp.float32), Bm, Cm)
    return y, h
