"""sLSTM recurrence — Pallas TPU kernel with VMEM-resident weights.

The xLSTM §Perf analysis (EXPERIMENTS.md) showed the XLA sLSTM scan
re-reads the block-diagonal recurrent matrices R (4·H·Pd² floats) from
HBM every timestep — the dominant memory term of the whole architecture.
This kernel makes the residency structural: R's BlockSpec index_map is
constant, so the Pallas pipeline fetches it into VMEM **once** and every
grid step reuses it; the (c, n, h, m) state lives in VMEM scratch across
the sequential time grid.

Grid: (n_chunks,) sequential; each step consumes a (B, L, 4d) block of
the precomputed input contributions wx and emits (B, L, d) hidden
states, running L recurrence steps in an unrolled fori_loop on-core.

VMEM per step (B=16, L=16, d=768, H=4: R 4x4x192x192 f32 = 2.4 MiB +
wx block 0.8 MiB + state 4x(16,768) f32 = 0.2 MiB) ≈ 3.5 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend as kb


def _slstm_kernel(wx_ref, r_ref, b_ref, c0_ref, n0_ref, h0_ref, m0_ref,
                  hs_ref, cF_ref, nF_ref, hF_ref, mF_ref,
                  c_scr, n_scr, h_scr, m_scr, *,
                  L: int, H: int, Pd: int, n_chunks: int):
    ci = pl.program_id(0)
    d = H * Pd

    @pl.when(ci == 0)
    def _init():
        c_scr[...] = c0_ref[...].astype(jnp.float32)
        n_scr[...] = n0_ref[...].astype(jnp.float32)
        h_scr[...] = h0_ref[...].astype(jnp.float32)
        m_scr[...] = m0_ref[...].astype(jnp.float32)

    R = r_ref[...].astype(jnp.float32)            # (4, H, Pd, Pd) — resident
    bias = b_ref[...].astype(jnp.float32)         # (4d,)

    def step(t, _):
        c, n, h, m = c_scr[...], n_scr[...], h_scr[...], m_scr[...]
        wx = wx_ref[:, t, :].astype(jnp.float32)  # (B, 4d)
        h3 = h.reshape(-1, H, Pd)
        rec = jax.lax.dot_general(
            h3.transpose(1, 0, 2), R.transpose(1, 0, 2, 3),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)   # (H, B, 4, Pd)
        rec = rec.transpose(1, 2, 0, 3).reshape(-1, 4 * d)
        pre = wx + rec + bias[None]
        z_t, i_t, f_t, o_t = jnp.split(pre, 4, axis=-1)
        f_log = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(f_log + m, i_t)
        i_p = jnp.exp(i_t - m_new)
        f_p = jnp.exp(f_log + m - m_new)
        c = f_p * c + i_p * jnp.tanh(z_t)
        n = f_p * n + i_p
        h = jax.nn.sigmoid(o_t) * c / jnp.maximum(n, 1.0)
        c_scr[...], n_scr[...], h_scr[...], m_scr[...] = c, n, h, m_new
        hs_ref[:, t, :] = h.astype(hs_ref.dtype)
        return ()

    jax.lax.fori_loop(0, L, step, (), unroll=True)

    @pl.when(ci == n_chunks - 1)
    def _emit():
        cF_ref[...] = c_scr[...]
        nF_ref[...] = n_scr[...]
        hF_ref[...] = h_scr[...]
        mF_ref[...] = m_scr[...]


@kb.register("slstm_scan", kb.MOSAIC)
def slstm_scan_kernel(wx: jax.Array, R: jax.Array, b: jax.Array,
                      state, *, n_heads: int, chunk: int = 16,
                      interpret: bool = False):
    """wx: (B, S, 4d) input contributions; R: (4, H, Pd, Pd); b: (4d,);
    state: (c, n, h, m) each (B, d) f32.
    Returns hs (B, S, d), final state."""
    B, S, d4 = wx.shape
    d = d4 // 4
    H = n_heads
    Pd = d // H
    L = min(chunk, S)
    assert S % L == 0
    n_chunks = S // L
    c0, n0, h0, m0 = state

    kernel = functools.partial(_slstm_kernel, L=L, H=H, Pd=Pd,
                               n_chunks=n_chunks)
    sstate = jax.ShapeDtypeStruct((B, d), jnp.float32)
    hs, cF, nF, hF, mF = pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((B, L, 4 * d), lambda c: (0, c, 0)),
            pl.BlockSpec((4, H, Pd, Pd), lambda c: (0, 0, 0, 0)),  # resident
            pl.BlockSpec((4 * d,), lambda c: (0,)),
            pl.BlockSpec((B, d), lambda c: (0, 0)),
            pl.BlockSpec((B, d), lambda c: (0, 0)),
            pl.BlockSpec((B, d), lambda c: (0, 0)),
            pl.BlockSpec((B, d), lambda c: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B, L, d), lambda c: (0, c, 0)),
            pl.BlockSpec((B, d), lambda c: (0, 0)),
            pl.BlockSpec((B, d), lambda c: (0, 0)),
            pl.BlockSpec((B, d), lambda c: (0, 0)),
            pl.BlockSpec((B, d), lambda c: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, d), wx.dtype),
            sstate, sstate, sstate, sstate,
        ],
        scratch_shapes=[pltpu.VMEM((B, d), jnp.float32)] * 4,
        compiler_params=kb.compiler_params(
            kb.MOSAIC, interpret=interpret,
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(wx, R, b, c0, n0, h0, m0)
    return hs, (cF, nF, hF, mF)

# No Triton registration: the sLSTM recurrence is strictly sequential per
# timestep with a batch-wide matmul — there is no block parallelism for a
# GPU program to exploit, so dispatch falls back to the XLA reference
# (ref.slstm_scan), which XLA fuses well on GPU.
