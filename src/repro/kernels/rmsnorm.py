"""Fused RMSNorm — Pallas kernel (TPU Mosaic and GPU Triton).

Grid over row blocks; each step normalizes (block_rows, D) in one fused
pass (mean-square, rsqrt, scale) instead of XLA's multi-kernel
reduce + mul chain. D is kept whole per block (norm is a row reduction);
VMEM per step at block_rows=256, D=8192, bf16: 4 MiB in + 4 MiB out.

The kernel body is backend-neutral — no scratch, no scalar memory, a
fully parallel grid — so the same ``pallas_call`` lowers through Mosaic
on TPU and Triton on GPU; only the compiler params differ (built by
``backend.compiler_params``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend as kb


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps) * g_ref[...].astype(jnp.float32)
                  ).astype(o_ref.dtype)


def rmsnorm_kernel(x: jax.Array, gamma: jax.Array, *, eps: float = 1e-5,
                   block_rows: int = 256, interpret: bool = False,
                   backend: str = kb.MOSAIC) -> jax.Array:
    """x: (..., D) -> same shape. Rows are processed in blocks."""
    orig_shape = x.shape
    D = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, D)
    br = min(block_rows, rows)
    while rows % br:
        br -= 1
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, D), x.dtype),
        compiler_params=kb.compiler_params(
            backend, interpret=interpret,
            dimension_semantics=("parallel",), num_warps=4),
        interpret=interpret,
    )(x2, gamma)
    return out.reshape(orig_shape)


kb.register("rmsnorm", kb.MOSAIC)(rmsnorm_kernel)
kb.register("rmsnorm", kb.TRITON)(
    functools.partial(rmsnorm_kernel, backend=kb.TRITON))
