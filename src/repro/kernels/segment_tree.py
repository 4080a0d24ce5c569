"""Stratified prefix-sum descent over a sum-tree — the PER sampling op.

Proportional prioritized replay (Schaul et al. 2016) samples leaf i with
probability p_i / Σp. The device-resident formulation stores the leaf
masses of a heap-layout segment/sum-tree (``tree_build``) and answers a
batch of inverse-CDF queries: for each target t on [0, Σp) find the leaf
whose inclusive prefix sum first exceeds t.

The XLA oracle (``ref.segment_tree_sample``) walks the tree root-to-leaf
(log₂P gathers per query). Per-lane tree gathers do not map onto the VPU,
so both Pallas schedules use the equivalent *compare-count* formulation
over the leaf level: idx(t) = #{i : cumsum_i <= t}, computed blockwise in
one pass over the leaf array (exactly the flash-decoding pattern already
used by ``decode_attention``):

TPU Mosaic — grid over leaf blocks (innermost, sequential). A block of
bl leaves is laid out as (bl/128, 128) so each row is one lane vector;
the in-row prefix sum is a log-step shift-and-add with ``pltpu.roll``
(Mosaic has no ``cumsum``), and rows are chained through a running
offset. The offset and per-target, per-lane hit counts ride in VMEM
scratch; the (n,) query batch stays resident across steps. VMEM per
step at bl=1024: 4 KiB of leaves, and one (N, 128) compare tile per
row plus the (N, 128) count scratch, 16 KiB each at N=32 (64 KiB at
N=128) — far below the 16 MiB scoped-VMEM default of a v5e.

GPU Triton — grid over target blocks, one program per 128 queries; the
leaf array is walked with an on-chip ``fori_loop``; (offset, counts)
ride in registers (Triton grids have no sequential axis).

Both schedules agree with the tree-descent oracle exactly whenever the
prefix sums are exactly representable (the equivalence tests use integer
masses); for general floats they differ only on measure-zero CDF
boundaries, like any reordered reduction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend as kb


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1)."""
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


def tree_build(priority: jax.Array) -> jax.Array:
    """(P,) leaf masses -> (2P,) heap-layout sum-tree, pure XLA.

    P must be a power of two. ``tree[1]`` is the root (total mass), node
    i's children are 2i and 2i+1, leaves occupy [P, 2P); ``tree[0]`` is
    unused padding. Shared by every backend (building is a cheap fully
    parallel reduction; only the query path is a custom kernel).
    """
    P = priority.shape[0]
    assert P & (P - 1) == 0, f"leaf count {P} not a power of two"
    levels = [priority.astype(jnp.float32)]
    while levels[-1].shape[0] > 1:
        levels.append(levels[-1].reshape(-1, 2).sum(axis=1))
    return jnp.concatenate([jnp.zeros((1,), jnp.float32)] + levels[::-1])


# ---------------------------------------------------------------------------
# TPU Mosaic schedule
# ---------------------------------------------------------------------------

def _lane_prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the 128-lane axis of ``x`` (rows, 128):
    Hillis-Steele shift-and-add, log2(128) = 7 rolls."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < x.shape[1]:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1), 0.0)
        shift *= 2
    return x


def _seg_kernel(leaf_ref, t_ref, o_ref, cnt_scr, off_scr, *, rows: int,
                n_blocks: int, max_idx: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cnt_scr[...] = jnp.zeros_like(cnt_scr)
        off_scr[...] = jnp.zeros_like(off_scr)

    leaves = leaf_ref[...].astype(jnp.float32)            # (rows, 128)
    cum = _lane_prefix_sum(leaves)                        # in-row prefix
    row_mass = jnp.sum(leaves, axis=1, keepdims=True)     # (rows, 1)
    t = t_ref[...].astype(jnp.float32)                    # (N, 1)
    off = off_scr[0:1, :]                                 # (1, 128)
    hits = cnt_scr[...]                                   # (N, 128)
    for r in range(rows):
        hits = hits + (off + cum[r:r + 1, :] <= t).astype(jnp.float32)
        off = off + row_mass[r:r + 1, :]
    cnt_scr[...] = hits
    off_scr[0:1, :] = off

    @pl.when(i == n_blocks - 1)
    def _finalize():
        cnt = jnp.sum(cnt_scr[...], axis=1, keepdims=True)   # (N, 1)
        o_ref[...] = jnp.broadcast_to(
            jnp.minimum(cnt, max_idx).astype(jnp.int32), o_ref.shape)


@kb.register("segment_tree", kb.MOSAIC)
def segment_tree_kernel(tree: jax.Array, targets: jax.Array, *,
                        block: int = 1024,
                        interpret: bool = False) -> jax.Array:
    """tree: (2P,) f32 sum-tree; targets: (n,) f32. Returns (n,) int32."""
    two_p = tree.shape[0]
    assert two_p & (two_p - 1) == 0, two_p
    P = two_p // 2
    leaves = tree[P:]
    L = max(P, 128)                                   # lane-pad tiny trees
    if L > P:
        leaves = jnp.pad(leaves, (0, L - P))
    bl = min(block, L)                                # both powers of two
    rows = bl // 128
    n_blocks = L // bl
    n = targets.shape[0]
    N = max(-(-n // 8) * 8, 8)                        # sublane-pad queries
    t = targets.astype(jnp.float32)
    if N > n:
        t = jnp.pad(t, (0, N - n), constant_values=-1.0)   # count 0, sliced

    kernel = functools.partial(_seg_kernel, rows=rows, n_blocks=n_blocks,
                               max_idx=P - 1)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((rows, 128), lambda i: (i, 0)),
            pl.BlockSpec((N, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((N, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 128), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((N, 128), jnp.float32),        # per-target, per-lane hits
            pltpu.VMEM((8, 128), jnp.float32),        # running offset, row 0
        ],
        compiler_params=kb.compiler_params(
            kb.MOSAIC, interpret=interpret, dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(leaves.reshape(L // 128, 128), t.reshape(N, 1))
    return out[:n, 0]


# ---------------------------------------------------------------------------
# GPU-Triton schedule
# ---------------------------------------------------------------------------

def _seg_kernel_gpu(leaf_ref, t_ref, o_ref, *, bl: int, n_blocks: int,
                    max_idx: int):
    t = t_ref[...].astype(jnp.float32)                # (tb,)
    tb = t.shape[0]

    def body(i, carry):
        off, cnt = carry
        lv = leaf_ref[pl.ds(i * bl, bl)].astype(jnp.float32)
        cum = off + jnp.cumsum(lv)
        cnt = cnt + jnp.sum((cum[:, None] <= t[None, :]).astype(jnp.float32),
                            axis=0)
        return off + jnp.sum(lv), cnt

    _, cnt = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.float32(0.0), jnp.zeros((tb,), jnp.float32)))
    o_ref[...] = jnp.minimum(cnt, max_idx).astype(jnp.int32)


@kb.register("segment_tree", kb.TRITON)
def segment_tree_kernel_gpu(tree: jax.Array, targets: jax.Array, *,
                            block: int = 1024, tb: int = 128,
                            interpret: bool = False) -> jax.Array:
    """Same contract as :func:`segment_tree_kernel`, Triton schedule."""
    two_p = tree.shape[0]
    assert two_p & (two_p - 1) == 0, two_p
    P = two_p // 2
    leaves = tree[P:]
    bl = min(block, P)
    n_blocks = P // bl
    n = targets.shape[0]
    tb = min(tb, next_pow2(n))
    NT = -(-n // tb) * tb
    t = targets.astype(jnp.float32)
    if NT > n:
        t = jnp.pad(t, (0, NT - n), constant_values=-1.0)

    kernel = functools.partial(_seg_kernel_gpu, bl=bl, n_blocks=n_blocks,
                               max_idx=P - 1)
    out = pl.pallas_call(
        kernel,
        grid=(NT // tb,),
        in_specs=[
            pl.BlockSpec((P,), lambda i: (0,)),
            pl.BlockSpec((tb,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((tb,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((NT,), jnp.int32),
        compiler_params=kb.compiler_params(
            kb.TRITON, interpret=interpret, num_warps=4, num_stages=2),
        interpret=interpret,
    )(leaves, t)
    return out[:n]
