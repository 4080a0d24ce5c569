"""Replay memory 𝒟 — device-resident ring buffer, pure-functional ops.

Paper semantics reproduced exactly (§3): during a Concurrent-Training
cycle the trainer samples only from the 𝒟 *snapshot* taken at the cycle
boundary; experiences collected by the samplers are staged and flushed
into 𝒟 only at the θ⁻ ← θ synchronization point. In this JAX
formulation the "staging buffer" is simply the sampler scan's stacked
output, and the flush is one ``replay_add_batch`` at the end of the
jitted cycle — 𝒟 is immutable during training *by dataflow construction*,
which is the determinism guarantee the paper argues for.

Prioritized replay (Schaul et al. 2016) extends the same state dict with
a leaf-mass array for the segment/sum-tree (``kernels/segment_tree``).
The staging discipline carries over: priority updates computed by the
trainer are *staged* during the cycle and flushed only at the sync
point (``per_flush_priorities``), so the snapshot's sampling
distribution is frozen for the whole training burst — the PER analogue
of the snapshot-𝒟 guarantee. Staged updates combine by ``max`` (an
order-independent reduction), keeping the flush deterministic even when
one slot is sampled by several minibatches.

Transitions are stored as full (obs, action, reward, next_obs, done)
records. Storage dtype for observations is uint8 (the paper's 1-byte
pixel economy).

How a frame stack is stored follows its shape and dtype alone. A uint8
stack of at least one lane row (128 32-bit words, 512 bytes) is packed
four bytes to a ``uint32`` word and zero-padded to a whole number of
lane rows: ``u32[capacity, words]`` (84x84x4: 7,056 words padded to
7,168). A TPU's default layout puts the dimension that needs the least
padding in the lanes; a raw stack's row (84*84*4 = 28,224 bytes) fits
no lane multiple, so the capacity axis took the lanes and XLA
relayouted the whole store around every draw and every flush. The
aligned row keeps the row-major layout that the gather and the scatter
use, so the store is never relayouted. Smaller or non-uint8 stacks
(vector observations) are stored as they are. ``replay_add_batch``
packs, and ``replay_sample`` / ``per_sample`` unpack the minibatch, so
the bytes that leave the API are the bytes that came in. ``obs_like``,
a zero-size ``[0, *obs_shape]`` array of the stack's dtype, carries the
stack's shape for the unpacking.

This module is the public replay API (the concurrent cycle, the
baselines and the disaggregated learner all import from here); the
staging/flush timeline is diagrammed in docs/architecture.md.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.segment_tree import next_pow2, tree_build

__all__ = [
    "ReplayState", "FIELDS", "frame_words", "replay_init",
    "replay_capacity", "replay_size", "replay_is_prioritized",
    "replay_add_batch", "replay_sample", "per_tree", "stratified_indices",
    "per_sample", "per_stage_priorities", "per_flush_priorities",
]

ReplayState = Dict[str, jax.Array]

FIELDS = ("obs", "action", "reward", "next_obs", "done")
FRAMES = ("obs", "next_obs")

LANES = 128      # 32-bit words in one lane row of a TPU tile
BYTE_SHIFTS = (0, 8, 16, 24)   # byte k of a packed word: bits 8k..8k+7
PACK_ROWS = 4096   # stacks packed at a time on the way in


def frame_words(obs_shape: Tuple[int, ...], obs_dtype) -> int:
    """32-bit words that one stored frame stack takes, a whole number of
    128-word lane rows, or 0 where the stack is stored as it is (not
    uint8, or under one lane row)."""
    words = -(-math.prod(obs_shape) // 4)
    if jnp.dtype(obs_dtype) != jnp.uint8 or words < LANES:
        return 0
    return -(-words // LANES) * LANES


def _pack(frames: jax.Array, width: int) -> jax.Array:
    """u8[n, *obs_shape] -> u32[n, width]: four bytes a word, in order,
    zero-padded to ``width`` words."""
    n = frames.shape[0]
    flat = frames.reshape(n, -1)
    flat = jnp.pad(flat, ((0, 0), (0, -flat.shape[1] % 4)))
    quads = flat.reshape(n, -1, 4).astype(jnp.uint32)
    words = jnp.sum(quads << jnp.array(BYTE_SHIFTS, jnp.uint32), axis=-1,
                    dtype=jnp.uint32)
    return jnp.pad(words, ((0, 0), (0, width - words.shape[1])))


def _unpack(words: jax.Array, obs_shape: Tuple[int, ...]) -> jax.Array:
    """u32[n, width] -> u8[n, *obs_shape]: drops the padding. The barrier
    keeps the layout the consumer wants (the first conv takes the batch
    in the lanes) from reaching back into the unpacking, so the words
    are split with a frame row in the lanes (84 of 128 used at 84x84)
    and not the batch (32 of 128)."""
    n, size = words.shape[0], math.prod(obs_shape)
    quads = (words[:, :-(-size // 4), None]
             >> jnp.array(BYTE_SHIFTS, jnp.uint32)) & 0xFF
    flat = quads.astype(jnp.uint8).reshape(n, -1)[:, :size]
    return jax.lax.optimization_barrier(flat.reshape((n,) + obs_shape))


def _set_frames(state: ReplayState, store: jax.Array, idx: jax.Array,
                frames: jax.Array) -> jax.Array:
    """``store.at[idx].set(frames)`` with the stacks as the state stores
    them, packed ``PACK_ROWS`` at a time: packing a whole prepopulate in
    one go (50,000 stacks at 84x84x4) holds ~3 GB of temporaries."""
    like = state["obs_like"]
    width = frame_words(like.shape[1:], like.dtype)
    frames = frames.astype(like.dtype)
    if not width:
        return store.at[idx].set(frames)
    for s in range(0, idx.shape[0], PACK_ROWS):
        rows = _pack(frames[s:s + PACK_ROWS], width)
        store = store.at[idx[s:s + PACK_ROWS]].set(rows)
    return store


def _gather(state: ReplayState, idx: jax.Array) -> Dict[str, jax.Array]:
    out = {k: state[k][idx] for k in FIELDS}
    like = state["obs_like"]
    if frame_words(like.shape[1:], like.dtype):
        for k in FRAMES:
            out[k] = _unpack(out[k], like.shape[1:])
    return out


def replay_init(capacity: int, obs_shape: Tuple[int, ...],
                obs_dtype=jnp.uint8, prioritized: bool = False) -> ReplayState:
    obs_shape = tuple(obs_shape)
    width = frame_words(obs_shape, obs_dtype)
    frames = (((capacity, width), jnp.uint32) if width
              else ((capacity,) + obs_shape, obs_dtype))
    state = {
        "obs": jnp.zeros(*frames),
        "action": jnp.zeros((capacity,), jnp.int32),
        "reward": jnp.zeros((capacity,), jnp.float32),
        "next_obs": jnp.zeros(*frames),
        "done": jnp.zeros((capacity,), jnp.bool_),
        "obs_like": jnp.zeros((0,) + obs_shape, obs_dtype),
        "cursor": jnp.zeros((), jnp.int32),
        "size": jnp.zeros((), jnp.int32),
    }
    if prioritized:
        # Leaf masses of the sum-tree, padded to a power of two so the
        # tree is perfect; slots >= capacity stay 0 forever (never
        # sampled). Unfilled slots < capacity also carry 0 mass, which
        # is how the prioritized path masks them.
        state["priority"] = jnp.zeros((next_pow2(capacity),), jnp.float32)
        # Running max of priority mass; new transitions enter at this
        # mass so every experience is replayed at least once (Schaul
        # et al. §3.3).
        state["max_priority"] = jnp.ones((), jnp.float32)
    return state


def replay_capacity(state: ReplayState) -> int:
    return state["obs"].shape[0]


def replay_size(state: ReplayState) -> jax.Array:
    return state["size"]


def replay_is_prioritized(state: ReplayState) -> bool:
    return "priority" in state


def replay_add_batch(state: ReplayState, batch: Dict[str, jax.Array]) -> ReplayState:
    """Append n transitions (the staging-buffer flush). batch leaves have
    leading dim n. Wraps modulo capacity; oldest entries overwritten.

    Equivalent to appending the n transitions one at a time: when n
    exceeds capacity, only the last ``capacity`` transitions survive (the
    prefix would be overwritten before it could ever be sampled), so the
    overflowing prefix is dropped up front. This also keeps the scatter
    indices unique — with duplicates, ``.at[idx].set`` applies them in
    undefined order.

    On a prioritized state the overwritten slots' old priority mass is
    replaced by the current ``max_priority`` (new experiences enter at
    max priority), so stale mass can never outlive its transition."""
    cap = replay_capacity(state)
    n = batch["action"].shape[0]
    offset = jnp.arange(min(n, cap), dtype=jnp.int32)
    if n > cap:
        batch = {k: v[n - cap:] for k, v in batch.items()}
        offset = offset + (n - cap)
    idx = (state["cursor"] + offset) % cap
    new = dict(state)
    for k in FIELDS:
        if k in FRAMES:
            new[k] = _set_frames(state, state[k], idx, batch[k])
        else:
            new[k] = state[k].at[idx].set(batch[k].astype(state[k].dtype))
    if replay_is_prioritized(state):
        new["priority"] = state["priority"].at[idx].set(state["max_priority"])
    new["cursor"] = (state["cursor"] + n) % cap
    new["size"] = jnp.minimum(state["size"] + n, cap)
    return new


def replay_sample(state: ReplayState, key: jax.Array, n: int) -> Dict[str, jax.Array]:
    """Uniform minibatch with replacement (as in Mnih et al. 2015).

    Only filled slots are drawn: ``randint``'s maxval is exclusive, so
    indices are uniform on [0, size) whenever size >= 1. An empty
    buffer degrades to slot 0 (the max(size, 1) floor) rather than an
    out-of-range read — locked in by
    test_replay_wraparound.test_uniform_sample_masks_unfilled_slots."""
    idx = jax.random.randint(key, (n,), 0, jnp.maximum(state["size"], 1))
    return _gather(state, idx)


# ---------------------------------------------------------------------------
# prioritized sampling + deferred priority updates
# ---------------------------------------------------------------------------

def per_tree(state: ReplayState) -> jax.Array:
    """The (2P,) sum-tree over the current leaf masses (pure XLA; built
    once per cycle on the frozen snapshot)."""
    return tree_build(state["priority"])


def stratified_indices(tree: jax.Array, key: jax.Array, n: int,
                       size: jax.Array,
                       backend: Optional[str] = None) -> jax.Array:
    """n stratified inverse-CDF draws from a (2P,) sum-tree: the CDF
    [0, total) splits into n equal strata, one uniform draw each, mapped
    to leaves by the segment-tree kernel. Indices are clamped to the
    filled prefix [0, max(size, 1)) — zero-mass leaves are unreachable
    except at exact CDF boundaries (measure-zero), where the clamp
    applies. Shared by ``per_sample`` and the disaggregated learner."""
    total = tree[1]
    u = jax.random.uniform(key, (n,))
    targets = (jnp.arange(n, dtype=jnp.float32) + u) / n * total
    idx = kops.segment_tree_sample(tree, targets, backend=backend)
    return jnp.minimum(idx, jnp.maximum(size, 1) - 1)


def per_sample(state: ReplayState, key: jax.Array, n: int, beta: jax.Array,
               tree: Optional[jax.Array] = None,
               backend: Optional[str] = None) -> Dict[str, jax.Array]:
    """Stratified proportional minibatch (Schaul et al. 2016 §3.3).

    The CDF [0, total) is split into n equal strata, one uniform draw
    each; the segment-tree kernel maps the draws to leaf indices. Extra
    fields in the returned batch: ``index`` (for the priority update)
    and ``weight`` (importance-sampling correction (N·P(i))^-β,
    normalized by its max). ``tree`` lets the caller pass a prebuilt
    snapshot tree; ``backend`` is the kernel-backend request.
    """
    if tree is None:
        tree = per_tree(state)
    total = tree[1]
    size = jnp.maximum(state["size"], 1)
    idx = stratified_indices(tree, key, n, state["size"], backend=backend)
    # With total > 0 every sampled leaf has positive mass; the floor only
    # bites on an all-zero tree (empty buffer), where it degrades to
    # equal probabilities -> unit weights instead of inf/inf = NaN.
    probs = jnp.maximum(state["priority"][idx] / jnp.maximum(total, 1e-30),
                        1e-30)
    w = (size.astype(jnp.float32) * probs) ** (-beta)
    w = w / jnp.maximum(jnp.max(w), 1e-30)
    out = _gather(state, idx)
    out["index"] = idx
    out["weight"] = w
    return out


def per_stage_priorities(pending: jax.Array, idx: jax.Array,
                         td_abs: jax.Array, alpha: float,
                         eps: float) -> jax.Array:
    """Stage new priority masses (|td| + ε)^α into ``pending`` (a (P,)
    array, 0 = untouched). Duplicate indices combine by ``max`` — an
    order-independent reduction, so the later flush is deterministic
    regardless of scatter order."""
    mass = (jnp.abs(td_abs) + eps) ** alpha
    return pending.at[idx].max(mass)


def per_flush_priorities(state: ReplayState, pending: jax.Array) -> ReplayState:
    """Apply staged priority updates at the θ⁻ ← θ sync point (the PER
    analogue of the staging-buffer flush)."""
    new = dict(state)
    new["priority"] = jnp.where(pending > 0, pending, state["priority"])
    new["max_priority"] = jnp.maximum(state["max_priority"], jnp.max(pending))
    return new
