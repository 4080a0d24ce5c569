"""Property tests (hypothesis) for the replay memory's ring-buffer
invariants and the staging/flush semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (pip install "
    "hypothesis); deterministic replay coverage lives in "
    "test_replay_wraparound.py")
from hypothesis import given, settings, strategies as st

from repro.core import replay
from repro.core.replay import (FIELDS, frame_words, per_sample,
                               replay_add_batch, replay_init, replay_sample,
                               replay_size, stratified_indices)
from repro.kernels.segment_tree import next_pow2, tree_build

OBS = (3, 3, 1)


def _batch(start: int, n: int):
    obs = np.arange(start, start + n, dtype=np.uint8)[:, None, None, None]
    return {
        "obs": jnp.asarray(np.broadcast_to(obs, (n,) + OBS)),
        "action": jnp.arange(start, start + n, dtype=jnp.int32) % 5,
        "reward": jnp.arange(start, start + n, dtype=jnp.float32),
        "next_obs": jnp.asarray(np.broadcast_to(obs, (n,) + OBS)),
        "done": jnp.zeros((n,), jnp.bool_),
    }


@settings(max_examples=25, deadline=None)
@given(cap=st.integers(4, 32), adds=st.lists(st.integers(1, 10), min_size=1,
                                             max_size=6))
def test_size_and_cursor_invariants(cap, adds):
    state = replay_init(cap, OBS)
    total = 0
    for i, n in enumerate(adds):
        state = replay_add_batch(state, _batch(total, n))
        total += n
        assert int(replay_size(state)) == min(total, cap)
        assert int(state["cursor"]) == total % cap


@settings(max_examples=25, deadline=None)
@given(cap=st.integers(4, 24), n1=st.integers(1, 24), n2=st.integers(1, 24))
def test_wraparound_keeps_newest(cap, n1, n2):
    state = replay_init(cap, OBS)
    state = replay_add_batch(state, _batch(0, n1))
    state = replay_add_batch(state, _batch(n1, n2))
    total = n1 + n2
    stored = set(np.asarray(state["reward"])[: int(replay_size(state))].astype(int))
    newest = set(range(max(0, total - cap), total))
    assert stored == newest


@settings(max_examples=20, deadline=None)
@given(cap=st.integers(8, 32), n=st.integers(1, 32), batch=st.integers(1, 16),
       seed=st.integers(0, 1000))
def test_sample_only_valid_entries(cap, n, batch, seed):
    state = replay_init(cap, OBS)
    state = replay_add_batch(state, _batch(0, n))
    got = replay_sample(state, jax.random.PRNGKey(seed), batch)
    valid = set(range(max(0, n - cap), n))
    for r in np.asarray(got["reward"]).astype(int):
        assert r in valid
    assert got["obs"].shape == (batch,) + OBS


def test_flush_at_sync_freezes_snapshot():
    """The §3 determinism property: samples drawn from a snapshot are
    unaffected by later adds (the staged experiences of the same cycle)."""
    state = replay_init(16, OBS)
    state = replay_add_batch(state, _batch(0, 8))
    snapshot = state
    key = jax.random.PRNGKey(0)
    before = replay_sample(snapshot, key, 8)
    _ = replay_add_batch(state, _batch(8, 8))   # staged flush (new buffer)
    after = replay_sample(snapshot, key, 8)
    for k in before:
        np.testing.assert_array_equal(np.asarray(before[k]),
                                      np.asarray(after[k]))


class PlainRing:
    """The reference: a plain ring buffer that stores every field as it
    comes, frame stacks as ``u8[capacity, *obs_shape]``."""

    def __init__(self, cap, obs_shape):
        self.cap, self.cursor, self.size = cap, 0, 0
        self.data = {"obs": np.zeros((cap,) + obs_shape, np.uint8),
                     "action": np.zeros((cap,), np.int32),
                     "reward": np.zeros((cap,), np.float32),
                     "next_obs": np.zeros((cap,) + obs_shape, np.uint8),
                     "done": np.zeros((cap,), np.bool_)}
        self.priority = np.zeros((next_pow2(cap),), np.float32)

    def add(self, batch):
        for i in range(batch["action"].shape[0]):
            for k in FIELDS:
                self.data[k][self.cursor] = np.asarray(batch[k][i])
            self.priority[self.cursor] = 1.0   # new slots: max priority
            self.cursor = (self.cursor + 1) % self.cap
            self.size = min(self.size + 1, self.cap)

    def uniform_index(self, key, n):
        return jax.random.randint(key, (n,), 0, max(self.size, 1))

    def prioritized_index(self, key, n):
        tree = tree_build(jnp.asarray(self.priority))
        return stratified_indices(tree, key, n, jnp.int32(self.size))

    def gather(self, idx):
        return {k: v[np.asarray(idx)] for k, v in self.data.items()}


def _random_batch(rng, n, obs_shape):
    return {
        "obs": jnp.asarray(rng.integers(0, 256, (n,) + obs_shape, np.uint8)),
        "action": jnp.asarray(rng.integers(0, 6, (n,), np.int32)),
        "reward": jnp.asarray(rng.standard_normal(n).astype(np.float32)),
        "next_obs": jnp.asarray(
            rng.integers(0, 256, (n,) + obs_shape, np.uint8)),
        "done": jnp.asarray(rng.random(n) < 0.3),
    }


@pytest.mark.parametrize("wrap", [False, True], ids=["fill", "wrap"])
@pytest.mark.parametrize("prioritized", [False, True],
                         ids=["uniform", "prioritized"])
@pytest.mark.parametrize("obs_shape", [(84, 84, 4), (15, 15, 3), (3, 3, 1)],
                         ids=["84x84x4", "15x15x3", "3x3x1"])
def test_stored_frames_round_trip(obs_shape, prioritized, wrap,
                                  monkeypatch):
    """Whatever shape the replay stores a frame stack in, its draws are
    bitwise the draws of a plain ring buffer given the same keys, and
    the lane rows' padding words are zeros that never reach a batch.
    Adds are packed four stacks at a time, so whole and partial chunks
    both occur."""
    monkeypatch.setattr(replay, "PACK_ROWS", 4)
    cap, batch = 12, 16
    rng = np.random.default_rng(7)
    state = replay_init(cap, obs_shape, prioritized=prioritized)
    ref = PlainRing(cap, obs_shape)
    for n in ([5, 4] if not wrap else [5, 9, 7]):   # 9 or 21 in all
        b = _random_batch(rng, n, obs_shape)
        state = replay_add_batch(state, b)
        ref.add(b)

    width = frame_words(obs_shape, np.uint8)
    assert (width > 0) == (obs_shape != (3, 3, 1))
    size = int(np.prod(obs_shape))
    words = -(-size // 4)
    for k in ("obs", "next_obs"):
        stored = np.asarray(state[k])
        if width:
            assert stored.shape == (cap, width)
            assert stored.dtype == np.uint32
            assert not stored[:, words:].any()
            if size % 4:   # the last word's unused high bytes
                assert not (stored[:, words - 1] >> 8 * (size % 4)).any()
        else:
            assert stored.shape == (cap,) + obs_shape
            assert stored.dtype == np.uint8

    for seed in range(3):
        key = jax.random.PRNGKey(seed)
        if prioritized:
            got = per_sample(state, key, batch, jnp.float32(0.4))
            idx = ref.prioritized_index(key, batch)
            np.testing.assert_array_equal(np.asarray(got["index"]),
                                          np.asarray(idx))
        else:
            got = replay_sample(state, key, batch)
            idx = ref.uniform_index(key, batch)
        want = ref.gather(idx)
        for k in FIELDS:
            assert got[k].shape == want[k].shape, k
            assert got[k].dtype == want[k].dtype, k
            np.testing.assert_array_equal(np.asarray(got[k]), want[k],
                                          err_msg=k)


@pytest.mark.parametrize("obs_shape,dtype,width", [
    ((84, 84, 4), np.uint8, 7168),   # 7,056 words in 56 lane rows
    ((84, 84, 1), np.uint8, 1792),   # 1,764 words in 14
    ((64, 64, 4), np.uint8, 4096),   # exact: no padding
    ((11, 12, 4), np.uint8, 256),    # 132 words
    ((15, 15, 3), np.uint8, 256),    # 675 bytes: the last word padded
    ((16, 8, 4), np.uint8, 128),     # exactly one lane row
    ((10, 10, 2), np.uint8, 0),      # 50 words: under one lane row
    ((3, 3, 1), np.uint8, 0),
    ((84, 84, 4), np.float32, 0),    # not uint8
    ((128,), np.float32, 0),         # vector observations
])
def test_frame_words_follow_shape_and_dtype(obs_shape, dtype, width):
    assert frame_words(obs_shape, dtype) == width
    state = replay_init(4, obs_shape, obs_dtype=dtype)
    want = (4, width) if width else (4,) + obs_shape
    assert state["obs"].shape == state["next_obs"].shape == want
