"""Policy serving: a server is a spec plus a carry.

The PR-5 Experiment API made every run a declarative
:class:`ExperimentSpec` whose state is one checkpointable carry. Serving
a trained policy to many concurrent client streams is then just:

    loaded = load_policy(ckpt_dir)              # spec.json + newest
                                                # restorable step_*.npz
    server = make_server(loaded, ServeSpec(policy="egreedy"))
    server.warm_start(n_streams=1024)           # compile every bucket
    ...
    server.submit(stream_id, raw_obs, first=episode_started)
    actions = server.flush()                    # ONE jitted Q batch

The server applies the same many-streams-one-inference-batch discipline
the training sampler uses (``sync_round``) and ``launch/serve.py``
applies to LLM decoding: observations from clients arriving within a
tick window are stacked into ONE jitted ``q_forward`` call (*dynamic
microbatching*), padded up to a fixed set of compile-size *buckets* so a
latency tick never triggers an XLA recompilation (``warm_start``
pre-compiles all of them).

Clients send RAW observations (a rendered uint8 frame or a state
vector); the per-stream frame-stack history lives server-side, updated
by the same ``push_frame`` / zero-on-episode-start rule the sampler
uses. Action selection is :func:`repro.core.policy.policy_step` — the
exact primitive inside ``evaluate`` — with per-stream RNG keys, so
served actions are bitwise-identical to evaluation's choices for the
same (params, observation stack, key), and neither batch padding nor
batch composition can change the action a stream receives
(tests/test_serve_policy.py).

Serving policies: ``greedy`` (ε=0 argmax), ``egreedy`` (ε =
``ServeSpec.eps``, the evaluation default 0.05), ``noisy`` (NoisyNet
parameter noise redrawn once per tick, ε=0 — the Rainbow exploration
head served live). See docs/serving.md.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.spec import ExperimentSpec, load_run_spec
from repro.core.policy import policy_step
from repro.envs.preprocess import ObsPipeline, push_frame
from repro.telemetry import NullTracer

__all__ = ["POLICIES", "ServeSpec", "PolicyServer", "LoadedPolicy",
           "load_policy", "make_server"]

POLICIES = ("greedy", "egreedy", "noisy")


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """The serving-side knobs (the experiment side lives in
    :class:`ExperimentSpec` — a server is that spec plus a carry)."""

    policy: str = "egreedy"   # one of POLICIES
    eps: float = 0.05         # exploration rate for policy="egreedy"
    max_batch: int = 1024     # microbatch ceiling per jitted call
    # Compile-size buckets a microbatch is padded up to; () derives
    # powers of two up to max_batch. Every bucket is one XLA program —
    # warm_start() compiles them all up front.
    buckets: Tuple[int, ...] = ()
    replica: int = 0          # population checkpoints: which replica
    seed: int = 0             # serve-side RNG stream (ε draws, noise)

    def validate(self) -> None:
        if self.policy not in POLICIES:
            raise ValueError(
                f"unknown serving policy {self.policy!r}; one of "
                f"{POLICIES}")
        if not 0.0 <= self.eps <= 1.0:
            raise ValueError(f"eps must be in [0, 1], got {self.eps}")
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if any(b < 1 for b in self.buckets):
            raise ValueError(f"buckets must be >= 1, got {self.buckets}")
        if self.replica < 0:
            raise ValueError(f"replica must be >= 0, got {self.replica}")

    def resolved_buckets(self) -> Tuple[int, ...]:
        """Ascending bucket sizes, always ending at ``max_batch``."""
        if self.buckets:
            return tuple(sorted({min(b, self.max_batch)
                                 for b in self.buckets} | {self.max_batch}))
        out, b = [], 1
        while b < self.max_batch:
            out.append(b)
            b *= 2
        return tuple(out + [self.max_batch])


class PolicyServer:
    """Microbatching action server over a fixed set of parameters.

    Protocol per stream: ``submit(stream_id, obs, first=...)`` enqueues
    the stream's *raw* current observation (``first=True`` on the first
    observation of an episode — the server zeroes that stream's stack
    history exactly like the sampler's autoreset);  ``flush()`` drains
    the queue in arrival order as microbatches of at most
    ``ServeSpec.max_batch`` rows, each padded to the smallest compiled
    bucket, and returns ``{stream_id: action}``.

    Per-stream RNG: stream s's t-th action draws from
    ``fold_in(fold_in(PRNGKey(seed), s), t)`` — a pure function of the
    serve seed, the stream id and the stream's own action counter, so a
    reconnecting client replays identically and no draw depends on batch
    composition. ``flush(keys=...)`` overrides the keys row-for-row
    (how the tests mirror ``evaluate``'s exact key chain).

    Shapes are static per (stream capacity, bucket): growing the stream
    table or hitting a new bucket compiles once. ``warm_start(n)``
    pre-sizes the table for n streams and compiles every bucket so no
    serve tick ever recompiles.
    """

    def __init__(self, params, q_forward: Callable, pipe: ObsPipeline,
                 frame_stack: int, n_actions: int,
                 serve: ServeSpec = ServeSpec(), tracer=None):
        serve.validate()
        # telemetry (repro.telemetry): each flush records a serve.flush
        # span with per-microbatch serve.queue_wait (oldest submit ->
        # flush start: the latency the batching window itself adds) and
        # serve.compute (the jitted call through device sync) children.
        # The default NullTracer keeps the request path zero-cost.
        self.tracer = tracer if tracer is not None else NullTracer()
        self.params = params
        self.pipe = pipe
        self.frame_stack = frame_stack
        self.n_actions = n_actions
        self.serve = serve
        self._buckets = serve.resolved_buckets()
        self._eps = np.float32(serve.eps if serve.policy == "egreedy"
                               else 0.0)
        self._noisy = serve.policy == "noisy"
        self._base = jax.random.PRNGKey(serve.seed)
        # fold a constant tag so per-stream action keys and per-tick
        # noise keys live on distinct streams of the same seed
        self._noise_base = jax.random.fold_in(self._base, 7)
        self._slots: Dict[Any, int] = {}       # stream id -> stack row
        self._steps: List[int] = []            # per-slot action counter
        self._stacks: Optional[jax.Array] = None   # (cap, *obs, K)
        self._cap = 0
        self._queue: List[Tuple[Any, int, np.ndarray, bool, float]] = []
        self._tick = 0
        self._latencies: List[float] = []

        def _serve(params, stacks, slots, obs, first, eps, keys, noise_key):
            rows = stacks[slots]               # gather (b, *obs, K)
            zero = first.reshape((-1,) + (1,) * (rows.ndim - 1))
            rows = jnp.where(zero, jnp.zeros_like(rows), rows)
            rows = push_frame(rows, obs)
            actions = policy_step(q_forward, params, rows, eps, keys,
                                  noise_key)
            # padded rows carry slot == cap (out of bounds): the scatter
            # drops them, so padding never touches real stream state
            stacks = stacks.at[slots].set(rows, mode="drop")
            return stacks, actions

        self._serve_fn = jax.jit(_serve)
        self._keys_fn = jax.jit(lambda sids, steps: jax.vmap(
            lambda s, t: jax.random.fold_in(
                jax.random.fold_in(self._base, s), t))(sids, steps))

    # -- stream table ------------------------------------------------------

    def _grow(self, cap: int) -> None:
        cap = max(cap, 1)
        if cap <= self._cap:
            return
        new = jnp.zeros((cap,) + self.pipe.shape + (self.frame_stack,),
                        self.pipe.dtype)
        if self._stacks is not None and self._cap > 0:
            new = new.at[: self._cap].set(self._stacks)
        self._stacks = new
        self._cap = cap

    def _slot(self, stream_id) -> int:
        slot = self._slots.get(stream_id)
        if slot is None:
            slot = len(self._slots)
            self._slots[stream_id] = slot
            self._steps.append(0)
            if slot >= self._cap:
                self._grow(max(2 * self._cap, 1))
        return slot

    @property
    def n_streams(self) -> int:
        return len(self._slots)

    # -- request path ------------------------------------------------------

    def submit(self, stream_id, obs, first: bool = False) -> None:
        """Enqueue one stream's raw observation for the next flush."""
        self._queue.append((stream_id, self._slot(stream_id),
                            np.asarray(obs), bool(first),
                            time.perf_counter()))

    def submit_many(self, stream_ids: Sequence, obs_batch,
                    first) -> None:
        """Vectorized submit: obs_batch (n, *obs), first (n,) bools."""
        obs_batch = np.asarray(obs_batch)
        first = np.asarray(first)
        now = time.perf_counter()
        for i, sid in enumerate(stream_ids):
            self._queue.append((sid, self._slot(sid), obs_batch[i],
                                bool(first[i]), now))

    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    def flush(self, keys: Optional[np.ndarray] = None) -> Dict[Any, int]:
        """Serve every queued request; returns ``{stream_id: action}``.

        ``keys`` (optional) overrides the per-stream RNG keys row-for-row
        in queue order — shape (len(queue), *key_shape)."""
        queue, self._queue = self._queue, []
        if keys is not None:
            keys = np.asarray(keys)
            assert keys.shape[0] == len(queue), (keys.shape, len(queue))
        noise_key = (jax.random.fold_in(self._noise_base, self._tick)
                     if self._noisy else None)
        out: Dict[Any, int] = {}
        mb = self.serve.max_batch
        with self.tracer.span("serve.flush", tick=self._tick,
                              requests=len(queue)):
            for lo in range(0, len(queue), mb):
                chunk = queue[lo: lo + mb]
                self._serve_chunk(chunk, keys, lo, noise_key, out)
            self.tracer.count("serve.actions", len(queue))
        self._tick += 1
        return out

    def _serve_chunk(self, chunk, keys, lo: int, noise_key,
                     out: Dict[Any, int]) -> None:
        """One microbatch: pad to a bucket, run the jitted program,
        scatter actions back. Telemetry: a ``serve.queue_wait`` span
        (oldest submit -> compute start: the latency the batching
        window itself added) then a ``serve.compute`` span fenced on
        the device sync."""
        B = len(chunk)
        bucket = self._bucket_for(B)
        if self.tracer.enabled and chunk:
            self.tracer.complete("serve.queue_wait",
                                 min(t0 for *_x, t0 in chunk),
                                 time.perf_counter(), batch=B)
        with self.tracer.span("serve.compute", batch=B, bucket=bucket):
            obs = np.zeros((bucket,) + self.pipe.shape, self.pipe.dtype)
            first = np.zeros((bucket,), bool)
            slots = np.full((bucket,), self._cap, np.int32)  # OOB = pad
            sids = np.zeros((bucket,), np.int32)
            steps = np.zeros((bucket,), np.int32)
            for i, (sid, slot, ob, fr, _t0) in enumerate(chunk):
                obs[i] = ob
                first[i] = fr
                slots[i] = slot
                # integer stream ids key the RNG directly (stable across
                # reconnects); non-integer ids fall back to the slot
                sids[i] = int(sid) if isinstance(sid, (int, np.integer)) \
                    else slot
                steps[i] = self._steps[slot]
            if keys is None:
                kchunk = self._keys_fn(jnp.asarray(sids),
                                       jnp.asarray(steps))
            else:
                kchunk = jnp.asarray(keys[lo: lo + B])
                if B < bucket:
                    pad = jnp.zeros((bucket - B,) + kchunk.shape[1:],
                                    kchunk.dtype)
                    kchunk = jnp.concatenate([kchunk, pad])
            self._stacks, actions = self._serve_fn(
                self.params, self._stacks, jnp.asarray(slots),
                jnp.asarray(obs), jnp.asarray(first), self._eps, kchunk,
                noise_key)
            acts = np.asarray(actions)        # device sync: batch served
        done_t = time.perf_counter()
        for i, (sid, slot, _ob, _fr, t0) in enumerate(chunk):
            out[sid] = int(acts[i])
            self._steps[slot] += 1
            self._latencies.append(done_t - t0)

    # -- operations --------------------------------------------------------

    def warm_start(self, n_streams: int = 0) -> int:
        """Pre-size the stream table for ``n_streams`` and compile every
        bucket shape (with and without padding state effects), so no
        serve tick ever pays an XLA compile. Returns the number of
        bucket programs compiled."""
        if n_streams:
            cap = 1
            while cap < n_streams:
                cap *= 2
            self._grow(cap)
        self._grow(1)
        noise_key = (jax.random.fold_in(self._noise_base, -1)
                     if self._noisy else None)
        k0 = np.asarray(self._base)
        for b in self._buckets:
            obs = jnp.zeros((b,) + self.pipe.shape, self.pipe.dtype)
            slots = jnp.full((b,), self._cap, jnp.int32)   # all padded:
            first = jnp.zeros((b,), bool)                  # no state write
            kz = jnp.zeros((b,) + k0.shape, k0.dtype)
            self._keys_fn(jnp.zeros((b,), jnp.int32),
                          jnp.zeros((b,), jnp.int32))
            self._stacks, _ = self._serve_fn(
                self.params, self._stacks, slots, obs, first, self._eps,
                kz, noise_key)
        return len(self._buckets)

    def drain_latencies(self) -> List[float]:
        """Per-request submit->action latencies (seconds) accumulated
        since the last drain."""
        out, self._latencies = self._latencies, []
        return out


# ---------------------------------------------------------------------------
# Loading: spec.json + the newest restorable checkpoint -> serving pieces
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LoadedPolicy:
    """Everything serving needs, extracted from one checkpoint dir."""

    spec: ExperimentSpec
    params: Any                   # single-replica policy params
    q_forward: Callable           # (params, obs[, noise_key]) -> (B, A)
    pipe: ObsPipeline
    frame_stack: int
    n_actions: int
    step: int                     # the checkpoint step being served
    skipped: List[str]            # corrupt checkpoints passed over


def load_policy(ckpt_dir: str, spec: Optional[ExperimentSpec] = None,
                step: Optional[int] = None,
                replica: int = 0) -> LoadedPolicy:
    """Load serving state from a training checkpoint directory.

    The spec comes from the dir's ``spec.json`` unless given explicitly;
    the carry comes from ``step`` or the newest *restorable* step (a
    torn checkpoint is skipped with its path recorded in
    ``LoadedPolicy.skipped`` — see ``checkpoint.restore_latest``).
    Population checkpoints serve one replica's params (``replica``)."""
    from repro.api.trainers import _Components, build_trainer
    from repro.checkpoint import restore_checkpoint, restore_latest

    spec = spec or load_run_spec(ckpt_dir)
    if spec is None:
        raise ValueError(
            f"{ckpt_dir} holds no spec.json — pass the run's "
            "ExperimentSpec explicitly (rl_train --print-spec emits it)")
    if spec.variant.recurrent:
        raise ValueError(
            f"net {spec.net!r} is recurrent: a served action depends on "
            "the stream's LSTM state, and the policy server is stateless "
            "per request; serving it is not supported")
    trainer = build_trainer(spec)
    template = trainer.init_template()
    skipped: List[str] = []
    if step is None:
        step, carry, skipped = restore_latest(ckpt_dir, template)
        if carry is None:
            detail = ":\n  " + "\n  ".join(skipped) if skipped else ""
            raise ValueError(
                f"no restorable checkpoint in {ckpt_dir}{detail}")
    else:
        carry = restore_checkpoint(ckpt_dir, step, template)
    params = carry.params
    if trainer.replicas > 1 or spec.mode == "population":
        if not 0 <= replica < trainer.replicas:
            raise ValueError(
                f"replica {replica} out of range for a "
                f"{trainer.replicas}-replica checkpoint")
        params = jax.tree.map(lambda x: x[replica], params)
    c = _Components(spec)
    return LoadedPolicy(spec, params, c.qf, c.obs, c.dcfg.frame_stack,
                        c.env.n_actions, step, skipped)


def make_server(loaded: LoadedPolicy, serve: ServeSpec = ServeSpec(),
                tracer=None) -> PolicyServer:
    """A :class:`PolicyServer` over a loaded checkpoint (the spec + the
    carry — nothing else crosses the training/serving boundary).
    ``tracer`` (repro.telemetry) records queue-wait vs compute spans
    per flush; None = NullTracer, zero-cost."""
    if serve.policy == "noisy" and not loaded.spec.variant.noisy:
        raise ValueError(
            f"serving policy 'noisy' needs a NoisyNet checkpoint; "
            f"variant {loaded.spec.variant.name!r} has no noise "
            "parameters — use 'greedy' or 'egreedy'")
    return PolicyServer(loaded.params, loaded.q_forward, loaded.pipe,
                        loaded.frame_stack, loaded.n_actions, serve,
                        tracer=tracer)
