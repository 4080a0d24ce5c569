"""Concurrent Training (§3) — the C-cycle.

Algorithm 1 as a single jitted super-step covering C timesteps:

  1. θ⁻ ← θ  (the synchronization point);
  2. sampler: C/W synchronized rounds acting from **θ⁻** (Concurrent
     Training's key substitution) — experiences accumulate in the scan's
     stacked output, the staging buffer;
  3. trainer: C/F minibatch updates on θ, sampling only from the replay
     snapshot 𝒟 taken at the cycle boundary;
  4. flush: staged experiences enter 𝒟.

Steps 2 and 3 have *no dataflow dependency on each other* — θ⁻ and the
𝒟 snapshot are both fixed at the cycle boundary. That is exactly the
property the paper exploits with threads; here it lets XLA schedule the
two computations concurrently, and on a disaggregated mesh they run on
disjoint device sets (see core/actor_learner.py). Because 𝒟 is frozen
during the training burst and the flush is ordered, results are
deterministic — bit-equal to the sequential oracle in
tests/test_concurrent.py.

The off-policy variant family (``cfg.variant``) preserves that
structure. Under PER the trainer samples from the snapshot's sum-tree
(built once at the boundary) and *stages* its priority updates exactly
like the sampler stages experiences; both flush at the next sync point
(priorities first, then the staged transitions, whose slots enter at
max priority). n-step aggregation happens on the staging buffer before
the flush. NoisyNet exploration replaces the ε-greedy schedule (ε=0)
with parameter noise resampled once per cycle for the actor and once
per update for the trainer — every key is folded out of the carry's
replica seed and step counter (``replica_key``), so the cycle stays a
pure function of its carry and a vmapped population of carries with
distinct seeds (core/population.py) runs decorrelated replicas. C51
losses ride the same PER staging with cross-entropy in place of |td|.
Every variant therefore keeps the paper's snapshot-𝒟 determinism
guarantee — locked in by tests/test_variants.py. docs/architecture.md
has the cycle timeline. Launchers construct this cycle through the
``concurrent`` / ``population`` entries of the ``repro.api`` trainer
registry (docs/experiment_api.md) rather than calling
``make_concurrent_cycle`` directly.
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import DQNConfig
from repro.core.dqn import make_update_fn
from repro.core.replay import (ReplayState, per_flush_priorities, per_sample,
                               per_stage_priorities, per_tree,
                               replay_add_batch, replay_sample)
from repro.core.synchronized import (ENV_SCOPE, POLICY_SCOPE, RENDER_SCOPE,
                                     Obs, SamplerState, nstep_aggregate,
                                     sync_round)
from repro.envs.games import EnvSpec
from repro.optim.schedule import linear_epsilon


class TrainerCarry(NamedTuple):
    params: Dict
    opt_state: Dict
    replay: ReplayState
    sampler: SamplerState
    step: jax.Array          # global env-step counter t
    # Replica seed: every RNG stream the cycle derives (trainer sampling,
    # NoisyNet draws) folds this in, so a population of carries vmapped
    # over distinct seeds runs decorrelated replicas while each replica
    # stays bitwise-reproducible as a standalone run. Scalar int32; the
    # default keeps pre-population call sites working (replica 0).
    seed: jax.Array = 0


def replica_key(tag: int, seed: jax.Array, step: jax.Array) -> jax.Array:
    """The cycle RNG derivation: a stream tag (a small constant per use
    site), the replica seed, and the step counter — all folded into one
    key, so every stream is a pure function of (tag, seed, step)."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(tag), seed), step)


# The evaluation RNG stream tag, shared by population.eval_keys and the
# repro.api trainers so a population eval and a single-replica eval with
# the same (seed, cycle index) draw identical keys (the concurrent ==
# 1-seed-population bitwise guarantee depends on this single constant).
EVAL_STREAM_TAG = 29

# Named scopes of the C-cycle's phases. They change only the op_name
# metadata of the compiled program, never its arithmetic; a profiler
# trace of the cycle attributes each device op to the innermost one.
ACT_SCOPE = "act"
"""The sampler scan: all C/W synchronized rounds, acting from θ⁻."""
PER_TREE_SCOPE = "per_tree"
"""The sum-tree rebuild over the replay snapshot (PER variants only)."""
LEARN_SCOPE = "learn"
"""The trainer scan: all C/F minibatch updates."""
SAMPLE_SCOPE = "sample"
"""One update's replay draw; under PER the tree descent and weights too."""
UPDATE_SCOPE = "update"
"""One update's forward, backward and optimizer step, and its priority
staging under PER."""
FLUSH_SCOPE = "flush"
"""The sync-point flush: staged priorities, n-step aggregation and the
staged transitions into 𝒟."""
CYCLE_SCOPES = (
    ACT_SCOPE, f"{ACT_SCOPE}/{POLICY_SCOPE}", f"{ACT_SCOPE}/{ENV_SCOPE}",
    f"{ACT_SCOPE}/{RENDER_SCOPE}", PER_TREE_SCOPE, LEARN_SCOPE,
    f"{LEARN_SCOPE}/{SAMPLE_SCOPE}", f"{LEARN_SCOPE}/{UPDATE_SCOPE}",
    FLUSH_SCOPE)
"""Every scope path of the cycle, nested scopes joined by ``/``."""


def make_concurrent_cycle(spec: EnvSpec, q_forward: Callable, opt,
                          cfg: DQNConfig, obs: Obs = 84,
                          cycle_steps: int = 0,
                          kernel_backend: Optional[str] = None,
                          q_logits: Optional[Callable] = None) -> Callable:
    """Build the jitted C-cycle. ``cycle_steps`` overrides C for tests;
    ``kernel_backend`` is the kernel request for the PER segment tree
    and the C51 projection op; ``q_logits`` is the (B, A, K) categorical
    head required by distributional variants. NoisyNet variants expect
    ``q_forward``/``q_logits`` to accept a trailing noise key.
    Returns cycle(carry) -> (carry', metrics)."""
    C = cycle_steps or cfg.target_update_period
    W = cfg.n_envs
    assert C % W == 0, (C, W)
    rounds = C // W
    updates = max(C // cfg.train_period, 1)
    variant = cfg.variant
    variant.validate()
    assert rounds >= variant.n_step, (rounds, variant.n_step)
    update_fn = make_update_fn(q_forward, opt, cfg, variant,
                               q_logits=q_logits,
                               kernel_backend=kernel_backend)
    eps_fn = linear_epsilon(cfg.eps_start, cfg.eps_end, cfg.eps_anneal_steps)

    def cycle(carry: TrainerCarry) -> Tuple[TrainerCarry, Dict[str, jax.Array]]:
        # --- synchronization point: θ⁻ ← θ; snapshot 𝒟 ---
        target_params = carry.params
        replay_snapshot = carry.replay

        # --- sampler: C/W synchronized rounds from θ⁻ ------------------
        # NoisyNet: ε-greedy is disabled; exploration is the cycle's
        # parameter-noise draw, frozen with θ⁻ for all C/W rounds (the
        # key is a pure function of carry.step — determinism preserved).
        if variant.noisy:
            k_act = replica_key(23, carry.seed, carry.step)
            qf_act = lambda p, o: q_forward(p, o, k_act)  # noqa: E731
        else:
            qf_act = q_forward

        def sample_body(s, i):
            eps = (jnp.float32(0.0) if variant.noisy
                   else eps_fn(carry.step + i * W))
            s, tr = sync_round(spec, qf_act, target_params, s, eps, obs)
            return s, tr

        with jax.named_scope(ACT_SCOPE):
            sampler, staged = jax.lax.scan(
                sample_body, carry.sampler, jnp.arange(rounds))
        # staging buffer: (rounds, W, ...) stacked transitions

        # --- trainer: C/F updates on θ from the frozen snapshot --------
        ktrain = replica_key(17, carry.seed, carry.step)

        def split_update_key(k):
            """Sampling key + (noisy only) per-update noise key. Non-
            noisy variants keep the seed-era single-key stream."""
            if variant.noisy:
                ks, kn = jax.random.split(k)
                return ks, kn
            return k, None

        if variant.prioritized:
            # The snapshot's sampling distribution: one tree build at the
            # boundary, frozen for the whole training burst.
            with jax.named_scope(PER_TREE_SCOPE):
                tree = per_tree(replay_snapshot)
                beta = jnp.minimum(
                    1.0, variant.per_beta0 + (1.0 - variant.per_beta0)
                    * carry.step.astype(jnp.float32)
                    / variant.per_beta_anneal_steps)

            def train_body(tc, k):
                params, opt_state, pending = tc
                ks, kn = split_update_key(k)
                with jax.named_scope(SAMPLE_SCOPE):
                    batch = per_sample(replay_snapshot, ks,
                                       cfg.minibatch_size, beta, tree=tree,
                                       backend=kernel_backend)
                with jax.named_scope(UPDATE_SCOPE):
                    params, opt_state, loss, td_abs = update_fn(
                        params, target_params, opt_state, batch, kn)
                    pending = per_stage_priorities(
                        pending, batch["index"], td_abs, variant.per_alpha,
                        variant.per_eps)
                return (params, opt_state, pending), loss

            with jax.named_scope(LEARN_SCOPE):
                pending0 = jnp.zeros_like(replay_snapshot["priority"])
                (params, opt_state, pending), losses = jax.lax.scan(
                    train_body, (carry.params, carry.opt_state, pending0),
                    jax.random.split(ktrain, updates))
        else:
            def train_body(tc, k):
                params, opt_state = tc
                ks, kn = split_update_key(k)
                with jax.named_scope(SAMPLE_SCOPE):
                    batch = replay_sample(replay_snapshot, ks,
                                          cfg.minibatch_size)
                with jax.named_scope(UPDATE_SCOPE):
                    params, opt_state, loss, _ = update_fn(
                        params, target_params, opt_state, batch, kn)
                return (params, opt_state), loss

            with jax.named_scope(LEARN_SCOPE):
                (params, opt_state), losses = jax.lax.scan(
                    train_body, (carry.params, carry.opt_state),
                    jax.random.split(ktrain, updates))

        # --- flush at the sync point: staged priorities, then staged ---
        # experiences (new slots enter at the updated max priority) -----
        with jax.named_scope(FLUSH_SCOPE):
            replay = carry.replay
            if variant.prioritized:
                replay = per_flush_priorities(replay, pending)
            agg = nstep_aggregate(staged, variant.n_step, cfg.discount)
            flat = {k: v.reshape((-1,) + v.shape[2:])
                    for k, v in agg.items()}
            replay = replay_add_batch(replay, flat)

        metrics = {
            "loss": jnp.mean(losses),
            "reward": jnp.sum(staged["reward"]),
            "episodes": jnp.sum(staged["done"]),
            "eps": (jnp.float32(0.0) if variant.noisy
                    else eps_fn(carry.step)),
        }
        new = TrainerCarry(params, opt_state, replay, sampler,
                           carry.step + C, carry.seed)
        return new, metrics

    return cycle


def prepopulate(spec: EnvSpec, q_forward: Callable, cfg: DQNConfig,
                replay: ReplayState, sampler: SamplerState,
                n: int, obs: Obs = 84):
    """Fill 𝒟 with at least n uniform-random transitions (the paper's
    N=50 000). On a prioritized replay the slots enter at max priority
    (1.0 before any TD error has been observed).

    Rounds are rounded *up*: ``n // W`` would truncate whenever W does
    not divide n, and n-step aggregation drops the last (n_step-1)·W
    staged transitions, so the round count compensates for both —
    (rounds - n_step + 1)·W = ceil(n/W)·W >= n transitions land in 𝒟."""
    W = cfg.n_envs
    rounds = max(-(-n // W), 1) + (cfg.variant.n_step - 1)

    # ε=1 ⇒ uniform-random actions; Q values are ignored by egreedy, so a
    # zero-Q function avoids touching (possibly None) params entirely.
    zero_q = lambda params, obs: jnp.zeros((obs.shape[0], spec.n_actions))

    def body(s, _):
        s, tr = sync_round(spec, zero_q, None, s, jnp.float32(1.0), obs)
        return s, tr

    sampler, staged = jax.lax.scan(body, sampler, None, length=rounds)
    agg = nstep_aggregate(staged, cfg.variant.n_step, cfg.discount)
    flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in agg.items()}
    return replay_add_batch(replay, flat), sampler
