"""Synchronized Execution (§4 of the paper).

W sampler streams step in lock-step; their observations are aggregated
into ONE batched Q-inference per round (Figure 3b) instead of W separate
device transactions (Figure 3a). In this JAX formulation the W streams
are a vmapped batch dimension and the barrier is the dataflow itself; on
the production mesh the (W, ...) inference batch is sharded over the
data/pod axes — the multi-chip generalization of "one shared minibatch".

``sync_round`` is one synchronized step of all W envs: render -> ONE
batched Q call -> ε-greedy -> vmapped env step. Its scan (see
concurrent.py) is the sampler loop of Algorithm 1.
"""

from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.config import DQNConfig
from repro.envs.games import EnvSpec, step_autoreset
from repro.envs.preprocess import (ObsPipeline, as_obs, init_obs_stack,
                                   obs_batch, push_frame, reset_stack_where)
from repro.core.policy import egreedy_stream, policy_step, stream_keys
from repro.models.r2d2 import Core, RecurrentQ, reset_core, zero_core

# ``obs`` arguments below accept a plain int (legacy pixel frame size)
# or an ObsPipeline (pixels | vector) — see envs/preprocess.py.
Obs = Union[int, ObsPipeline]

# Named scopes of one synchronized round. They change only the op_name
# metadata of the compiled program, never its arithmetic; inside the
# C-cycle they nest under ``concurrent.ACT_SCOPE`` (whose module cannot
# be imported from here), and a profiler trace reads them back.
POLICY_SCOPE = "policy"
"""The batched Q forward and the ε-greedy or noisy action draw."""
ENV_SCOPE = "env"
"""The vmapped env dynamics (``step_autoreset``)."""
RENDER_SCOPE = "render"
"""The observation render of the stepped envs (``obs_batch``)."""
LSTM_SCOPE = "lstm"
"""A recurrent net's recurrence: the actor's LSTM step inside ``act``,
the learner's unrolls inside ``learn`` (``core/dqn.py``)."""


class SamplerState(NamedTuple):
    env_states: Dict[str, jax.Array]   # vmapped env states (leading W)
    stack: jax.Array                   # (W, *obs, K) — current obs stack
    key: jax.Array
    # a recurrent net's per-stream input to the next step (LSTM state,
    # previous action and reward, zero at episode starts); None for a
    # feed-forward net
    core: Optional[Core] = None


def sampler_init(spec: EnvSpec, cfg: DQNConfig, key: jax.Array,
                 obs: Obs = 84, core_cfg=None) -> SamplerState:
    """``core_cfg``: a recurrent net's configuration (its zero ``Core``
    per stream), or None."""
    pipe = as_obs(obs)
    kreset, kstate = jax.random.split(key)
    env_states = jax.vmap(spec.reset)(jax.random.split(kreset, cfg.n_envs))
    stack = init_obs_stack(cfg.n_envs, pipe, cfg.frame_stack)
    frame = obs_batch(pipe, spec, env_states)
    stack = push_frame(stack, frame)
    core = None if core_cfg is None else zero_core(cfg.n_envs, core_cfg)
    return SamplerState(env_states, stack, kstate, core)


def sync_round(spec: EnvSpec, q_forward: Callable, params,
               s: SamplerState, eps: jax.Array,
               obs: Obs = 84) -> Tuple[SamplerState, Dict[str, jax.Array]]:
    """One synchronized W-env step. Returns (state', transitions) where
    transitions have leading dim W. The single q_forward call is the
    paper's one-transaction-per-round property."""
    pipe = as_obs(obs)
    key, kact, kstep = jax.random.split(s.key, 3)
    cur = s.stack                                           # (W, *obs, K)
    W = cur.shape[0]
    if s.core is None:
        # ONE batched Q call + per-stream ε draws — the same stateless
        # primitive the serving layer batches client streams through
        # (core/policy.py), so served actions match these bitwise.
        with jax.named_scope(POLICY_SCOPE):
            actions = policy_step(q_forward, params, cur, eps,
                                  stream_keys(kact, W))
    else:
        # a recurrent net (models/r2d2.RecurrentQ): the same one batched
        # step, its recurrence under a scope of its own
        rq, core = q_forward, s.core
        with jax.named_scope(POLICY_SCOPE):
            gates_x = rq.embed(params, cur, core.prev_action,
                               core.prev_reward)
        with jax.named_scope(LSTM_SCOPE):
            h, c = rq.step(params, gates_x, core.h, core.c)
        with jax.named_scope(POLICY_SCOPE):
            q = rq.head(params, h)
            eps = jnp.broadcast_to(jnp.asarray(eps, jnp.float32), (W,))
            actions = jax.vmap(egreedy_stream)(q, eps, stream_keys(kact, W))
    with jax.named_scope(ENV_SCOPE):
        env_states, rewards, dones = jax.vmap(
            lambda st, a, k: step_autoreset(spec, st, a, k)
        )(s.env_states, actions, jax.random.split(kstep, W))
    with jax.named_scope(RENDER_SCOPE):
        frame = obs_batch(pipe, spec, env_states)
    next_obs = push_frame(s.stack, frame)                   # pre-reset view
    new_stack = push_frame(reset_stack_where(s.stack, dones), frame)
    transitions = {"obs": cur, "action": actions, "reward": rewards,
                   "next_obs": next_obs, "done": dones}
    if s.core is None:
        return SamplerState(env_states, new_stack, key), transitions
    core = reset_core(Core(h, c, actions, rewards), dones)
    return SamplerState(env_states, new_stack, key, core), transitions


def nstep_aggregate(staged: Dict[str, jax.Array], n: int,
                    discount: float) -> Dict[str, jax.Array]:
    """Collapse the staged (rounds, W, ...) 1-step transitions into
    n-step transitions along the rounds axis (per stream).

    For each start round t (0 <= t <= rounds-n):
      reward   <- Σ_{k<n} γᵏ r[t+k] · Π_{j<k}(1 - done[t+j])
                  (rewards stop accumulating after the first terminal;
                  the terminal step's own reward is included);
      next_obs <- next_obs[t+n-1]  (only consumed when no terminal fell
                  inside the window — ``done`` zeroes the bootstrap
                  otherwise, so the post-reset frames never leak in);
      done     <- any terminal within the window.

    The matching loss bootstraps with γⁿ (see ``dqn.q_loss_variant``).
    The last n-1 rounds of a cycle lack their future context and are
    dropped — a deterministic truncation of (n-1)·W transitions per
    cycle, mirroring the staging-buffer semantics (nothing crosses the
    sync point half-accumulated).
    """
    if n <= 1:
        return staged
    rounds = staged["reward"].shape[0]
    assert rounds >= n, (rounds, n)
    R = rounds - n + 1
    live = jnp.ones_like(staged["reward"][:R])          # Π (1 - done) so far
    reward = jnp.zeros_like(staged["reward"][:R])
    done = jnp.zeros_like(staged["done"][:R])
    for k in range(n):
        reward = reward + (discount ** k) * live * staged["reward"][k:k + R]
        done = done | staged["done"][k:k + R]
        live = live * (1.0 - staged["done"][k:k + R].astype(live.dtype))
    return {
        "obs": staged["obs"][:R],
        "action": staged["action"][:R],
        "reward": reward,
        "next_obs": staged["next_obs"][n - 1:],
        "done": done,
    }


def evaluate(spec: EnvSpec, q_forward: Callable, params, key: jax.Array,
             cfg: DQNConfig, n_episodes: int = 30, obs: Obs = 84,
             max_steps: int = 1000) -> jax.Array:
    """ε=0.05 greedy evaluation (paper §5.2): mean episode return over
    n_episodes parallel evaluation streams.

    Only streams whose episode *finished* within ``max_steps`` enter the
    mean — a stream cut off mid-episode holds a partial return, and
    averaging it as if complete biases the score low on long envs
    (pong/breakout run to 500 steps). When no stream finishes at all the
    partial-return mean is returned as a fallback (callers should size
    ``max_steps`` from ``spec.max_steps`` so this never triggers)."""
    eval_cfg = cfg
    pipe = as_obs(obs)
    kinit, krun = jax.random.split(key)
    env_states = jax.vmap(spec.reset)(jax.random.split(kinit, n_episodes))
    stack = init_obs_stack(n_episodes, pipe, cfg.frame_stack)
    stack = push_frame(stack, obs_batch(pipe, spec, env_states))
    # a recurrent net starts each stream at zero
    core = (zero_core(n_episodes, q_forward.cfg)
            if isinstance(q_forward, RecurrentQ) else None)
    s = SamplerState(env_states, stack, krun, core)

    def body(carry, _):
        s, ret, live = carry
        s2, tr = sync_round(spec, q_forward, params, s,
                            jnp.float32(eval_cfg.eval_eps), pipe)
        ret = ret + tr["reward"] * live
        live = live * (1.0 - tr["done"].astype(jnp.float32))
        return (s2, ret, live), None

    zeros = jnp.zeros((n_episodes,), jnp.float32)
    (_, returns, live), _ = jax.lax.scan(body, (s, zeros, zeros + 1.0), None,
                                         length=max_steps)
    finished = 1.0 - live                    # streams whose episode ended
    n_finished = jnp.sum(finished)
    finished_mean = jnp.sum(returns * finished) / jnp.maximum(n_finished, 1.0)
    return jnp.where(n_finished > 0, finished_mean, jnp.mean(returns))
