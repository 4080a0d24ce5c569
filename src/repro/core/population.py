"""Population training layer — vmapped multi-seed replica fleets.

Stooke & Abbeel (*Accelerated Methods for Deep RL*) observe that a
single DQN run leaves server-grade accelerators mostly idle, and that
stacking many learners/seeds into one device-saturating program is the
way to amortize the hardware; CuLE (Dalton et al.) shows vectorized
environments are what unlock that batch dimension. Our envs are pure
JAX and already vmap (envs/games.py), and the concurrent C-cycle is a
pure function of its carry whose every RNG stream folds in
``carry.seed`` (core/concurrent.replica_key) — so the *entire* cycle
vmaps over a population axis P with no further changes.

A population is P independent ``TrainerCarry`` replicas stacked on a
new leading axis (P = seeds, or seeds × games when the launcher loops
games — different games have different state pytrees and action counts,
so the game axis is a Python-level product, not a vmap axis). The
guarantees, locked in by tests/test_population.py:

* replica r of a vmapped population run is **bitwise identical** to the
  standalone single-seed run with ``seed = seeds[r]`` — populations are
  a pure batching transform, not a different algorithm;
* the full population carry checkpoints and resumes bitwise through
  ``repro.checkpoint`` (the carry is the whole training state: params,
  optimizer, replay, sampler streams, step and seed).

Launchers and benchmarks construct this layer through the
``population`` entry of the ``repro.api`` trainer registry
(``build_trainer(spec)``; docs/experiment_api.md) — the functions below
are the mechanism, the spec is the interface.

When several devices are visible, the replica axis is sharded over a
1-D ``replica`` mesh with ``jax.shard_map`` — each
device advances P/D replicas with zero cross-device communication (the
replicas are independent by construction, so the program partitions
embarrassingly).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.config import DQNConfig
from repro.core.concurrent import (EVAL_STREAM_TAG, TrainerCarry,
                                   make_concurrent_cycle, prepopulate,
                                   prepopulate_sequences, replica_key)
from repro.core.replay import (replay_init, sequence_layout,
                               sequence_replay_init)
from repro.core.synchronized import Obs, evaluate, sampler_init
from repro.envs.games import EnvSpec
from repro.envs.preprocess import as_obs

__all__ = [
    "seed_array", "packed_seeds", "make_replica_init", "population_init",
    "make_population_cycle", "population_evaluate", "eval_keys",
    "replica_mesh",
]


def seed_array(base_seed: int, n: int) -> jax.Array:
    """The n consecutive replica seeds [base, base + n)."""
    return jnp.int32(base_seed) + jnp.arange(n, dtype=jnp.int32)


def packed_seeds(seeds: Sequence[int]) -> jax.Array:
    """Explicit (possibly non-contiguous) replica seeds — the sweep
    packer's entry onto the replica axis. A packed fleet trains several
    sweep runs that differ only in seed as one vmapped program, so the
    seed list is arbitrary rather than the contiguous ``seed_array``
    range; every other population guarantee (replica r bitwise-equals
    the standalone run with ``seeds[r]``) carries over unchanged because
    ``population_init`` and the cycle only ever consume the per-replica
    seed value. Duplicates are rejected: two replicas sharing a seed
    would train bitwise-identical twins, which a sweep manifest must
    surface as a bug, not silently compute twice."""
    vals = [int(s) for s in seeds]
    if not vals:
        raise ValueError("packed_seeds needs at least one replica seed")
    dupes = sorted({s for s in vals if vals.count(s) > 1})
    if dupes:
        raise ValueError(
            f"duplicate replica seeds {dupes} in packed fleet — each "
            "packed run must carry a distinct seed")
    return jnp.asarray(vals, jnp.int32)


def make_replica_init(spec: EnvSpec, q_init_fn: Callable,
                      q_forward: Callable, opt, cfg: DQNConfig,
                      obs: Obs = 84) -> Callable:
    """Build ``init_one(seed) -> TrainerCarry``: params, optimizer state,
    replay (prepopulated with ``cfg.prepopulate`` uniform-random
    transitions) and sampler streams, all derived from ``PRNGKey(seed)``.

    ``q_init_fn(key) -> params``. The same function defines both the
    standalone single-seed init and (vmapped by ``population_init``) the
    population init, so the two cannot drift.

    The seed key is split once and each consumer gets its own half —
    network init and the sampler's reset streams must never draw the
    same bits (the PR-6 RNG audit: the seed-era code passed ``key`` to
    both, aliasing the init randomness with episode randomness)."""
    pipe = as_obs(obs)

    def init_one(seed: jax.Array) -> TrainerCarry:
        seed = jnp.asarray(seed, jnp.int32)
        kinit, ksampler = jax.random.split(jax.random.PRNGKey(seed))
        params = q_init_fn(kinit)
        if cfg.variant.recurrent:
            # q_forward is the recurrent net (models/r2d2.RecurrentQ)
            replay = sequence_replay_init(sequence_layout(cfg), pipe.shape,
                                          q_forward.cfg.lstm)
            sampler = sampler_init(spec, cfg, ksampler, pipe,
                                   core_cfg=q_forward.cfg)
            replay, sampler = prepopulate_sequences(
                spec, q_forward, params, cfg, replay, sampler,
                cfg.prepopulate, pipe)
        else:
            replay = replay_init(
                cfg.replay_capacity, pipe.shape + (cfg.frame_stack,),
                obs_dtype=pipe.dtype,
                prioritized=cfg.variant.prioritized)
            sampler = sampler_init(spec, cfg, ksampler, pipe)
            replay, sampler = prepopulate(spec, q_forward, cfg, replay,
                                          sampler, cfg.prepopulate, pipe)
        target = params if cfg.variant.recurrent else None
        return TrainerCarry(params, opt.init(params), replay, sampler,
                            jnp.int32(0), seed, target)

    return init_one


def population_init(init_one: Callable, seeds) -> TrainerCarry:
    """Stack P replicas: vmap the single-replica init over the seed
    array. Every leaf of the returned carry has leading dim P."""
    return jax.vmap(init_one)(jnp.asarray(seeds, jnp.int32))


def replica_mesh(n_replicas: int, devices: Optional[Sequence] = None):
    """A 1-D ``replica`` mesh over the largest visible device count that
    divides P, or None when only one device would participate (vmap
    alone is already optimal there)."""
    n_dev = len(devices) if devices is not None else jax.device_count()
    d = min(n_dev, n_replicas)
    while d > 1 and n_replicas % d != 0:
        d -= 1
    if d <= 1:
        return None
    return jax.make_mesh(
        (d,), ("replica",), axis_types=(jax.sharding.AxisType.Auto,),
        devices=None if devices is None else list(devices)[:d])


def make_population_cycle(spec: EnvSpec, q_forward: Callable, opt,
                          cfg: DQNConfig, obs: Obs = 84,
                          cycle_steps: int = 0,
                          kernel_backend: Optional[str] = None,
                          q_logits: Optional[Callable] = None,
                          mesh=None) -> Callable:
    """The population super-step: the single-replica concurrent cycle,
    vmapped over the leading replica axis. With a ``replica`` mesh the
    vmapped cycle is additionally shard_mapped so each device advances
    its P/D replicas locally (no collectives — replicas are
    independent). Returns cycle(carry) -> (carry', metrics) where every
    metric has leading dim P."""
    cycle = make_concurrent_cycle(spec, q_forward, opt, cfg,
                                  obs=obs,
                                  cycle_steps=cycle_steps,
                                  kernel_backend=kernel_backend,
                                  q_logits=q_logits)
    vcycle = jax.vmap(cycle)
    if mesh is None:
        return vcycle
    pspec = jax.sharding.PartitionSpec("replica")
    return jax.shard_map(vcycle, mesh=mesh, in_specs=pspec,
                         out_specs=pspec, check_vma=False)


def eval_keys(seeds: jax.Array, step) -> jax.Array:
    """Per-replica evaluation keys: a dedicated stream tag folded with
    each replica's seed and the eval step counter, so eval RNG never
    collides with the training streams and resumes reproducibly."""
    return jax.vmap(
        lambda s: replica_key(EVAL_STREAM_TAG, s, jnp.asarray(step)))(
        jnp.asarray(seeds, jnp.int32))


def population_evaluate(spec: EnvSpec, q_forward: Callable, params,
                        keys: jax.Array, cfg: DQNConfig,
                        n_episodes: int = 30, obs: Obs = 84,
                        max_steps: Optional[int] = None) -> jax.Array:
    """Per-replica ε=0.05 evaluation: (P,) finished-episode-aware mean
    returns. ``max_steps`` defaults to the env's own episode bound so
    truncation (and the partial-return fallback) cannot bias scores."""
    if max_steps is None:
        max_steps = spec.max_steps + 2
    return jax.vmap(
        lambda p, k: evaluate(spec, q_forward, p, k, cfg,
                              n_episodes=n_episodes, obs=obs,
                              max_steps=max_steps))(params, keys)
