"""The `Trainer` protocol and the execution-mode registry.

Every execution mode in the paper's framework — the sequential
baseline, Synchronized Execution, Concurrent Training, and the PR-4
population layer — is exposed through ONE protocol:

    trainer = build_trainer(spec)          # the single construction path
    carry   = trainer.init_carry()         # params/opt/replay/samplers
    carry, metrics = trainer.cycle(carry)  # one jitted super-step
    returns = trainer.eval(carry, trainer.eval_key(i))

Modes register in ``TRAINERS`` the same way kernel backends register
per-op in ``kernels/backend.py``: a decorator populates a dict keyed by
mode name, ``build_trainer`` dispatches on ``spec.mode``, and an
unknown mode fails with the registered alternatives listed. Adding the
fifth mode means writing one adapter class and one
``@register_trainer("<mode>")`` line — launchers, benchmarks and tests
pick it up through the registry.

Uniform shape contract (what makes launchers mode-agnostic): *every*
trainer presents a leading replica axis of size ``trainer.replicas`` on
its metrics, eval returns and ``steps(carry)`` — the population trainer
has P = ``spec.seeds`` replicas, the single-carry modes have P = 1 and
expand dims at the jit boundary (free at runtime). The carry itself is
opaque to callers: checkpoint it with ``repro.checkpoint`` against
``trainer.init_template()``, never reach into it.

Mode semantics (the paper's Table 1 grid):

==============  ============================================================
baseline        Standard DQN control flow (Figure 1a): act from the current
                θ, one blocking update every F steps, experiences enter 𝒟
                immediately. Inside one jitted program the W streams are
                necessarily batched — the *transaction-level* cost of
                unsynchronized per-stream inference is measured by the host
                runner (benchmarks/table1_speed.py), which this mode
                mirrors in dataflow.
synchronized    Synchronized Execution without Concurrent Training: the
                same sequential update structure, with the W >= 2 streams
                explicitly aggregated into one batched Q call per round
                (sync_round). Numerically identical to ``baseline`` at
                equal W — the difference is the device-transaction count,
                again measured on the host runner.
concurrent      Algorithm 1: the jitted C-cycle (θ⁻ acting, snapshot-𝒟
                training burst, boundary flush) for a single replica.
population      The concurrent cycle vmapped over ``spec.seeds`` replicas
                and sharded over visible devices (core/population.py).
                Replica r is bitwise-equal to a ``concurrent`` run with
                seed ``spec.seed + r``.
==============  ============================================================

``baseline``/``synchronized`` support only loss-level variants (double,
dueling): PER, n-step, C51 and NoisyNet all require the concurrent
cycle's stage-then-flush machinery, and requesting them under a
sequential mode raises at build time with the supported alternatives.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Protocol, Tuple, \
    runtime_checkable

import jax
import jax.numpy as jnp

from repro.api.nets import build_net
from repro.api.spec import ExperimentSpec, MODES
from repro.core.baseline import BaselineCarry, make_baseline_chunk
from repro.core.concurrent import (EVAL_STREAM_TAG, TrainerCarry,
                                   make_concurrent_cycle, prepopulate,
                                   replica_key)
from repro.core.population import (eval_keys, make_population_cycle,
                                   make_replica_init, packed_seeds,
                                   population_evaluate, population_init,
                                   replica_mesh, seed_array)
from repro.core.replay import replay_init
from repro.core.synchronized import evaluate, sampler_init
from repro.envs import make_env
from repro.envs.preprocess import pixel_obs, vector_obs
from repro.optim import adamw, centered_rmsprop

__all__ = ["Trainer", "TRAINERS", "register_trainer", "build_trainer",
           "build_packed_fleet", "EVAL_STREAM_TAG"]
# EVAL_STREAM_TAG is defined once in core/concurrent.py (population's
# eval_keys folds the same constant) and re-exported here.


@runtime_checkable
class Trainer(Protocol):
    """The common contract over all execution modes (see module doc).

    ``cycle`` is a jitted callable — ``trainer.cycle.lower(carry)``
    works for roofline extraction (launch/dryrun.py uses this).
    """

    spec: ExperimentSpec
    replicas: int

    def init_carry(self, key: Optional[jax.Array] = None) -> Any: ...

    def init_template(self) -> Any: ...

    def cycle(self, carry) -> Tuple[Any, Dict[str, jax.Array]]: ...

    def eval(self, carry, key: jax.Array) -> jax.Array: ...

    def eval_key(self, cycle_index) -> jax.Array: ...

    def steps(self, carry) -> jax.Array: ...


TRAINERS: Dict[str, Callable[[ExperimentSpec], Trainer]] = {}


def register_trainer(mode: str):
    """Decorator registering a Trainer factory for an execution mode
    (mirrors ``kernels.backend.register``)."""
    assert mode in MODES, mode

    def deco(factory):
        TRAINERS[mode] = factory
        return factory

    return deco


def build_trainer(spec: ExperimentSpec) -> Trainer:
    """THE construction path from a declarative spec to a runnable
    trainer. Every launcher, benchmark and test goes through here — the
    spec is validated, the mode resolved through the registry, and the
    returned object satisfies the :class:`Trainer` protocol."""
    spec.validate()
    try:
        factory = TRAINERS[spec.mode]
    except KeyError:
        raise KeyError(f"unknown execution mode {spec.mode!r}; "
                       f"registered: {sorted(TRAINERS)}") from None
    return factory(spec)


def build_packed_fleet(spec: ExperimentSpec, seeds) -> Trainer:
    """A heterogeneous-seed population fleet — the construction path the
    sweep packer (repro.api.sweep) uses for a group of runs that differ
    only in seed. ``spec`` is the shared fleet spec with
    ``spec.seeds == len(seeds)``; ``seeds`` is the explicit replica-seed
    list (non-contiguous is fine). Replica r is bitwise-equal to the
    standalone single-seed run with ``seed = seeds[r]`` — the same
    population guarantee, with the contiguity assumption removed."""
    spec.validate()
    if spec.mode != "population":
        raise ValueError(
            f"packed fleets run in population mode (got {spec.mode!r}); "
            "non-population sweep runs execute as singleton fleets "
            "through build_trainer")
    return PopulationTrainer(spec, seeds=seeds)


# ---------------------------------------------------------------------------
# Shared component assembly (the wiring rl_train and dryrun used to
# duplicate, now derived from the spec exactly once)
# ---------------------------------------------------------------------------

class _Components:
    """env spec + obs pipeline + DQN config + the network's parts
    (``api/nets.py``) + optimizer."""

    def __init__(self, spec: ExperimentSpec):
        self.env = make_env(spec.env, **spec.env_params)
        # the observation pipeline every sampler/eval path consumes
        self.obs = (vector_obs(self.env) if spec.obs_mode == "vector"
                    else pixel_obs(spec.frame_size))
        self.dcfg = spec.dqn_config()
        # the network's parts (api/nets.py): ``qf`` is the feed-forward
        # Q function, or the recurrent net's step functions
        net = build_net(spec, self.env)
        self.qf, self.qlog, self.q_init = net.qf, net.qlog, net.init
        algo = spec.algo
        lr = algo.learning_rate
        if algo.optimizer == "rmsprop":
            self.opt = centered_rmsprop(lr or 2.5e-4)
        else:
            self.opt = adamw(lr or 1e-3, b2=algo.adam_b2, eps=algo.adam_eps,
                             weight_decay=0.0,
                             grad_clip=algo.adam_grad_clip or None)


def _expand_replica_axis(metrics: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Single-carry modes present the population shape contract by
    adding a leading axis of 1 (a view, not a copy, under jit)."""
    return jax.tree.map(lambda x: jnp.asarray(x)[None], metrics)


# ---------------------------------------------------------------------------
# population — the default mode; exactly the PR-4 rl_train wiring
# ---------------------------------------------------------------------------

@register_trainer("population")
class PopulationTrainer:
    """``spec.seeds`` replicas of the concurrent C-cycle as one vmapped
    (and, multi-device, shard_mapped) program. Replica r is
    bitwise-equal to the standalone run with seed ``spec.seed + r``
    (tests/test_population.py, tests/test_api.py)."""

    def __init__(self, spec: ExperimentSpec, seeds=None):
        self.spec = spec
        self.replicas = spec.seeds
        c = _Components(spec)
        self._c = c
        # ``seeds`` is the sweep packer's hook: an explicit (possibly
        # non-contiguous) replica-seed list replaces the contiguous
        # [seed, seed + P) range; everything downstream only consumes
        # the per-replica seed values.
        self.seeds = (seed_array(spec.seed, spec.seeds) if seeds is None
                      else packed_seeds(seeds))
        if self.seeds.shape[0] != spec.seeds:
            raise ValueError(
                f"packed seed list has {self.seeds.shape[0]} entries but "
                f"spec.seeds={spec.seeds} — the fleet spec must declare "
                "exactly the packed replica count")
        init_one = make_replica_init(c.env, c.q_init, c.qf, c.opt, c.dcfg,
                                     c.obs)
        self._init = lambda: population_init(init_one, self.seeds)
        mesh = replica_mesh(spec.seeds)
        # with a replica mesh, init builds each replica on its own device
        # (unsharded, the whole P-stack would land on device 0)
        self._carry_sharding = (None if mesh is None else
                                jax.sharding.NamedSharding(
                                    mesh, jax.sharding.PartitionSpec(
                                        "replica")))
        self.cycle = jax.jit(make_population_cycle(
            c.env, c.qf, c.opt, c.dcfg, obs=c.obs,
            kernel_backend=spec.exec.kernel_backend, q_logits=c.qlog,
            mesh=mesh))
        self._eval = jax.jit(lambda p, k: population_evaluate(
            c.env, c.qf, p, k, c.dcfg,
            n_episodes=spec.schedule.eval_episodes, obs=c.obs,
            max_steps=c.env.max_steps + 2))

    def init_carry(self, key: Optional[jax.Array] = None) -> TrainerCarry:
        # the replica seeds fully determine every RNG stream; ``key`` is
        # accepted for protocol uniformity and must be None
        assert key is None, "population init derives all RNG from seeds"
        return jax.jit(self._init, out_shardings=self._carry_sharding)()

    def init_template(self) -> TrainerCarry:
        return jax.eval_shape(self._init)

    def eval(self, carry: TrainerCarry, key: jax.Array) -> jax.Array:
        return self._eval(carry.params, key)

    def eval_key(self, cycle_index) -> jax.Array:
        return eval_keys(self.seeds, cycle_index)

    def steps(self, carry: TrainerCarry) -> jax.Array:
        return carry.step


# ---------------------------------------------------------------------------
# single-replica plumbing shared by the concurrent and sequential modes
# ---------------------------------------------------------------------------

class _SingleReplicaTrainer:
    """Protocol plumbing common to every P=1 adapter: the jitted
    ε=0.05 evaluator, the canonical eval-key derivation (same
    EVAL_STREAM_TAG as the population's ``eval_keys``), leading-axis
    expansion on eval/steps, and seed-derived init. Subclasses set
    ``self._init`` (the traceable carry constructor) and ``self.cycle``
    (the jitted super-step) in ``_build(spec, components)``."""

    replicas = 1

    def __init__(self, spec: ExperimentSpec):
        self.spec = spec
        c = _Components(spec)
        self._c = c
        self._eval = jax.jit(lambda p, k: evaluate(
            c.env, c.qf, p, k, c.dcfg,
            n_episodes=spec.schedule.eval_episodes,
            obs=c.obs, max_steps=c.env.max_steps + 2))
        self._build(spec, c)

    def _build(self, spec: ExperimentSpec, c: _Components) -> None:
        raise NotImplementedError

    def init_carry(self, key: Optional[jax.Array] = None):
        assert key is None, \
            f"{self.spec.mode} init derives all RNG from spec.seed"
        return jax.jit(self._init)()

    def init_template(self):
        return jax.eval_shape(self._init)

    def eval(self, carry, key: jax.Array) -> jax.Array:
        return self._eval(carry.params, jnp.asarray(key))[None]

    def eval_key(self, cycle_index) -> jax.Array:
        return replica_key(EVAL_STREAM_TAG, jnp.int32(self.spec.seed),
                           jnp.asarray(cycle_index))

    def steps(self, carry) -> jax.Array:
        return carry.step[None]


# ---------------------------------------------------------------------------
# concurrent — Algorithm 1 for a single replica
# ---------------------------------------------------------------------------

@register_trainer("concurrent")
class ConcurrentTrainer(_SingleReplicaTrainer):
    """The jitted C-cycle on one ``TrainerCarry``. Bitwise-equal to a
    1-seed population (the population layer is a pure batching
    transform); kept as its own mode so single-run tooling (dryrun
    roofline extraction, the concurrency tests) sees the unbatched
    program."""

    def _build(self, spec: ExperimentSpec, c: _Components) -> None:
        init_one = make_replica_init(c.env, c.q_init, c.qf, c.opt,
                                     c.dcfg, c.obs)
        self._init = lambda: init_one(jnp.int32(spec.seed))
        cycle_fn = make_concurrent_cycle(
            c.env, c.qf, c.opt, c.dcfg, obs=c.obs,
            kernel_backend=spec.exec.kernel_backend, q_logits=c.qlog)

        def cycle1(carry):
            carry, m = cycle_fn(carry)
            return carry, _expand_replica_axis(m)

        self.cycle = jax.jit(cycle1)


# ---------------------------------------------------------------------------
# baseline / synchronized — the sequential modes
# ---------------------------------------------------------------------------

# Variant toggles that need the concurrent cycle's staging machinery
# (PER priority staging, n-step aggregation on the staging buffer, C51
# projection in the burst loss, per-cycle NoisyNet draws).
_STAGING_TOGGLES = ("prioritized", "distributional", "noisy")


class _SequentialTrainer(_SingleReplicaTrainer):
    """Shared adapter over ``core.baseline.make_baseline_chunk``: one
    protocol cycle = ``schedule.cycle_steps`` timesteps of standard
    sequential DQN."""

    def __init__(self, spec: ExperimentSpec):
        bad = [t for t in _STAGING_TOGGLES if getattr(spec.variant, t)]
        if spec.variant.n_step > 1:
            bad.append(f"n_step={spec.variant.n_step}")
        if spec.variant.recurrent:
            bad.append("sequence replay")
        if bad:
            raise ValueError(
                f"mode {spec.mode!r} runs standard sequential DQN and "
                f"supports only loss-level variants (double/dueling); "
                f"variant {spec.variant.name!r} needs {', '.join(bad)} — "
                "use mode='concurrent' or 'population'")
        F, W = spec.algo.train_period, spec.envs
        if F % W != 0:
            raise ValueError(
                f"mode {spec.mode!r} updates every train_period env "
                f"steps over W-batched rounds, so train_period must be "
                f"a positive multiple of envs (got train_period={F}, "
                f"envs={W}) — raise train_period, lower envs, or use "
                "mode='concurrent'/'population' (any F)")
        if spec.schedule.cycle_steps % F != 0:
            raise ValueError(
                f"mode {spec.mode!r} needs cycle_steps divisible by "
                f"train_period (got {spec.schedule.cycle_steps} % {F})")
        super().__init__(spec)

    def _build(self, spec: ExperimentSpec, c: _Components) -> None:
        pipe = c.obs
        chunk = make_baseline_chunk(c.env, c.qf, c.opt, c.dcfg,
                                    obs=pipe,
                                    chunk_steps=spec.schedule.cycle_steps)

        def cycle1(carry):
            carry, m = chunk(carry)
            return carry, _expand_replica_axis(m)

        self.cycle = jax.jit(cycle1)

        def init() -> BaselineCarry:
            # split once, derive per-purpose: network init and the
            # sampler's episode streams must not draw the same bits
            # (same discipline as population.make_replica_init)
            kinit, ksampler = jax.random.split(
                jax.random.PRNGKey(jnp.int32(spec.seed)))
            params = c.q_init(kinit)
            replay = replay_init(c.dcfg.replay_capacity,
                                 pipe.shape + (c.dcfg.frame_stack,),
                                 obs_dtype=pipe.dtype)
            sampler = sampler_init(c.env, c.dcfg, ksampler, pipe)
            replay, sampler = prepopulate(c.env, c.qf, c.dcfg, replay,
                                          sampler, c.dcfg.prepopulate, pipe)
            return BaselineCarry(params, params, c.opt.init(params), replay,
                                 sampler, jnp.int32(0), jnp.int32(0))

        self._init = init


@register_trainer("baseline")
class BaselineTrainer(_SequentialTrainer):
    """Standard DQN (Figure 1a): θ acts, updates block, 𝒟 writes are
    immediate. The in-jit program batches the W streams (dataflow
    model); the per-stream transaction cost is the host runner's job."""


@register_trainer("synchronized")
class SynchronizedTrainer(_SequentialTrainer):
    """Synchronized Execution without Concurrent Training: the
    sequential update structure over W >= 2 explicitly batched streams
    (one Q transaction per round, Figure 3b)."""

    def __init__(self, spec: ExperimentSpec):
        if spec.envs < 2:
            raise ValueError(
                "synchronized execution aggregates W >= 2 sampler "
                f"streams (the paper marks W=1 as '—'); got envs={spec.envs}")
        super().__init__(spec)
