"""The paper's §3 claims, as executable tests:

1. determinism — the jitted C-cycle equals a step-by-step Python oracle
   that (a) acts from θ⁻, (b) trains from the 𝒟 snapshot, (c) flushes
   staged experiences only at the boundary;
2. decoupling — the actions taken during a cycle are identical whatever
   the trainer does (zero vs real learning rate), because the behaviour
   policy reads only θ⁻.

The compiled cycle also carries the phases' named scopes in its op_name
metadata, which a profiler trace reads back.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import DQNConfig
from repro.configs.dqn_nature import NatureCNNConfig
from repro.envs import get_env
from repro.models.nature_cnn import q_forward, q_init
from repro.optim import adamw
from repro.core.dqn import make_update_fn
from repro.core.replay import replay_init, replay_add_batch, replay_sample
from repro.core.synchronized import sampler_init, sync_round
from repro.core.concurrent import (CYCLE_SCOPES, PER_TREE_SCOPE,
                                   TrainerCarry, make_concurrent_cycle,
                                   prepopulate, replica_key)
from repro.optim.schedule import linear_epsilon

FS = 10


def _setup(C=32, W=4):
    spec = get_env("catch")
    ncfg = NatureCNNConfig(frame_size=FS, frame_stack=2,
                           convs=((8, 3, 1),), hidden=16,
                           n_actions=spec.n_actions)
    dcfg = DQNConfig(minibatch_size=8, replay_capacity=512,
                     target_update_period=C, train_period=4,
                     prepopulate=64, n_envs=W, frame_stack=2,
                     eps_anneal_steps=1000)
    key = jax.random.PRNGKey(0)
    params = q_init(ncfg, spec.n_actions, key)
    qf = lambda p, o: q_forward(p, o, ncfg)
    opt = adamw(1e-3, weight_decay=0.0)
    replay = replay_init(dcfg.replay_capacity, (FS, FS, 2))
    sampler = sampler_init(spec, dcfg, key, FS)
    replay, sampler = prepopulate(spec, qf, dcfg, replay, sampler,
                                  dcfg.prepopulate, FS)
    return spec, ncfg, dcfg, qf, opt, params, replay, sampler


def _oracle_cycle(spec, qf, opt, dcfg, carry):
    """Sequential Python re-implementation of Algorithm 1's C-cycle."""
    C, W, F = dcfg.target_update_period, dcfg.n_envs, dcfg.train_period
    eps_fn = linear_epsilon(dcfg.eps_start, dcfg.eps_end, dcfg.eps_anneal_steps)
    update = make_update_fn(qf, opt, dcfg)

    target = carry.params
    snapshot = carry.replay
    # sampler: C/W rounds from θ⁻
    sampler = carry.sampler
    staged = []
    for i in range(C // W):
        eps = eps_fn(carry.step + jnp.int32(i * W))
        sampler, tr = sync_round(spec, qf, target, sampler, eps, FS)
        staged.append(tr)
    # trainer: C/F updates on the snapshot
    params, opt_state = carry.params, carry.opt_state
    ktrain = replica_key(17, carry.seed, carry.step)
    for k in jax.random.split(ktrain, C // F):
        batch = replay_sample(snapshot, k, dcfg.minibatch_size)
        params, opt_state, _ = update(params, target, opt_state, batch)
    # flush
    flat = {key: jnp.concatenate([t[key] for t in staged], axis=0)
            for key in staged[0]}
    replay = replay_add_batch(carry.replay, flat)
    return TrainerCarry(params, opt_state, replay, sampler,
                        carry.step + C)


def test_cycle_matches_sequential_oracle():
    spec, ncfg, dcfg, qf, opt, params, replay, sampler = _setup()
    carry0 = TrainerCarry(params, opt.init(params), replay, sampler,
                          jnp.int32(0))
    cycle = jax.jit(make_concurrent_cycle(spec, qf, opt, dcfg, obs=FS))
    got, _ = cycle(carry0)
    want = _oracle_cycle(spec, qf, opt, dcfg, carry0)
    for g, w in zip(jax.tree_util.tree_leaves(got.params),
                    jax.tree_util.tree_leaves(want.params)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-6, rtol=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(got.replay),
                    jax.tree_util.tree_leaves(want.replay)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert int(got.step) == int(want.step)


def test_actions_independent_of_learner():
    """θ⁻ acting ⇒ the experiences collected in a cycle don't depend on
    the concurrent updates to θ (the dependency the paper breaks)."""
    spec, ncfg, dcfg, qf, opt_real, params, replay, sampler = _setup()
    from repro.optim import adamw as mk
    for lr in (0.0, 1e-2):
        opt = mk(lr, weight_decay=0.0)
        carry = TrainerCarry(params, opt.init(params), replay, sampler,
                             jnp.int32(0))
        cycle = jax.jit(make_concurrent_cycle(spec, qf, opt, dcfg,
                                              obs=FS))
        new, _ = cycle(carry)
        if lr == 0.0:
            ref_replay = new.replay
        else:
            for g, w in zip(jax.tree_util.tree_leaves(new.replay),
                            jax.tree_util.tree_leaves(ref_replay)):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_target_refresh_at_boundary():
    """After a cycle, the next cycle's behaviour params equal the params
    produced by the previous cycle's training (θ⁻ ← θ)."""
    spec, ncfg, dcfg, qf, opt, params, replay, sampler = _setup()
    carry = TrainerCarry(params, opt.init(params), replay, sampler,
                         jnp.int32(0))
    cycle = jax.jit(make_concurrent_cycle(spec, qf, opt, dcfg, obs=FS))
    c1, _ = cycle(carry)
    # params changed during the cycle...
    diffs = [float(jnp.max(jnp.abs(a - b)))
             for a, b in zip(jax.tree_util.tree_leaves(c1.params),
                             jax.tree_util.tree_leaves(carry.params))]
    assert max(diffs) > 0


def scopes_found(hlo_text):
    """The scopes of ``CYCLE_SCOPES`` present in compiled HLO's op_name
    metadata. Each op_name component loses its transform wrappers
    (``vmap(act)`` -> ``act``), and a nested scope may have other
    components between it and its parent (``act/while/body/render``)."""
    paths = set()
    for name in set(re.findall(r'op_name="([^"]*)"', hlo_text)):
        parts = []
        for part in name.split("/"):
            while (m := re.fullmatch(r"[\w.]+\((.+)\)", part)):
                part = m.group(1)
            parts.append(part)
        paths.add("/" + "/".join(parts) + "/")
    found = set()
    for scope in CYCLE_SCOPES:
        pattern = re.compile(
            "/" + "/(?:.*/)?".join(map(re.escape, scope.split("/"))) + "/")
        if any(pattern.search(p) for p in paths):
            found.add(scope)
    return found


@pytest.mark.parametrize("mode", ["concurrent", "population"])
@pytest.mark.parametrize("variant", ["dqn", "rainbow"])
def test_compiled_cycle_carries_the_phase_scopes(variant, mode):
    """Every scope of the cycle lands in the compiled program's op_name
    metadata, nested as named, in the single-replica cycle and under
    the population's vmap; per_tree only where replay is prioritized."""
    from repro.api import AlgoSpec, ExperimentSpec, ScheduleSpec, build_trainer
    from repro.configs.dqn_nature import get_variant

    spec = ExperimentSpec(
        mode=mode, variant=get_variant(variant), envs=4, frame_size=10,
        net="tiny",
        schedule=ScheduleSpec(cycles=1, cycle_steps=16, prepopulate=32,
                              eval_every=1, eval_episodes=4),
        algo=AlgoSpec(minibatch_size=8, replay_capacity=128,
                      train_period=4, eps_anneal_steps=1000))
    trainer = build_trainer(spec)
    text = trainer.cycle.lower(trainer.init_template()).compile().as_text()
    want = set(CYCLE_SCOPES)
    if variant == "dqn":
        want.discard(PER_TREE_SCOPE)
    assert scopes_found(text) == want
