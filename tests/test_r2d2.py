"""R2D2 on the population trainer's path: the recurrent net's state in
acting and in the unroll, the sequence replay's rebuilt stacks, the
burn-in, value rescaling, sequence priorities, the target's own period
and the net registry. Tiny sizes on the CPU: 10x10 frames, the
``r2d2_tiny`` net, W=2-4 streams."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, build_trainer
from repro.core.concurrent import CYCLE_SCOPES
from repro.core.dqn import (burn_in, sequence_loss, sequence_priority,
                            value_rescale, value_rescale_inv)
from repro.core.replay import (pack_frames, sequence_add, sequence_gather,
                               sequence_layout, sequence_replay_init)
from repro.core.synchronized import sampler_init, sync_round
from repro.models import r2d2

from test_concurrent import scopes_found

R2D2 = dict(name="r2d2", double=True, dueling=True, prioritized=True,
            n_step=5, per_alpha=0.9, per_beta0=0.6, per_beta_anneal_steps=0,
            sequence_length=8, burn_in=4, sequence_period=4,
            priority_eta=0.9, value_rescale_eps=1e-3, actor_eps=0.4)


def tiny_spec(envs=4, target_cycles=2, **algo) -> ExperimentSpec:
    return ExperimentSpec.from_dict(dict(
        env="pong", mode="population", envs=envs, frame_size=10,
        net="r2d2_tiny", variant=R2D2, seed=5,
        schedule=dict(cycles=4, cycle_steps=8 * envs, prepopulate=16 * envs,
                      target_cycles=target_cycles, eval_episodes=4),
        algo=dict(dict(minibatch_size=4, replay_capacity=8 * envs,
                       train_period=8, discount=0.997, learning_rate=1e-4,
                       adam_b2=0.999, adam_eps=1e-3, adam_grad_clip=0.0),
                  **algo)))


@pytest.fixture(scope="module")
def trainer():
    return build_trainer(tiny_spec())


def _act(trainer, rounds, seed=1, eps=1.0):
    """``rounds`` synchronized rounds of the trainer's recurrent net from
    fresh streams: per round the actor's stack, its core before the
    step, and the step's action, reward and done."""
    c = trainer._c
    params = c.q_init(jax.random.PRNGKey(seed))
    s = sampler_init(c.env, c.dcfg, jax.random.PRNGKey(seed + 1), c.obs,
                     core_cfg=c.qf.cfg)
    step = jax.jit(lambda s: sync_round(c.env, c.qf, params, s,
                                        jnp.float32(eps), c.obs))
    out = []
    for _ in range(rounds):
        core = s.core
        s, tr = step(s)
        out.append((tr, core))
    return params, s, out


def test_acting_zeroes_the_core_where_an_episode_starts(trainer):
    _, s, rounds = _act(trainer, 40)
    ends = 0
    for (tr, _), (_, nxt) in zip(rounds, rounds[1:]):
        done = np.asarray(tr["done"])
        ends += done.sum()
        for x in nxt:
            x = np.asarray(x).reshape(done.shape[0], -1)
            assert not x[done].any()
        assert np.abs(np.asarray(nxt.h)[~done]).sum() > 0
        np.testing.assert_array_equal(np.asarray(nxt.prev_action)[~done],
                                      np.asarray(tr["action"])[~done])
    assert ends > 0, "no episode ended; the test saw no reset"


def test_unroll_zeroes_the_state_where_an_episode_starts(trainer):
    cfg = trainer._c.qf.cfg
    params = trainer._c.q_init(jax.random.PRNGKey(0))
    T, B, H = 6, 3, cfg.lstm
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    gx = jax.random.normal(k1, (T, B, 4 * H))
    h0, c0 = (jax.random.normal(k, (B, H)) for k in (k2, k3))
    first = jnp.zeros((T, B), bool).at[0, 1].set(True).at[3, 0].set(True)
    hs, _ = r2d2.unroll(params, gx, first, h0, c0)
    z = jnp.zeros((1, H))
    for t, b in ((0, 1), (3, 0)):
        want, _ = r2d2.lstm_step(params, gx[t, b][None], z, z)
        np.testing.assert_allclose(hs[t, b], want[0], rtol=1e-6)
    want, _ = r2d2.lstm_step(params, gx[0, 0][None], h0[:1], c0[:1])
    np.testing.assert_allclose(hs[0, 0], want[0], rtol=1e-6)


def test_replay_rebuilds_the_stacks_the_actor_saw(trainer):
    """Through an episode end and once the ring has wrapped, every
    drawable sequence holds, step for step, the stack, previous action
    and reward and episode-start flag the actor had, and the state the
    actor carried at its start."""
    cfg = trainer._c.dcfg
    layout = sequence_layout(cfg)
    rounds_n = layout.ring + 5 * layout.period
    _, _, rounds = _act(trainer, rounds_n)
    state = sequence_replay_init(layout, (10, 10), trainer._c.qf.cfg.lstm)
    P = layout.period
    checked = ends = 0
    for lo in range(0, rounds_n, P):
        chunk = rounds[lo:lo + P]
        staged = {
            "frames": jnp.stack([pack_frames(tr["obs"][..., -1])
                                 for tr, _ in chunk]),
            **{k: jnp.stack([tr[k] for tr, _ in chunk])
               for k in ("action", "reward", "done")},
            "h": chunk[0][1].h[None], "c": chunk[0][1].c[None]}
        state = sequence_add(state, layout, staged)
    assert int(state["steps"]) > layout.ring
    drawable = np.flatnonzero(np.asarray(state["priority"]) > 0)
    assert int(state["size"]) == len(drawable) > 0
    batch = sequence_gather(state, layout, jnp.asarray(drawable))
    steps = int(state["steps"])
    for i, leaf in enumerate(drawable):
        w, slot = divmod(int(leaf), layout.slots)
        start = slot * P + layout.ring * ((steps - 1 - slot * P)
                                          // layout.ring)
        for k in range(layout.span):
            tr, core = rounds[start + k]
            np.testing.assert_array_equal(batch["obs"][i, k], tr["obs"][w])
            np.testing.assert_array_equal(batch["prev_action"][i, k],
                                          core.prev_action[w])
            np.testing.assert_array_equal(batch["prev_reward"][i, k],
                                          core.prev_reward[w])
            was_reset = start + k > 0 and bool(rounds[start + k - 1][0]
                                                ["done"][w])
            assert bool(batch["first"][i, k]) == was_reset
            ends += was_reset
        np.testing.assert_array_equal(batch["h"][i], rounds[start][1].h[w])
        checked += 1
    assert checked and ends, (checked, ends)


def _batch(trainer, seed=3):
    c = trainer._c
    layout = sequence_layout(c.dcfg)
    carry = trainer.init_carry()
    replay = jax.tree.map(lambda x: x[0], carry.replay)
    idx = jnp.flatnonzero(replay["priority"] > 0, size=4)
    batch = sequence_gather(replay, layout, idx)
    batch["weight"] = jnp.ones((4,))
    params = c.q_init(jax.random.PRNGKey(seed))
    target = c.q_init(jax.random.PRNGKey(seed + 1))
    return params, target, batch


def test_burn_in_carries_no_gradient(trainer):
    c = trainer._c
    v = c.dcfg.variant
    params, target, batch = _batch(trainer)

    def loss_through_burn_in(p):
        on, tg = burn_in(c.qf, p, target, batch, v.burn_in)
        return sequence_loss(p, target, batch, on, tg, c.qf, c.dcfg, v)[0]

    on, tg = burn_in(c.qf, params, target, batch, v.burn_in)
    g_through = jax.grad(loss_through_burn_in)(params)
    g_after = jax.grad(lambda p: sequence_loss(
        p, target, batch, on, tg, c.qf, c.dcfg, v)[0])(params)
    for k in params:
        np.testing.assert_array_equal(g_through[k], g_after[k])
    # the burn-in does move the loss: it is not a no-op
    zero = (jnp.zeros_like(batch["h"]),) * 2
    assert sequence_loss(params, target, batch, zero, zero, c.qf, c.dcfg,
                         v)[0] != loss_through_burn_in(params)


@pytest.mark.parametrize("eps", [1e-3, 1e-2])
def test_value_rescaling_inverts(eps):
    x = jnp.concatenate([-jnp.logspace(-3, 3, 50), jnp.zeros(1),
                         jnp.logspace(-3, 3, 50)])
    np.testing.assert_allclose(value_rescale_inv(value_rescale(x, eps), eps),
                               x, rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(value_rescale(value_rescale_inv(x, eps), eps),
                               x, rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("eta,want", [(0.9, [2.9, 0.5]), (1.0, [3.0, 0.5]),
                                      (0.0, [2.0, 0.5])])
def test_sequence_priority_mixes_max_and_mean(eta, want):
    td = jnp.array([[1.0, -3.0], [0.5, -0.5]])
    np.testing.assert_allclose(sequence_priority(td, eta), want, rtol=1e-6)


@pytest.mark.parametrize("k", [1, 2])
def test_target_syncs_only_every_k_cycles(trainer, k):
    if k != 2:
        trainer = build_trainer(tiny_spec(target_cycles=k))
    carry = trainer.init_carry()
    history = [carry.params]
    targets = []
    for _ in range(4):
        carry, _ = trainer.cycle(carry)
        history.append(carry.params)
        targets.append(carry.target)
    # every K-th cycle syncs θ⁻ from θ at its start
    for cycle in range(4):
        for name in carry.params:
            np.testing.assert_array_equal(targets[cycle][name],
                                          history[cycle // k * k][name])
    assert any(not np.array_equal(history[1][name], history[0][name])
               for name in carry.params)


def test_compiled_cycle_carries_the_recurrent_scopes(trainer):
    text = trainer.cycle.lower(trainer.init_template()).compile().as_text()
    assert scopes_found(text) == set(CYCLE_SCOPES)


def test_evaluates_through_the_trainer(trainer):
    carry = trainer.init_carry()
    returns = trainer.eval(carry, trainer.eval_key(0))
    assert returns.shape == (1,) and np.isfinite(returns).all()


@pytest.mark.parametrize("change,match", [
    (dict(net="tiny"), "needs a recurrent net"),
    (dict(variant=dict(R2D2, sequence_length=0)), "sequence replay"),
    (dict(algo=dict(replay_capacity=30)), "whole number per stream"),
    (dict(schedule=dict(cycle_steps=24)), "multiple of envs"),
    (dict(net="lstm9"), "unknown net"),
    (dict(net="tiny", variant=dict(R2D2, sequence_length=0)),
     "needs a recurrent variant"),
])
def test_spec_refuses_what_the_sequence_replay_cannot_run(change, match):
    d = tiny_spec().to_dict()
    for k, v in change.items():
        d[k] = dict(d[k], **v) if isinstance(v, dict) else v
    with pytest.raises(ValueError, match=match):
        ExperimentSpec.from_dict(d).validate()


@pytest.mark.parametrize("field", ["burn_in", "value_rescale_eps",
                                   "actor_eps"])
def test_a_recurrent_variant_needs_each_of_r2d2s_parts(field):
    """A recurrent variant always burns in, rescales its targets and
    gives each stream its Ape-X ε: none of them can be set to 0."""
    v = tiny_spec().variant
    v.validate()
    with pytest.raises(AssertionError):
        dataclasses.replace(v, **{field: 0}).validate()


def test_adam_settings_reach_the_optimizer():
    """The spec's Adam ε, b2 and clip are the optimizer's: a step from
    the trainer's optimizer equals one of ``adamw`` built with them."""
    from repro.optim import adamw
    spec = tiny_spec()
    opt = build_trainer(spec)._c.opt
    want = adamw(1e-4, b2=0.999, eps=1e-3, weight_decay=0.0, grad_clip=None)
    params = {"w": jnp.ones((3,))}
    grads = {"w": jnp.array([10.0, -0.2, 3e-4])}
    got, _ = opt.update(grads, opt.init(params), params)
    ref, _ = want.update(grads, want.init(params), params)
    np.testing.assert_array_equal(got["w"], ref["w"])
    clipped = dataclasses.replace(spec, algo=dataclasses.replace(
        spec.algo, adam_grad_clip=1.0))
    opt = build_trainer(clipped)._c.opt
    got, _ = opt.update(grads, opt.init(params), params)
    assert not np.array_equal(got["w"], ref["w"])
