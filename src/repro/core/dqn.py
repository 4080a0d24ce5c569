"""DQN algorithm pieces: the loss of Eq. (1), ε-greedy action selection,
and the gradient update — shared verbatim by the sequential baseline and
the Concurrent/Synchronized runtime (the paper stresses that all variants
share time-critical code so measured speedups are attributable to the
execution framework alone).

The off-policy variant family (``VariantConfig``) plugs in here: double
Q-learning swaps the bootstrap argmax to the online network, n-step
returns raise the bootstrap discount to γⁿ (rewards are pre-aggregated
by the sampler, see ``synchronized.nstep_aggregate``), prioritized
replay threads per-sample importance-sampling weights into the Huber
mean and reads the per-sample TD errors back out for the priority
update, C51 swaps the Huber regression for a categorical cross-entropy
against the projected target distribution (the ``categorical_projection``
op), and NoisyNet threads per-call noise keys into the network.  With
the default variant every formula below reduces to the vanilla path
bit-for-bit. A recurrent variant (R2D2) has a loss of its own over
replayed sequences: ``burn_in`` and ``sequence_loss``.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import DQNConfig, VariantConfig
from repro.core.synchronized import LSTM_SCOPE
from repro.kernels import ops as kops
from repro.models.r2d2 import lstm_step, unroll


def q_loss(params, target_params, batch: Dict[str, jax.Array],
           q_forward: Callable, discount: float) -> jax.Array:
    """Eq. (1) with the standard Mnih-style TD-error clipping (Huber):
    quadratic within [-1, 1], linear outside."""
    loss, _ = q_loss_variant(params, target_params, batch, q_forward,
                             discount, VariantConfig())
    return loss


def _with_noise(q_forward: Callable, noise_key: Optional[jax.Array]):
    """Adapt the 2-arg q_forward convention to per-call noise: call site
    i gets an independent key (online/target/online-next noise must be
    independent draws, Fortunato et al. 2018 §4)."""
    if noise_key is None:
        return lambda p, o, i: q_forward(p, o)
    return lambda p, o, i: q_forward(p, o, jax.random.fold_in(noise_key, i))


def q_loss_variant(params, target_params, batch: Dict[str, jax.Array],
                   q_forward: Callable, discount: float,
                   variant: VariantConfig,
                   noise_key: Optional[jax.Array] = None):
    """Variant-aware Eq. (1). Returns (scalar loss, per-sample |td|).

    * double: a* = argmax_a Q_θ(s', a); bootstrap = Q_θ⁻(s', a*)
      (van Hasselt et al. 2016) instead of max_a Q_θ⁻(s', a);
    * n-step: batch rewards hold Σ γᵏ r (masked past the first done), so
      the bootstrap discount is γⁿ and ``done`` means "episode ended
      within the window";
    * prioritized: ``batch['weight']`` scales each sample's Huber term
      (the IS correction); absent, the mean is unweighted;
    * noisy: ``noise_key`` (None = μ-only) is split per forward call, so
      online, target and online-next evaluations see independent noise.
    """
    qf = _with_noise(q_forward, noise_key)
    q = qf(params, batch["obs"], 0)                              # (B, A)
    qa = jnp.take_along_axis(q, batch["action"][:, None], axis=1)[:, 0]
    q_next = qf(target_params, batch["next_obs"], 1)
    if variant.double:
        q_next_online = qf(params, batch["next_obs"], 2)
        a_star = jnp.argmax(q_next_online, axis=-1)
        bootstrap = jnp.take_along_axis(q_next, a_star[:, None], axis=1)[:, 0]
    else:
        bootstrap = jnp.max(q_next, axis=-1)
    disc_n = discount ** variant.n_step
    y = batch["reward"] + disc_n * jnp.where(batch["done"], 0.0, bootstrap)
    td = jax.lax.stop_gradient(y) - qa
    huber = jnp.where(jnp.abs(td) <= 1.0, 0.5 * td * td, jnp.abs(td) - 0.5)
    if "weight" in batch:
        loss = jnp.mean(batch["weight"] * huber)
    else:
        loss = jnp.mean(huber)
    return loss, jax.lax.stop_gradient(jnp.abs(td))


def c51_loss_variant(params, target_params, batch: Dict[str, jax.Array],
                     q_logits: Callable, discount: float,
                     variant: VariantConfig,
                     noise_key: Optional[jax.Array] = None,
                     kernel_backend: Optional[str] = None):
    """Distributional (C51) cross-entropy loss (Bellemare et al. 2017).

    The target distribution is the ``categorical_projection`` of the
    θ⁻ next-state distribution under the γⁿ-shifted support (n-step
    rewards arrive pre-aggregated, exactly like the scalar path). With
    ``variant.double`` the next-state action is the argmax of the
    *online* expectation. Returns (scalar loss, per-sample
    cross-entropy): the CE doubles as the PER priority signal — it is
    KL(m ‖ p_θ) plus H(m), where H(m) is θ-independent but *per-sample*
    (it depends on each transition's projected target), so CE-ranked
    priorities can differ from KL-ranked ones; CE is the standard
    Rainbow choice because it is the quantity the loss minimizes.
    """
    z = kops.support(variant.num_atoms, variant.v_min, variant.v_max)
    qf = _with_noise(q_logits, noise_key)
    logits = qf(params, batch["obs"], 0)                         # (B, A, K)
    logp = jax.nn.log_softmax(logits, axis=-1)
    logp_a = jnp.take_along_axis(
        logp, batch["action"][:, None, None], axis=1)[:, 0]      # (B, K)
    tgt_logits = qf(target_params, batch["next_obs"], 1)
    tgt_probs = jax.nn.softmax(tgt_logits, axis=-1)              # (B, A, K)
    if variant.double:
        online_next = qf(params, batch["next_obs"], 2)
        q_next = jnp.sum(jax.nn.softmax(online_next, axis=-1) * z, axis=-1)
    else:
        q_next = jnp.sum(tgt_probs * z, axis=-1)                 # (B, A)
    a_star = jnp.argmax(q_next, axis=-1)
    p_t = jnp.take_along_axis(tgt_probs, a_star[:, None, None],
                              axis=1)[:, 0]                      # (B, K)
    disc_n = discount ** variant.n_step
    m = kops.categorical_projection(
        jax.lax.stop_gradient(p_t), batch["reward"],
        batch["done"].astype(jnp.float32), variant.v_min, variant.v_max,
        disc_n, backend=kernel_backend)
    ce = -jnp.sum(jax.lax.stop_gradient(m) * logp_a, axis=-1)    # (B,)
    if "weight" in batch:
        loss = jnp.mean(batch["weight"] * ce)
    else:
        loss = jnp.mean(ce)
    return loss, jax.lax.stop_gradient(ce)


def egreedy(q_values: jax.Array, eps: jax.Array, key: jax.Array) -> jax.Array:
    """q_values: (W, A) -> actions (W,). The one round key is split into
    W per-stream keys and each row draws from its own
    (``core.policy.egreedy_stream``), so stream i's randomness is
    independent of W and of the other rows — the batch-composition
    invariance the serving layer's microbatching relies on."""
    from repro.core.policy import egreedy_stream, stream_keys
    W = q_values.shape[0]
    eps = jnp.broadcast_to(jnp.asarray(eps, jnp.float32), (W,))
    return jax.vmap(egreedy_stream)(q_values, eps, stream_keys(key, W))


def make_update_fn(q_forward: Callable, opt, cfg: DQNConfig,
                   variant: Optional[VariantConfig] = None,
                   q_logits: Optional[Callable] = None,
                   kernel_backend: Optional[str] = None):
    """One minibatch gradient step.

    The loss follows ``cfg.variant`` (callers may override with an
    explicit ``variant``), so the baseline and host runner apply the
    same loss-level variants (double Q-learning) as the concurrent
    runtime — their *control flow* stays standard DQN (uniform replay,
    immediate 1-step writes), which is the baseline's point. Because
    those paths store 1-step transitions, the n-step bootstrap discount
    is neutralized on the legacy contract (γⁿ is only valid after
    ``nstep_aggregate``, which only the concurrent cycle runs).

    ``variant=None`` (the legacy contract, used by the baseline and the
    host runner): (params, target, opt_state, batch) ->
    (params', opt_state', loss). With an explicit ``VariantConfig`` the
    update additionally returns the per-sample priority signal (|td|,
    or the C51 cross-entropy) for the PER staging, and accepts an
    optional trailing ``noise_key`` (NoisyNet variants):
    -> (params', opt_state', loss, td_abs). Distributional variants
    require ``q_logits`` (the (B, A, K) head); ``kernel_backend`` is
    the projection-op request."""
    import dataclasses

    from repro.optim.base import apply_updates

    v = variant if variant is not None else dataclasses.replace(
        cfg.variant, n_step=1)
    if v.distributional:
        assert q_logits is not None, \
            "distributional variants need the q_logits callable"

        def loss_fn(params, target_params, batch, noise_key):
            return c51_loss_variant(params, target_params, batch, q_logits,
                                    cfg.discount, v, noise_key,
                                    kernel_backend)
    else:
        def loss_fn(params, target_params, batch, noise_key):
            return q_loss_variant(params, target_params, batch, q_forward,
                                  cfg.discount, v, noise_key)

    def update(params, target_params, opt_state, batch, noise_key=None):
        (loss, td_abs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, target_params, batch, noise_key)
        updates, opt_state = opt.update(grads, opt_state, params)
        new_params = apply_updates(params, updates)
        if variant is None:
            return new_params, opt_state, loss
        return new_params, opt_state, loss, td_abs

    return update


# ---------------------------------------------------------------------------
# the recurrent (R2D2) sequence loss
# ---------------------------------------------------------------------------

UPDATE_SCOPE = "update"
"""One update's forward, backward and optimizer step, and its priority
staging under PER; in a sequence update, all but the recurrences."""
BURN_IN_SCOPE = "burn_in"
"""Both no-gradient burn-in unrolls of a sequence update, online and
target, from the stored state."""


def value_rescale(x: jax.Array, eps: float) -> jax.Array:
    """h(x) = sign(x)(√(|x| + 1) − 1) + εx (Pohlen et al. 2018)."""
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def value_rescale_inv(x: jax.Array, eps: float) -> jax.Array:
    """h⁻¹(x) = sign(x)((√(1 + 4ε(|x| + 1 + ε)) − 1) / 2ε)² − 1),
    computed without its cancellations near 0: with u = √(|h⁻¹(x)| + 1),
    the root of εu² + u = 1 + ε + |x|, |h⁻¹(x)| = (u − 1)(u + 1) and
    u − 1 = |x| / (1 + ε(u + 1))."""
    y = jnp.abs(x)
    b = 1.0 + eps + y
    u = 2.0 * b / (1.0 + jnp.sqrt(1.0 + 4.0 * eps * b))
    return jnp.sign(x) * (y / (1.0 + eps * (u + 1.0))) * (u + 1.0)


def _sequence_gates(rq, params, batch, lo: int, hi: int) -> jax.Array:
    """The LSTM input projection of steps [lo, hi) of every sequence,
    all at once: (hi - lo, B, 4H), time major."""
    obs = batch["obs"][:, lo:hi]
    B, T = obs.shape[:2]
    gx = rq.embed(params, obs.reshape((B * T,) + obs.shape[2:]),
                  batch["prev_action"][:, lo:hi].reshape(-1),
                  batch["prev_reward"][:, lo:hi].reshape(-1))
    return jnp.swapaxes(gx.reshape(B, T, -1), 0, 1)


def burn_in(rq, params, target_params, batch, steps: int):
    """Unroll the online and the target net over the first ``steps``
    steps of each sequence from its stored state, without gradient;
    returns both states after them."""
    state = (batch["h"], batch["c"])
    params = jax.lax.stop_gradient(params)
    gx_on = _sequence_gates(rq, params, batch, 0, steps)
    gx_tg = _sequence_gates(rq, target_params, batch, 0, steps)

    def step(hc, xs):
        gon, gtg, first = xs
        hc = tuple(jnp.where(first[:, None], 0.0, x) for x in hc)
        h_on, c_on = lstm_step(params, gon, hc[0], hc[1])
        h_tg, c_tg = lstm_step(target_params, gtg, hc[2], hc[3])
        return (h_on, c_on, h_tg, c_tg), None

    hc, _ = jax.lax.scan(step, state + state,
                         (gx_on, gx_tg, batch["first"][:, :steps].T))
    hc = jax.lax.stop_gradient(hc)
    return hc[:2], hc[2:]


def sequence_loss(params, target_params, batch, state_on, state_tg, rq,
                  cfg: DQNConfig, variant: VariantConfig):
    """The R2D2 loss of a batch of sequences after their burn-in.

    Both nets unroll the remaining steps from their burned-in states.
    Each of the ``sequence_length`` trained steps t gets the n-step
    double-Q target, under value rescaling h:

        y_t = h(Σ_{k<n'} γᵏ r_{t+k} + γⁿ' h⁻¹(Q⁻(s_{t+n'}, argmax Q(s_{t+n'}))))

    with the return cut at an episode's end (no bootstrap then) and at
    the sequence's end (n' = min(n, steps left)). The loss is Σ_t ½δ_t²
    per sequence, averaged over the batch with the importance weights;
    the priority of a sequence is η·max_t|δ_t| + (1 − η)·mean_t|δ_t|.
    Returns (loss, priorities)."""
    L, m, n = variant.burn_in, variant.sequence_length, variant.n_step
    eps = variant.value_rescale_eps
    target_params = jax.lax.stop_gradient(target_params)
    with jax.named_scope(UPDATE_SCOPE):
        gx_on = _sequence_gates(rq, params, batch, L, L + m + 1)
        gx_tg = _sequence_gates(rq, target_params, batch, L, L + m + 1)
    first = batch["first"][:, L:].T
    with jax.named_scope(LSTM_SCOPE):
        hs_on, _ = unroll(params, gx_on, first, *state_on)
        hs_tg, _ = unroll(target_params, gx_tg, first, *state_tg)
    with jax.named_scope(UPDATE_SCOPE):
        q_on = jnp.swapaxes(rq.head(params, hs_on), 0, 1)     # (B, m+1, A)
        q_tg = jnp.swapaxes(rq.head(target_params, hs_tg), 0, 1)
        reward = batch["reward"][:, L:L + m]
        done = batch["done"][:, L:L + m].astype(reward.dtype)
        action = batch["action"][:, L:L + m]
        t = jnp.arange(m)
        ret = jnp.zeros_like(reward)
        live = jnp.ones_like(reward)
        pad = ((0, 0), (0, n))
        reward_p, done_p = jnp.pad(reward, pad), jnp.pad(done, pad)
        for k in range(n):
            inside = (t + k < m).astype(reward.dtype)
            ret = ret + (cfg.discount ** k) * live * inside * \
                reward_p[:, k:k + m]
            live = live * (1.0 - inside * done_p[:, k:k + m])
        steps = jnp.minimum(n, m - t)                          # n'
        boot = t + steps
        q_boot_on = jax.lax.stop_gradient(q_on)[:, boot]       # (B, m, A)
        a_star = jnp.argmax(q_boot_on, axis=-1)
        q_boot = jnp.take_along_axis(q_tg[:, boot], a_star[..., None],
                                     axis=-1)[..., 0]
        y = value_rescale(ret + live * (cfg.discount ** steps)
                          * value_rescale_inv(q_boot, eps), eps)
        qa = jnp.take_along_axis(q_on[:, :m], action[..., None],
                                 axis=-1)[..., 0]
        td = jax.lax.stop_gradient(y) - qa
        per_seq = jnp.sum(0.5 * td * td, axis=1)
        loss = jnp.mean(batch["weight"] * per_seq)
        priority = sequence_priority(jax.lax.stop_gradient(td),
                                     variant.priority_eta)
    return loss, priority


def sequence_priority(td: jax.Array, eta: float) -> jax.Array:
    """(B, m) TD errors -> (B,) η·max|δ| + (1 − η)·mean|δ| (R2D2 §2.3)."""
    abs_td = jnp.abs(td)
    return eta * jnp.max(abs_td, axis=-1) + (1.0 - eta) * jnp.mean(
        abs_td, axis=-1)


def make_sequence_update_fn(rq, opt, cfg: DQNConfig):
    """One sequence minibatch step after the burn-in:
    (params, target, opt_state, batch, state_on, state_tg) ->
    (params', opt_state', loss, priorities)."""
    from repro.optim.base import apply_updates
    variant = cfg.variant

    def update(params, target_params, opt_state, batch, state_on, state_tg):
        (loss, priority), grads = jax.value_and_grad(
            sequence_loss, has_aux=True)(params, target_params, batch,
                                         state_on, state_tg, rq, cfg,
                                         variant)
        with jax.named_scope(UPDATE_SCOPE):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
        return params, opt_state, loss, priority

    return update
