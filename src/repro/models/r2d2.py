"""The R2D2 recurrent Q-network (Kapturowski et al. 2019, ICLR).

The Nature conv torso (``nature_cnn.conv_torso``, no fc layer) feeds an
LSTM together with the previous action (one-hot) and the previous
reward (squashed by tanh), the observation-action-reward embedding of
Acme's R2D2 Atari network; a dueling head with a hidden layer of its
own per stream reads the LSTM's output:

    x_t = [torso(s_t), onehot(a_{t-1}), tanh(r_{t-1})]
    (h_t, c_t) = LSTM(x_t W_x + b, h_{t-1}, c_{t-1})
    Q(s_t, ·) = V(h_t) + A(h_t, ·) - mean A(h_t, ·)

The LSTM's gates are split i, f, g, o, and the forget gate carries a
bias of +1 on top of its parameter, as Haiku's LSTM has it.

Three functions make one step, so that a caller can time the
recurrence apart from the rest: ``embed`` (torso and the LSTM's input
projection, any batch of steps at once), ``lstm_step`` (the
recurrence) and ``head``. ``unroll`` runs the recurrence over a
sequence of embedded steps, resetting the state where an episode
starts. ``Core`` is what an actor carries per stream between steps.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.models import params as P
from repro.models.nature_cnn import conv_param_spec, conv_torso


@dataclasses.dataclass(frozen=True)
class R2D2Config:
    frame_size: int = 84
    frame_stack: int = 4
    # (out_channels, kernel, stride) per conv layer: the Nature torso
    convs: Tuple[Tuple[int, int, int], ...] = ((32, 8, 4), (64, 4, 2),
                                               (64, 3, 1))
    lstm: int = 512
    dueling_hidden: int = 512


# added to the forget gate's pre-activation, as Haiku's LSTM does
FORGET_BIAS = 1.0


class Core(NamedTuple):
    """One stream's recurrent input to its next step: the LSTM state
    and the previous action and reward; all zero where an episode
    starts."""
    h: jax.Array               # (B, lstm)
    c: jax.Array               # (B, lstm)
    prev_action: jax.Array     # (B,) int32
    prev_reward: jax.Array     # (B,) float32


def zero_core(batch: int, cfg: R2D2Config) -> Core:
    z = jnp.zeros((batch, cfg.lstm), jnp.float32)
    return Core(z, z, jnp.zeros((batch,), jnp.int32),
                jnp.zeros((batch,), jnp.float32))


def reset_core(core: Core, first: jax.Array) -> Core:
    """Zero the streams where ``first`` (B,) is set."""
    return Core(*(jnp.where(first.reshape((-1,) + (1,) * (x.ndim - 1)),
                            jnp.zeros_like(x), x) for x in core))


def param_spec(cfg: R2D2Config, n_actions: int) -> Dict[str, Any]:
    spec: Dict[str, Any] = {}
    flat = conv_param_spec(spec, cfg.convs, cfg.frame_size, cfg.frame_stack)
    d_in, H, D = flat + n_actions + 1, cfg.lstm, cfg.dueling_hidden
    spec["lstm_wx"] = P.Leaf((d_in, 4 * H), (None, "mlp"), fan_in=d_in)
    spec["lstm_wh"] = P.Leaf((H, 4 * H), (None, "mlp"), fan_in=H)
    spec["lstm_b"] = P.Leaf((4 * H,), ("mlp",), init="zeros")
    for name, d_out in (("val", 1), ("adv", n_actions)):
        spec[f"{name}_fc_w"] = P.Leaf((H, D), (None, "mlp"), fan_in=H)
        spec[f"{name}_fc_b"] = P.Leaf((D,), ("mlp",), init="zeros")
        spec[f"{name}_w"] = P.Leaf((D, d_out), ("mlp", None), fan_in=D)
        spec[f"{name}_b"] = P.Leaf((d_out,), (None,), init="zeros")
    return spec


def init(cfg: R2D2Config, n_actions: int, key: jax.Array):
    return P.init_tree(param_spec(cfg, n_actions), key)


def embed(params, frames: jax.Array, prev_action: jax.Array,
          prev_reward: jax.Array, cfg: R2D2Config, cdt=jnp.float32
          ) -> jax.Array:
    """(N, H, W, K) uint8 stacks, (N,) previous actions and rewards ->
    (N, 4 * lstm) gate inputs, the LSTM's input projection."""
    n_actions = params["adv_w"].shape[-1]
    x = jnp.concatenate(
        [conv_torso(params, frames, cfg.convs, cdt),
         jax.nn.one_hot(prev_action, n_actions, dtype=cdt),
         jnp.tanh(prev_reward.astype(cdt))[:, None]], axis=-1)
    return x @ params["lstm_wx"].astype(cdt) + params["lstm_b"].astype(cdt)


def lstm_step(params, gates_x: jax.Array, h: jax.Array, c: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    gates = gates_x + h @ params["lstm_wh"].astype(gates_x.dtype)
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + FORGET_BIAS) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    return jax.nn.sigmoid(o) * jnp.tanh(c), c


def head(params, h: jax.Array) -> jax.Array:
    """(..., lstm) -> (..., A) dueling Q-values, float32."""
    def stream(name):
        x = jax.nn.relu(h @ params[f"{name}_fc_w"] + params[f"{name}_fc_b"])
        return x @ params[f"{name}_w"] + params[f"{name}_b"]
    v, a = stream("val"), stream("adv")
    return (v + a - jnp.mean(a, axis=-1, keepdims=True)).astype(jnp.float32)


def unroll(params, gates_x: jax.Array, first: jax.Array, h: jax.Array,
           c: jax.Array):
    """The recurrence over T steps: ``gates_x`` (T, B, 4H), ``first``
    (T, B) episode starts, where the state is zeroed before the step.
    Returns the outputs (T, B, H) and the last state."""
    def step(hc, xs):
        gx, f = xs
        h, c = (jnp.where(f[:, None], 0.0, x) for x in hc)
        h, c = lstm_step(params, gx, h, c)
        return (h, c), h

    (h, c), hs = jax.lax.scan(step, (h, c), (gates_x, first))
    return hs, (h, c)


class RecurrentQ(NamedTuple):
    """The network's step as an actor or learner calls it, bound to its
    configuration: ``embed(params, frames, prev_action, prev_reward)``,
    ``step(params, gates_x, h, c)`` and ``head(params, h)``."""
    cfg: R2D2Config
    embed: Callable
    step: Callable
    head: Callable


def recurrent_q(cfg: R2D2Config, cdt=jnp.float32) -> RecurrentQ:
    return RecurrentQ(
        cfg, lambda p, o, a, r: embed(p, o, a, r, cfg, cdt), lstm_step, head)
