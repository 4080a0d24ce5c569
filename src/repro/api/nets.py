"""The Q-network registry: what an ``ExperimentSpec``'s ``net`` names.

Each entry is a family and builds the parts the trainers consume from a
spec and its env (``NetParts``): the feed-forward Nature-CNN family
(``configs/dqn_nature.NET_PRESETS``: ``auto``, ``nature``, ``small``,
``tiny``, ``mlp``, ``mlp_tiny``) and the recurrent ``r2d2`` network
(``models/r2d2.py``; ``r2d2_tiny`` for tests), which needs a recurrent
variant (sequence replay) and pixel observations. Adding a network is
one builder and one entry in ``NETS``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

from repro.configs.dqn_nature import NET_PRESETS


class NetParts(NamedTuple):
    """``qf(params, obs[, noise_key]) -> (B, A)`` Q-values of a
    feed-forward net, or a ``models.r2d2.RecurrentQ`` for a recurrent
    one; ``qlog`` the (B, A, K) C51 logits or None; ``init(key) ->
    params``."""
    qf: Any
    qlog: Optional[Callable]
    init: Callable


def _cnn(spec, env) -> NetParts:
    from repro.models.nature_cnn import q_forward, q_init, q_logits
    ncfg = spec.cnn_config(env.n_actions)
    ec = spec.exec
    # trailing noise key (NoisyNet; None = μ-only, e.g. greedy eval)
    qf = lambda p, o, k=None: q_forward(p, o, ncfg, ec, noise_key=k)  # noqa: E731
    qlog = ((lambda p, o, k=None: q_logits(p, o, ncfg, ec, noise_key=k))
            if spec.variant.distributional else None)
    return NetParts(qf, qlog, lambda key: q_init(ncfg, env.n_actions, key))


def r2d2_config(spec):
    """``r2d2``: R2D2's published widths; ``r2d2_tiny``: the tests'
    network (one 8-channel 3x3 conv, an LSTM and dueling layers of 16)."""
    from repro.models.r2d2 import R2D2Config
    if spec.net == "r2d2_tiny":
        return R2D2Config(frame_size=spec.frame_size, convs=((8, 3, 1),),
                          lstm=16, dueling_hidden=16)
    return R2D2Config(frame_size=spec.frame_size)


def _r2d2(spec, env) -> NetParts:
    from repro.models import r2d2
    cfg = r2d2_config(spec)
    rq = r2d2.recurrent_q(cfg, spec.exec.cdtype)
    return NetParts(rq, None, lambda key: r2d2.init(cfg, env.n_actions, key))


def _check_cnn(spec) -> None:
    if spec.variant.recurrent:
        raise ValueError(
            f"variant {spec.variant.name!r} replays sequences and needs a "
            f"recurrent net; net {spec.net!r} is feed-forward (use "
            "net='r2d2')")


def _check_r2d2(spec) -> None:
    if not spec.variant.recurrent:
        raise ValueError(
            f"net {spec.net!r} is recurrent and needs a variant with "
            "sequence replay (sequence_length > 0)")
    if spec.obs_mode != "pixels":
        raise ValueError(
            f"net {spec.net!r} reads pixel frames (obs_mode='pixels')")


# name -> (builder, spec check)
NETS: Dict[str, tuple] = {**{n: (_cnn, _check_cnn) for n in NET_PRESETS},
                          "r2d2": (_r2d2, _check_r2d2),
                          "r2d2_tiny": (_r2d2, _check_r2d2)}


def check_net(spec) -> None:
    if spec.net not in NETS:
        raise ValueError(f"unknown net {spec.net!r}; one of {tuple(NETS)}")
    NETS[spec.net][1](spec)


def frame_stack(spec) -> int:
    """The frames one observation stack holds under ``spec``."""
    if NETS[spec.net][0] is _r2d2:
        return r2d2_config(spec).frame_stack
    from repro.configs.dqn_nature import cnn_geometry
    return cnn_geometry(spec.net, spec.frame_size, 1,
                        obs_dim=spec.obs_dim()).frame_stack


def build_net(spec, env) -> NetParts:
    return NETS[spec.net][0](spec, env)
