"""The Nature-DQN convolutional Q-network (Mnih et al. 2015) — the paper's
own model. Pure JAX (lax.conv); XLA maps convs onto the MXU directly.

Input: (B, 84, 84, frame_stack) uint8 frames, scaled to [0, 1] on device
(the paper's CPU-side preprocessing produces uint8; scaling on device
keeps host->device transfers at 1 byte/pixel — part of the paper's
bus-saturation story).

Two head families extend the seed network for the variant family
(docs/variants.md):

* distributional (C51): ``num_atoms > 1`` sizes every head by
  num_atoms × actions; ``q_logits`` returns the (B, A, K) categorical
  logits and ``q_forward`` their expectation over the fixed support, so
  acting/eval code keeps consuming scalar Q-values;
* noisy (NoisyNet): the post-conv linears become factorized-Gaussian
  noisy layers (``models.layers.noisy_linear``). ``noise_key=None`` is
  the μ-only deterministic path; callers resample by passing fresh keys
  (the concurrent cycle derives them from the cycle RNG).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ExecConfig
from repro.configs.dqn_nature import NatureCNNConfig
from repro.models import params as P
from repro.models.layers import noisy_linear


def _linear_spec(spec: Dict[str, Any], name: str, d_in: int, d_out: int,
                 cfg: NatureCNNConfig, axes=("mlp", None)) -> None:
    """One (possibly noisy) affine layer's leaves: μ always; σ when
    ``cfg.noisy`` (init σ0/√fan_in per Fortunato et al. 2018 §3.2)."""
    spec[f"{name}_w"] = P.Leaf((d_in, d_out), axes, fan_in=d_in)
    spec[f"{name}_b"] = P.Leaf((d_out,), (axes[1],), init="zeros")
    if cfg.noisy:
        sigma = cfg.noisy_sigma0 / float(np.sqrt(d_in))
        spec[f"{name}_w_sigma"] = P.Leaf((d_in, d_out), axes, init="const",
                                         value=sigma)
        spec[f"{name}_b_sigma"] = P.Leaf((d_out,), (axes[1],), init="const",
                                         value=sigma)


def conv_param_spec(spec: Dict[str, Any], convs, frame_size: int,
                    frame_stack: int) -> int:
    """The conv torso's leaves (``conv{i}_w``/``conv{i}_b``) into
    ``spec``; returns the width of its flattened output."""
    in_ch = frame_stack
    size = frame_size
    for i, (out_ch, k, s) in enumerate(convs):
        spec[f"conv{i}_w"] = P.Leaf((k, k, in_ch, out_ch),
                                    (None, None, None, "mlp"),
                                    fan_in=k * k * in_ch)
        spec[f"conv{i}_b"] = P.Leaf((out_ch,), ("mlp",), init="zeros")
        size = (size - k) // s + 1
        in_ch = out_ch
    return size * size * in_ch


def conv_torso(params, frames: jax.Array, convs, cdt) -> jax.Array:
    """(B, H, W, C) uint8 frames -> (B, flat) ReLU conv features."""
    x = frames.astype(cdt) / jnp.asarray(255.0, cdt)
    for i, (_, k, s) in enumerate(convs):
        x = jax.lax.conv_general_dilated(
            x, params[f"conv{i}_w"].astype(cdt), window_strides=(s, s),
            padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + params[f"conv{i}_b"].astype(cdt))
    return x.reshape(x.shape[0], -1)


def q_param_spec(cfg: NatureCNNConfig, n_actions: int) -> Dict[str, Any]:
    spec: Dict[str, Any] = {}
    if cfg.vector_dim:
        # vector mode: fc-only trunk on the stacked state vectors
        flat = cfg.vector_dim * cfg.frame_stack
    else:
        flat = conv_param_spec(spec, cfg.convs, cfg.frame_size,
                               cfg.frame_stack)
    K = cfg.num_atoms
    spec["fc_w"] = P.Leaf((flat, cfg.hidden), (None, "mlp"), fan_in=flat)
    spec["fc_b"] = P.Leaf((cfg.hidden,), ("mlp",), init="zeros")
    if cfg.noisy:
        sigma = cfg.noisy_sigma0 / float(np.sqrt(flat))
        spec["fc_w_sigma"] = P.Leaf((flat, cfg.hidden), (None, "mlp"),
                                    init="const", value=sigma)
        spec["fc_b_sigma"] = P.Leaf((cfg.hidden,), ("mlp",), init="const",
                                    value=sigma)
    if cfg.dueling:
        # dueling heads (Wang et al. 2016): shared trunk, separate state-
        # value and advantage streams; Q = V + (A - mean A). Under C51
        # both streams emit per-atom logits combined before the softmax.
        _linear_spec(spec, "val", cfg.hidden, K, cfg)
        _linear_spec(spec, "adv", cfg.hidden, n_actions * K, cfg)
    else:
        _linear_spec(spec, "out", cfg.hidden, n_actions * K, cfg)
    return spec


def q_init(cfg: NatureCNNConfig, n_actions: int, key: jax.Array):
    return P.init_tree(q_param_spec(cfg, n_actions), key)


def _affine(params, name: str, x: jax.Array, cfg: NatureCNNConfig, cdt,
            noise_key: Optional[jax.Array]) -> jax.Array:
    if cfg.noisy:
        return noisy_linear(x, params[f"{name}_w"].astype(jnp.float32),
                            params[f"{name}_w_sigma"].astype(jnp.float32),
                            params[f"{name}_b"].astype(jnp.float32),
                            params[f"{name}_b_sigma"].astype(jnp.float32),
                            key=noise_key).astype(cdt)
    return x @ params[f"{name}_w"].astype(cdt) + params[f"{name}_b"].astype(cdt)


def _trunk(params, frames: jax.Array, cfg: NatureCNNConfig, cdt,
           noise_key: Optional[jax.Array]) -> jax.Array:
    if cfg.vector_dim:
        # (B, D, K) float32 state vectors, already in [0, 1] — no /255
        x = frames.astype(cdt).reshape(frames.shape[0], -1)
    else:
        x = conv_torso(params, frames, cfg.convs, cdt)
    kfc = jax.random.fold_in(noise_key, 0) if noise_key is not None else None
    return jax.nn.relu(_affine(params, "fc", x, cfg, cdt, kfc))


def q_logits(params, frames: jax.Array, cfg: NatureCNNConfig,
             ec: Optional[ExecConfig] = None,
             noise_key: Optional[jax.Array] = None) -> jax.Array:
    """frames: (B, H, W, C) uint8 -> categorical logits (B, A, K) f32.

    Only meaningful for distributional configs (``num_atoms > 1``); the
    softmax over the last axis is the per-action value distribution on
    the z_j support. ``noise_key`` drives the NoisyNet layers (None =
    μ-only).
    """
    cdt = jnp.float32 if ec is None else ec.cdtype
    x = _trunk(params, frames, cfg, cdt, noise_key)
    K = cfg.num_atoms
    kv = jax.random.fold_in(noise_key, 1) if noise_key is not None else None
    ka = jax.random.fold_in(noise_key, 2) if noise_key is not None else None
    if cfg.dueling:
        v = _affine(params, "val", x, cfg, cdt, kv)            # (B, K)
        a = _affine(params, "adv", x, cfg, cdt, ka)            # (B, A*K)
        a = a.reshape(x.shape[0], -1, K)
        logits = v[:, None, :] + a - jnp.mean(a, axis=1, keepdims=True)
    else:
        logits = _affine(params, "out", x, cfg, cdt, kv).reshape(
            x.shape[0], -1, K)
    return logits.astype(jnp.float32)


def q_forward(params, frames: jax.Array, cfg: NatureCNNConfig,
              ec: Optional[ExecConfig] = None,
              noise_key: Optional[jax.Array] = None) -> jax.Array:
    """frames: (B, H, W, C) uint8 -> Q-values (B, n_actions) float32.

    ``ec`` threads the execution config through the DQN path for parity
    with the LLM stack: it selects the conv/matmul compute dtype.
    ``ec=None`` (and the rl_train launcher default) is f32 — the paper
    trains the Q-network in full precision — so passing a bf16
    ``ExecConfig`` is an explicit opt-in (e.g. frozen-actor inference).
    The kernel-backend request is accepted but resolves to plain XLA on
    every backend: lax.conv already maps straight onto the MXU / cuDNN,
    so the CNN registers no custom kernels (the C51 projection op runs
    in the *loss*, not the network). Distributional configs return the
    expectation Σ softmax(logits)·z so acting stays scalar.
    """
    if cfg.num_atoms > 1:
        logits = q_logits(params, frames, cfg, ec, noise_key)
        z = jnp.linspace(cfg.v_min, cfg.v_max, cfg.num_atoms,
                         dtype=jnp.float32)
        return jnp.sum(jax.nn.softmax(logits, axis=-1) * z, axis=-1)
    cdt = jnp.float32 if ec is None else ec.cdtype
    x = _trunk(params, frames, cfg, cdt, noise_key)
    kv = jax.random.fold_in(noise_key, 1) if noise_key is not None else None
    ka = jax.random.fold_in(noise_key, 2) if noise_key is not None else None
    if cfg.dueling:
        v = _affine(params, "val", x, cfg, cdt, kv)
        a = _affine(params, "adv", x, cfg, cdt, ka)
        q = v + a - jnp.mean(a, axis=-1, keepdims=True)
    else:
        q = _affine(params, "out", x, cfg, cdt, kv)
    return q.astype(jnp.float32)
