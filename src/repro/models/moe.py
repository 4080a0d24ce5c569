"""Mixture-of-experts MLP with top-k routing.

Three dispatch implementations (ExecConfig.moe_impl):

* ``scatter`` (baseline): tokens scatter into per-expert capacity buffers
  ``(B, E, cap, d)`` grouped *per batch row*, so routing positions never
  cross the data-sharded batch axis; experts run as one batched SwiGLU
  matmul (MXU-friendly); results gather back weighted by router probs.
  Token dropping at capacity (Switch/GShard semantics).

* ``expert_parallel`` (§Perf optimized): ``shard_map`` over the mesh —
  expert weight stacks are sharded over the `model` axis (padded to
  ``moe.pad_to`` when n_experts doesn't divide it, e.g. qwen's 60 -> 64);
  activations are replicated over `model`, so each rank dispatches only
  to its local experts with **zero dispatch communication**, computes its
  partial output, and a single psum over `model` combines — the same
  collective shape as a Megatron MLP instead of per-expert all-reduces.

* ``dense`` (oracle/tests): every expert computes every token; exact
  (no drops). The scatter path must match it under high capacity.

Router runs in float32. Aux losses: Switch load-balance + ST-MoE z-loss.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig, MoEConfig
from repro.config import ExecConfig
from repro.models import params as PM


def padded_experts(m: MoEConfig) -> int:
    return max(m.pad_to, m.n_experts)


def moe_param_spec(cfg: ModelConfig) -> Dict[str, PM.Leaf]:
    m = cfg.moe
    d, f = cfg.d_model, cfg.d_ff
    E = padded_experts(m)
    spec = {
        "router": PM.Leaf((d, m.n_experts), ("embed", "experts_logits"), fan_in=d),
        "w_gate": PM.Leaf((E, d, f), ("experts", "embed", "expert_mlp"), fan_in=d),
        "w_up": PM.Leaf((E, d, f), ("experts", "embed", "expert_mlp"), fan_in=d),
        "w_down": PM.Leaf((E, f, d), ("experts", "expert_mlp", "embed"), fan_in=f),
    }
    if m.n_shared_experts:
        fs = f * m.n_shared_experts
        spec["shared_gate"] = PM.Leaf((d, fs), ("embed", "mlp"), fan_in=d)
        spec["shared_up"] = PM.Leaf((d, fs), ("embed", "mlp"), fan_in=d)
        spec["shared_down"] = PM.Leaf((fs, d), ("mlp", "embed"), fan_in=fs)
    return spec


def _router(x32: jax.Array, w: jax.Array, m: MoEConfig):
    """x32: (T, d) float32 -> top-k weights/ids + aux losses."""
    logits = x32 @ w.astype(jnp.float32)                     # (T, E_logical)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)             # (T, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    T = x32.shape[0]
    counts = jnp.zeros((m.n_experts,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    f_e = counts / (T * m.top_k)
    p_e = jnp.mean(probs, axis=0)
    lb_loss = m.n_experts * jnp.sum(f_e * p_e)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = m.load_balance_loss * lb_loss + m.router_z_loss * z_loss
    return top_w, top_e, aux


def _dispatch_row(xs, es, n_experts: int, cap: int, top_k: int):
    """xs: (S, d); es: (S, k) -> buffer (E, cap, d) + gather metadata.
    Positions via one-hot cumsum; over-capacity assignments dropped."""
    S, d = xs.shape
    e_flat = es.reshape(-1)                                   # (S*k,)
    onehot = (e_flat[:, None] == jnp.arange(n_experts)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                 # exclusive
    p_flat = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]
    keep = (p_flat < cap).astype(xs.dtype)
    slot = jnp.minimum(p_flat, cap - 1)
    tok = jnp.repeat(jnp.arange(S), top_k)
    buf = jnp.zeros((n_experts, cap, d), xs.dtype)
    buf = buf.at[e_flat, slot].add(xs[tok] * keep[:, None])
    return buf, (e_flat, slot, keep, tok)


def _gather_row(ob, ws, meta, S: int, d: int):
    e_flat, slot, keep, tok = meta
    y_flat = ob[e_flat, slot] * keep[:, None]                 # (S*k, d)
    w_flat = ws.reshape(-1).astype(ob.dtype)
    return jnp.zeros((S, d), ob.dtype).at[tok].add(y_flat * w_flat[:, None])


def _experts_swiglu(p, buf: jax.Array) -> jax.Array:
    """buf: (..., E, cap, d) -> same; batched per-expert SwiGLU."""
    dt = buf.dtype
    g = jnp.einsum("...ecd,edf->...ecf", buf, p["w_gate"].astype(dt))
    u = jnp.einsum("...ecd,edf->...ecf", buf, p["w_up"].astype(dt))
    return jnp.einsum("...ecf,efd->...ecd", jax.nn.silu(g) * u,
                      p["w_down"].astype(dt))


def _scatter_moe(p, x, top_w, top_e, m: MoEConfig):
    B, S, d = x.shape
    E = padded_experts(m)
    cap = int(m.capacity_factor * S * m.top_k / m.n_experts)
    cap = max(8, (cap + 7) // 8 * 8)
    tw = top_w.reshape(B, S, m.top_k)
    te = top_e.reshape(B, S, m.top_k)
    buf, meta = jax.vmap(
        lambda xs, es: _dispatch_row(xs, es, E, cap, m.top_k))(x, te)
    out = _experts_swiglu(p, buf)                             # (B,E,cap,d)
    y = jax.vmap(lambda ob, ws, mt: _gather_row(ob, ws, mt, S, d))(out, tw, meta)
    return y.reshape(B * S, d)


def _expert_parallel_moe(p, x, cfg: ModelConfig, m: MoEConfig):
    """shard_map expert parallelism. x: (B, S, d) with batch sharded over
    (pod?, data) and replicated over `model`; expert stacks sharded over
    `model`. Each rank dispatches to its local experts only and a single
    psum over `model` combines partial outputs."""
    mesh = jax.sharding.get_abstract_mesh()
    axes = mesh.axis_names
    bspec = tuple(a for a in ("pod", "data") if a in axes)
    B, S, d = x.shape
    E = padded_experts(m)
    msize = mesh.shape["model"]
    E_loc = E // msize
    cap = int(m.capacity_factor * S * m.top_k / m.n_experts)
    cap = max(8, (cap + 7) // 8 * 8)

    def local_fn(xr, router_w, wg, wu, wd):
        # xr: (B_loc, S, d) — replicated over model; w*: (E_loc, d, f)
        Bl = xr.shape[0]
        xt = xr.reshape(Bl * S, d)
        top_w, top_e, aux = _router(xt.astype(jnp.float32), router_w, m)
        ridx = jax.lax.axis_index("model")
        e_local = top_e - ridx * E_loc
        mine = (e_local >= 0) & (e_local < E_loc)
        te = jnp.where(mine, e_local, E_loc)          # E_loc = drop bucket
        tw = jnp.where(mine, top_w, 0.0)
        te_r = te.reshape(Bl, S, m.top_k)
        tw_r = tw.reshape(Bl, S, m.top_k)
        buf, meta = jax.vmap(
            lambda xs, es: _dispatch_row(xs, es, E_loc + 1, cap, m.top_k)
        )(xr, te_r)
        lp = {"w_gate": wg, "w_up": wu, "w_down": wd}
        out = _experts_swiglu(lp, buf[:, :E_loc])     # drop bucket unused
        out = jnp.concatenate(
            [out, jnp.zeros_like(out[:, :1])], axis=1)
        y = jax.vmap(lambda ob, ws, mt: _gather_row(ob, ws, mt, S, d))(
            out, tw_r, meta)
        # combine partial outputs in compute dtype: halves the all-reduce
        # payload vs f32 (§Perf qwen iteration 2)
        y = jax.lax.psum(y.astype(xr.dtype), "model")
        if bspec:
            aux = jax.lax.pmean(aux, bspec)
        return y, aux

    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False)(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y.reshape(B * S, d), aux


def moe_ffn(p, x: jax.Array, cfg: ModelConfig, ec: ExecConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss)."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)

    ep_ok = False
    if ec.moe_impl == "expert_parallel":
        mesh = jax.sharding.get_abstract_mesh()
        ep_ok = (not mesh.empty
                 and "model" in mesh.axis_names
                 and padded_experts(m) % mesh.shape["model"] == 0)
    if ep_ok:
        y, aux = _expert_parallel_moe(p, x, cfg, m)
    else:
        top_w, top_e, aux = _router(xt.astype(jnp.float32), p["router"], m)
        if ec.moe_impl == "dense":
            E = padded_experts(m)
            g = jnp.einsum("td,edf->etf", xt, p["w_gate"].astype(xt.dtype))
            u = jnp.einsum("td,edf->etf", xt, p["w_up"].astype(xt.dtype))
            y_all = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u,
                               p["w_down"].astype(xt.dtype))
            onehot = jax.nn.one_hot(top_e, E, dtype=xt.dtype)     # (T,k,E)
            w_e = jnp.einsum("tk,tke->te", top_w.astype(xt.dtype), onehot)
            y = jnp.einsum("te,etd->td", w_e, y_all)
        else:
            y = _scatter_moe(p, x, top_w, top_e, m)

    if m.n_shared_experts:
        g = xt @ p["shared_gate"].astype(xt.dtype)
        u = xt @ p["shared_up"].astype(xt.dtype)
        y = y + (jax.nn.silu(g) * u) @ p["shared_down"].astype(xt.dtype)

    return y.reshape(B, S, d), aux
