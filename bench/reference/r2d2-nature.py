"""Plain float32 reference of one R2D2 replica's training cycle.

The ``r2d2-nature`` configuration's own reference
(``bench/reference/__init__.py`` gives the contract). It subclasses the
shared ``cycle.py`` for the env, its render and the sum-tree descent,
and replaces the rest with R2D2 (Kapturowski et al. 2019), from the
configuration file's numbers and the seed alone:

* the network: the Nature conv torso, an LSTM fed the torso's output
  with the previous action (one-hot) and tanh of the previous reward,
  and a dueling head with one hidden layer per stream;
* acting: W streams, each with its LSTM state, previous action and
  previous reward, all zeroed where an episode starts, and a fixed
  Ape-X epsilon per stream; prepopulation acts with epsilon 1;
* replay: a ring of steps per stream (frame, action, reward, done), a
  sequence start every ``sequence_period`` steps holding the LSTM state
  and a priority; a start can be drawn while its 3 frames of history
  and its ``span`` steps are all in the ring;
* the learner: stratified proportional draws of sequences, a burn-in of
  both networks from the stored state without gradient, n-step double-Q
  targets under value rescaling, cut at episode ends and at the end of
  the sequence, the sum over the trained steps of half the squared TD
  error weighted by the importance weights, Adam, and the sequence
  priority eta * max|d| + (1 - eta) * mean|d|;
* the flush: the staged priorities, then the staged steps.

The random streams follow the program's derivation (one key per use
site, folded from the replica seed and the step counter), so both draw
the same actions and sequences. The frame stacks of a sequence are
rebuilt by replaying the actor's own stack rule over its window, one
step after another. It runs one period of acting and one update at a
time, so it fits on a chip that holds nothing else.
"""

from __future__ import annotations

from pathlib import Path
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import cells

Shared = cells.shared_reference(Path(__file__).resolve().parents[1])

# Ape-X's per-actor epsilon: 0.4^(1 + 7 i / (N - 1)) (Horgan et al. 2018)
APEX_ALPHA = 7.0


class R2D2State(NamedTuple):
    params: Dict[str, jax.Array]
    target: Dict[str, jax.Array]
    opt: Dict[str, Any]
    replay: Dict[str, jax.Array]
    env: Dict[str, jax.Array]
    stack: jax.Array
    core: Tuple[jax.Array, jax.Array, jax.Array, jax.Array]
    key: jax.Array
    step: jax.Array
    seed: jax.Array


def _key(tag: int, seed, step):
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(tag),
                                                 seed), step)


class Reference(Shared):

    def __init__(self, cfg: Dict[str, Any], envs: int, dtype=jnp.float32):
        spec, net = cfg["spec"], cfg["network"]
        self.consts = cfg["program_constants"]
        self.variant, self.algo = spec["variant"], spec["algo"]
        self.sched = spec["schedule"]
        self.W = envs
        self.dtype = jnp.dtype(dtype)
        self.prec = (lax.Precision.HIGHEST if self.dtype == jnp.float32
                     else lax.Precision.DEFAULT)
        env_params = spec.get("env_params", {})
        self.n = int(env_params.get("size", 10))
        self.paddle_half = int(env_params.get("paddle_width", 3)) // 2
        self.max_steps = int(env_params.get("max_steps", 0)) or 50 * self.n
        self.n_actions = 3
        self.frame = int(net["frame_size"])
        self.K = int(net["frame_stack"])
        self.convs = [tuple(c) for c in net["convs"]]
        self.H = int(net["lstm"])
        self.D = int(net["dueling_hidden"])
        v = self.variant
        self.burn = int(v["burn_in"])
        self.m = int(v["sequence_length"])
        self.period = int(v["sequence_period"])
        self.span = self.burn + self.m + 1
        self.hist = self.K - 1
        self.C = int(self.sched["cycle_steps"])
        self.B = int(self.algo["minibatch_size"])
        self.updates = max(self.C // int(self.algo["train_period"]), 1)
        self.slots = int(self.algo["replay_capacity"]) // envs
        self.ring = self.slots * self.period
        self.leaves = 1 << (self.slots * envs - 1).bit_length()
        i = np.arange(envs, dtype=np.float64) / max(envs - 1, 1)
        self.stream_eps = jnp.asarray(
            float(v["actor_eps"]) ** (1.0 + APEX_ALPHA * i), jnp.float32)

    # -------------------------------------------------------------- network

    def param_shapes(self):
        out = {}
        size, ch = self.frame, self.K
        for i, (oc, k, s) in enumerate(self.convs):
            out[f"conv{i}_w"] = ((k, k, ch, oc), "normal",
                                 1 / np.sqrt(k * k * ch))
            out[f"conv{i}_b"] = ((oc,), "zeros", 0.0)
            size = (size - k) // s + 1
            ch = oc
        d_in = size * size * ch + self.n_actions + 1
        H, D = self.H, self.D
        out["lstm_wx"] = ((d_in, 4 * H), "normal", 1 / np.sqrt(d_in))
        out["lstm_wh"] = ((H, 4 * H), "normal", 1 / np.sqrt(H))
        out["lstm_b"] = ((4 * H,), "zeros", 0.0)
        for name, d_out in (("val", 1), ("adv", self.n_actions)):
            out[f"{name}_fc_w"] = ((H, D), "normal", 1 / np.sqrt(H))
            out[f"{name}_fc_b"] = ((D,), "zeros", 0.0)
            out[f"{name}_w"] = ((D, d_out), "normal", 1 / np.sqrt(D))
            out[f"{name}_b"] = ((d_out,), "zeros", 0.0)
        return out

    def _dot(self, a, b):
        return jnp.dot(a, b, precision=self.prec)

    def gates_in(self, p, frames, prev_a, prev_r):
        """(N, H, W, K) uint8 stacks and the previous action and reward
        -> the LSTM's input projection (N, 4H)."""
        dt = self.dtype
        x = frames.astype(dt) / jnp.asarray(255.0, dt)
        for i, (_, _, s) in enumerate(self.convs):
            x = lax.conv_general_dilated(
                x, p[f"conv{i}_w"], (s, s), "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=self.prec)
            x = jax.nn.relu(x + p[f"conv{i}_b"])
        x = x.reshape(x.shape[0], -1)
        x = jnp.concatenate([
            x, jax.nn.one_hot(prev_a, self.n_actions, dtype=dt),
            jnp.tanh(prev_r.astype(dt))[:, None]], axis=-1)
        return self._dot(x, p["lstm_wx"]) + p["lstm_b"]

    def lstm(self, p, gx, h, c):
        z = gx + self._dot(h, p["lstm_wh"])
        H = self.H
        i, f = z[..., :H], z[..., H:2 * H]
        g, o = z[..., 2 * H:3 * H], z[..., 3 * H:]
        bias = self.consts["lstm_forget_bias"]
        c = jax.nn.sigmoid(f + bias) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        return jax.nn.sigmoid(o) * jnp.tanh(c), c

    def q_head(self, p, h):
        def stream(name):
            x = jax.nn.relu(self._dot(h, p[f"{name}_fc_w"])
                            + p[f"{name}_fc_b"])
            return self._dot(x, p[f"{name}_w"]) + p[f"{name}_b"]
        v, a = stream("val"), stream("adv")
        return v + a - jnp.mean(a, axis=-1, keepdims=True)

    # -------------------------------------------------------------- acting

    def zero_core(self):
        z = jnp.zeros((self.W, self.H), self.dtype)
        return (z, z, jnp.zeros((self.W,), jnp.int32),
                jnp.zeros((self.W,), jnp.float32))

    def act_step(self, params, env, stack, core, key, eps):
        """One synchronized round of W streams: the net's step on each
        stream's stack and state, an epsilon-greedy action per stream,
        the env step, and the next stack and state (zero where an
        episode ended)."""
        key, kact, kstep = jax.random.split(key, 3)
        h, c, prev_a, prev_r = core
        h, c = self.lstm(params, self.gates_in(params, stack, prev_a,
                                               prev_r), h, c)
        q = self.q_head(params, h).astype(jnp.float32)

        def egreedy(q_row, e, k):
            kr, ka = jax.random.split(k)
            rand = jax.random.randint(ka, (), 0, q_row.shape[-1])
            explore = jax.random.uniform(kr, ()) < e
            return jnp.where(explore, rand, jnp.argmax(q_row)).astype(
                jnp.int32)

        eps = jnp.broadcast_to(eps, (self.W,))
        actions = jax.vmap(egreedy)(q, eps, jax.random.split(kact, self.W))
        env, rewards, dones = jax.vmap(self.env_step_autoreset)(
            env, actions, jax.random.split(kstep, self.W))
        step = {"frame": stack[..., -1], "action": actions,
                "reward": rewards, "done": dones}
        frame = jax.vmap(self.render)(env)
        stack = self.push(jnp.where(dones[:, None, None, None], 0, stack)
                          .astype(stack.dtype), frame)
        live = ~dones
        core = (jnp.where(live[:, None], h, 0.0),
                jnp.where(live[:, None], c, 0.0),
                jnp.where(live, actions, 0), jnp.where(live, rewards, 0.0))
        return env, stack, core, key, step

    def act(self, params, env, stack, core, key, eps, periods):
        """``periods`` periods of rounds: the staged steps (R, W) and the
        LSTM state at each period's start (periods, W, H)."""
        def one_round(carry, _):
            env, stack, core, key = carry
            env, stack, core, key, step = self.act_step(
                params, env, stack, core, key, eps)
            return (env, stack, core, key), step

        def one_period(carry, _):
            start = {"h": carry[2][0], "c": carry[2][1]}
            carry, steps = lax.scan(one_round, carry, None,
                                    length=self.period)
            return carry, (steps, start)

        carry, (steps, starts) = lax.scan(one_period,
                                          (env, stack, core, key), None,
                                          length=periods)
        steps = {k: v.reshape((-1,) + v.shape[2:]) for k, v in steps.items()}
        return carry, steps, starts

    # -------------------------------------------------------------- replay

    def replay_init(self):
        W, L, S, f = self.W, self.ring, self.slots, self.frame
        return {"frame": jnp.zeros((W, L, f, f), jnp.uint8),
                "action": jnp.zeros((W, L), jnp.int32),
                "reward": jnp.zeros((W, L), jnp.float32),
                "done": jnp.zeros((W, L), jnp.bool_),
                "h": jnp.zeros((W, S, self.H), self.dtype),
                "c": jnp.zeros((W, S, self.H), self.dtype),
                "start": jnp.full((S,), -1, jnp.int32),
                "priority": jnp.zeros((self.leaves,), jnp.float32),
                "max_priority": jnp.float32(1.0),
                "steps": jnp.int32(0)}

    def drawable(self, start, steps):
        return ((start >= 0) & (start + self.span <= steps)
                & (start - self.hist >= steps - self.ring))

    def replay_add(self, r, steps, starts):
        R = steps["action"].shape[0]
        t0 = r["steps"]
        t1 = t0 + R
        pos = (t0 + jnp.arange(R)) % self.ring
        out = dict(r)
        for k in ("frame", "action", "reward", "done"):
            vals = jnp.swapaxes(steps[k], 0, 1)             # (W, R, ...)
            out[k] = r[k].at[:, pos].set(vals.astype(r[k].dtype))
        p_starts = t0 + self.period * jnp.arange(R // self.period)
        slot = (p_starts // self.period) % self.slots
        for k in ("h", "c"):
            out[k] = r[k].at[:, slot].set(
                jnp.swapaxes(starts[k], 0, 1).astype(r[k].dtype))
        start = r["start"].at[slot].set(p_starts)
        ok0 = self.drawable(r["start"], t0)
        ok1 = self.drawable(start, t1)
        same = ok0 & (start == r["start"])
        prio = r["priority"][:self.W * self.slots].reshape(self.W,
                                                           self.slots)
        prio = jnp.where(ok1[None], jnp.where(same[None], prio,
                                              r["max_priority"]), 0.0)
        out["priority"] = r["priority"].at[:self.W * self.slots].set(
            prio.reshape(-1))
        out["start"] = start
        out["steps"] = t1
        return out

    def window(self, r, w, slot):
        """One sequence's window of ``hist + span`` ring entries, its
        stacks rebuilt by the actor's rule, its inputs and its stored
        state."""
        s = r["start"][slot]
        pos = (s - self.hist + jnp.arange(self.hist + self.span)) % self.ring
        frame, action = r["frame"][w, pos], r["action"][w, pos]
        reward, done = r["reward"][w, pos], r["done"][w, pos]

        def rebuild(stack, x):
            f, ended_before = x
            stack = self.push(jnp.where(ended_before, 0, stack)
                              .astype(stack.dtype), f)
            return stack, stack

        ended_before = jnp.concatenate([jnp.zeros((1,), jnp.bool_),
                                        done[:-1]])
        zero = jnp.zeros((self.frame, self.frame, self.K), jnp.uint8)
        _, stacks = lax.scan(rebuild, zero, (frame, ended_before))
        hs = self.hist
        first = done[hs - 1:hs - 1 + self.span]
        return {"obs": stacks[hs:],
                "prev_action": jnp.where(first, 0,
                                         action[hs - 1:hs - 1 + self.span]),
                "prev_reward": jnp.where(first, 0.0,
                                         reward[hs - 1:hs - 1 + self.span]),
                "first": first,
                "action": action[hs:], "reward": reward[hs:],
                "done": done[hs:],
                "h": r["h"][w, slot], "c": r["c"][w, slot]}

    def sample(self, r, key, tree, beta):
        total = tree[1]
        size = jnp.maximum(self.W * jnp.sum(self.drawable(r["start"],
                                                          r["steps"])), 1)
        u = jax.random.uniform(key, (self.B,))
        targets = (jnp.arange(self.B, dtype=jnp.float32) + u) / self.B * total
        idx = jnp.minimum(self.tree_descend(tree, targets),
                          self.W * self.slots - 1)
        probs = jnp.maximum(r["priority"][idx] / jnp.maximum(total, 1e-30),
                            1e-30)
        w = (size.astype(jnp.float32) * probs) ** (-beta)
        batch = jax.vmap(lambda i: self.window(r, i // self.slots,
                                               i % self.slots))(idx)
        batch["weight"] = w / jnp.maximum(jnp.max(w), 1e-30)
        batch["index"] = idx
        return batch

    # -------------------------------------------------------------- learner

    def unroll(self, p, batch, lo, hi, h, c):
        """Q-values (B, hi - lo, A) of steps [lo, hi) from state (h, c)."""
        obs = batch["obs"][:, lo:hi]
        B, T = obs.shape[:2]
        gx = self.gates_in(p, obs.reshape((B * T,) + obs.shape[2:]),
                           batch["prev_action"][:, lo:hi].reshape(-1),
                           batch["prev_reward"][:, lo:hi].reshape(-1))
        gx = gx.reshape(B, T, -1)

        def step(hc, x):
            g, first = x
            h, c = (jnp.where(first[:, None], 0.0, y) for y in hc)
            h, c = self.lstm(p, g, h, c)
            return (h, c), h

        (h, c), hs = lax.scan(step, (h, c), (jnp.swapaxes(gx, 0, 1),
                                             batch["first"][:, lo:hi].T))
        return jnp.swapaxes(self.q_head(p, hs), 0, 1), (h, c)

    def rescale(self, x):
        e = float(self.variant["value_rescale_eps"])
        return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + e * x

    def rescale_inv(self, x):
        e = float(self.variant["value_rescale_eps"])
        return jnp.sign(x) * (((jnp.sqrt(1.0 + 4.0 * e * (jnp.abs(x) + 1.0
                                                         + e)) - 1.0)
                               / (2.0 * e)) ** 2 - 1.0)

    def loss(self, params, target, batch):
        dt = self.dtype
        L, m, n = self.burn, self.m, int(self.variant["n_step"])
        gamma = float(self.algo["discount"])
        _, state = self.unroll(lax.stop_gradient(params), batch, 0, L,
                               batch["h"], batch["c"])
        state = lax.stop_gradient(state)
        q, _ = self.unroll(params, batch, L, self.span, *state)
        qt, _ = self.unroll(target, batch, 0, self.span, batch["h"],
                            batch["c"])
        qt = qt[:, L:]
        q_next = lax.stop_gradient(q)
        reward = batch["reward"][:, L:].astype(dt)
        done = batch["done"][:, L:]
        tds = []
        for t in range(m):
            ret = jnp.zeros((q.shape[0],), dt)
            live = jnp.ones((q.shape[0],), jnp.bool_)
            k_end = min(n, m - t)
            for k in range(k_end):
                ret = ret + jnp.where(live, gamma ** k * reward[:, t + k], 0.0)
                live = live & ~done[:, t + k]
            a_star = jnp.argmax(q_next[:, t + k_end], axis=-1)
            boot = jnp.take_along_axis(qt[:, t + k_end], a_star[:, None],
                                       axis=-1)[:, 0]
            y = self.rescale(ret + jnp.where(
                live, gamma ** k_end * self.rescale_inv(boot), 0.0))
            qa = jnp.take_along_axis(q[:, t], batch["action"][:, L + t, None],
                                     axis=-1)[:, 0]
            tds.append(lax.stop_gradient(y) - qa)
        td = jnp.stack(tds, axis=1)                             # (B, m)
        per_seq = jnp.sum(0.5 * td * td, axis=1)
        loss = jnp.mean(batch["weight"].astype(dt) * per_seq)
        a = jnp.abs(lax.stop_gradient(td)).astype(jnp.float32)
        eta = float(self.variant["priority_eta"])
        prio = eta * jnp.max(a, axis=1) + (1.0 - eta) * jnp.mean(a, axis=1)
        return loss, prio

    def opt_init(self, params):
        zeros = lambda: {k: jnp.zeros_like(v) for k, v in params.items()}  # noqa: E731
        return {"m": zeros(), "v": zeros(), "step": jnp.int32(0)}

    def opt_update(self, grads, st, params):
        a = self.algo
        lr, b2, eps = (float(a["learning_rate"]), float(a["adam_b2"]),
                       float(a["adam_eps"]))
        b1 = float(self.consts["adam_b1"])
        dt = self.dtype
        step = st["step"] + 1
        if float(a["adam_grad_clip"]):
            clip = float(a["adam_grad_clip"])
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                for g in grads.values()))
            scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
            grads = {k: g * scale.astype(dt) for k, g in grads.items()}
        m = {k: b1 * st["m"][k] + (1 - b1) * g for k, g in grads.items()}
        v = {k: b2 * st["v"][k] + (1 - b2) * g * g for k, g in grads.items()}
        bc1 = (1 - b1 ** step.astype(jnp.float32)).astype(dt)
        bc2 = (1 - b2 ** step.astype(jnp.float32)).astype(dt)
        new = {k: params[k] - lr * ((m[k] / bc1)
                                    / (jnp.sqrt(v[k] / bc2) + eps))
               for k in params}
        return new, {"m": m, "v": v, "step": step}

    # ----------------------------------------------------------------- run

    def init(self, seed) -> R2D2State:
        seed = jnp.asarray(seed, jnp.int32)
        kinit, ksampler = jax.random.split(jax.random.PRNGKey(seed))
        params = self.init_params(kinit)
        kreset, kstate = jax.random.split(ksampler)
        env = jax.vmap(self.env_reset)(jax.random.split(kreset, self.W))
        stack = jnp.zeros((self.W, self.frame, self.frame, self.K), jnp.uint8)
        stack = self.push(stack, jax.vmap(self.render)(env))
        periods = int(self.sched["prepopulate"]) // (self.W * self.period)
        (env, stack, core, key), steps, starts = self.act(
            params, env, stack, self.zero_core(), kstate, jnp.float32(1.0),
            periods)
        replay = self.replay_add(self.replay_init(), steps, starts)
        return R2D2State(params, params, self.opt_init(params), replay, env,
                         stack, core, key, jnp.int32(0), seed)

    def cycle(self, st: R2D2State):
        v = self.variant
        every = int(self.sched["target_cycles"])
        sync = (st.step // self.C) % every == 0
        target = {k: jnp.where(sync, st.params[k], st.target[k])
                  for k in st.params}
        snap = st.replay
        (env, stack, core, key), steps, starts = self.act(
            st.params, st.env, st.stack, st.core, st.key, self.stream_eps,
            self.C // (self.W * self.period))

        tree = self.sum_tree(snap["priority"])
        beta = jnp.float32(v["per_beta0"])

        def train_body(c, k):
            params, opt, pending = c
            batch = self.sample(snap, k, tree, beta)
            (loss, prio), grads = jax.value_and_grad(self.loss, has_aux=True)(
                params, target, batch)
            params, opt = self.opt_update(grads, opt, params)
            mass = (prio + v["per_eps"]) ** v["per_alpha"]
            pending = pending.at[batch["index"]].max(mass)
            return (params, opt, pending), loss

        keys = jax.random.split(_key(17, st.seed, st.step), self.updates)
        (params, opt, pending), losses = lax.scan(
            train_body, (st.params, st.opt, jnp.zeros_like(snap["priority"])),
            keys)
        replay = dict(st.replay)
        replay["priority"] = jnp.where(pending > 0, pending,
                                       replay["priority"])
        replay["max_priority"] = jnp.maximum(replay["max_priority"],
                                             jnp.max(pending))
        replay = self.replay_add(replay, steps, starts)
        new = R2D2State(params, target, opt, replay, env, stack, core, key,
                        st.step + self.C, st.seed)
        return new, jnp.mean(losses.astype(jnp.float32))
