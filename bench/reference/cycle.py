"""Plain float32 reference of one replica's training cycle.

The shared reference: every configuration without a
``bench/reference/<config>.py`` of its own runs this one, and such a
module may subclass it (``bench/reference/__init__.py``).

This module imports nothing from the program under test. It rebuilds,
from the configuration file's numbers and the seed alone, what the
program's population trainer computes for one replica:

* the env (``pong`` on a 10x10 grid) and its render, upscaled to the
  84x84 uint8 frame the Nature CNN reads, and the 4-frame stack;
* acting: one batched Q call per round from the target parameters,
  epsilon-greedy per stream (or NoisyNet noise, epsilon 0);
* replay: add with wrap-around, uniform sampling with replacement, and
  proportional prioritized sampling by a sum-tree descent;
* the learner: Huber TD loss or C51 cross-entropy through a
  categorical projection, double-Q bootstrap, dueling and noisy heads,
  centered RMSProp or Adam;
* n-step aggregation of the staged transitions and the flush of staged
  priorities and transitions at the sync point.

The random streams follow the same derivation as the program (one key
per use site, folded from the replica seed and the step counter), so
the reference and the program draw the same actions and minibatches
and a gap between them is a gap in arithmetic.

``dtype`` is float32 for the reference proper. Every matmul and
convolution then runs at ``Precision.HIGHEST``. With ``bfloat16`` the
learner (parameters, optimizer state, activations, losses) runs in
bfloat16: that is the lower-precision control.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


class RefState(NamedTuple):
    params: Dict[str, jax.Array]
    opt: Dict[str, Any]
    replay: Dict[str, jax.Array]
    env: Dict[str, jax.Array]
    stack: jax.Array
    key: jax.Array
    step: jax.Array
    seed: jax.Array


def _key(tag: int, seed, step):
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(tag),
                                                 seed), step)


def _next_pow2(n: int) -> int:
    return 1 << (int(n) - 1).bit_length() if n > 1 else 1


class Reference:
    """One replica of the configuration in ``cfg`` (a configuration
    file's dict, see ``bench/configs``), with W streams and C51 / PER /
    noisy / dueling / double / n-step as the file's variant says."""

    def __init__(self, cfg: Dict[str, Any], envs: int,
                 dtype=jnp.float32):
        spec = cfg["spec"]
        net = cfg["network"]
        consts = cfg["program_constants"]
        self.variant = spec["variant"]
        self.algo = spec["algo"]
        self.sched = spec["schedule"]
        self.consts = consts
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
        self.hidden = int(net["hidden"])
        v = self.variant
        self.atoms = int(v["num_atoms"]) if v["distributional"] else 1
        self.z = jnp.linspace(v["v_min"], v["v_max"], self.atoms,
                              dtype=jnp.float32)
        self.C = int(self.sched["cycle_steps"])
        self.F = int(self.algo["train_period"])
        self.B = int(self.algo["minibatch_size"])
        self.capacity = int(self.algo["replay_capacity"])
        self.rounds = self.C // self.W
        self.updates = max(self.C // self.F, 1)

    # ------------------------------------------------------------------ env

    def env_reset(self, key):
        kx, kd = jax.random.split(key)
        return {
            "ball_x": jax.random.randint(kx, (), 1, self.n - 1),
            "ball_y": jnp.int32(1),
            "dx": jax.random.choice(kd, jnp.array([-1, 1], jnp.int32)),
            "dy": jnp.int32(1),
            "paddle_x": jnp.int32(self.n // 2),
            "t": jnp.int32(0),
        }

    def env_step(self, s, a):
        n = self.n
        paddle = jnp.clip(s["paddle_x"] + a - 1, 0, n - 1)
        nx = s["ball_x"] + s["dx"]
        dx = jnp.where((nx < 0) | (nx >= n), -s["dx"], s["dx"])
        nx = jnp.clip(nx, 0, n - 1)
        ny = s["ball_y"] + s["dy"]
        dy = jnp.where(ny < 0, -s["dy"], s["dy"])
        ny = jnp.clip(ny, 0, n - 1)
        at_bottom = ny >= n - 1
        on_paddle = jnp.abs(nx - paddle) <= self.paddle_half
        bounce = at_bottom & on_paddle
        dy = jnp.where(bounce, -jnp.abs(dy), dy)
        reward = jnp.where(bounce, 1.0, 0.0).astype(jnp.float32)
        done = (at_bottom & ~on_paddle) | (s["t"] >= self.max_steps)
        return ({"ball_x": nx, "ball_y": ny, "dx": dx, "dy": dy,
                 "paddle_x": paddle, "t": s["t"] + 1}, reward, done)

    def env_step_autoreset(self, s, a, key):
        _, kreset = jax.random.split(key)
        ns, reward, done = self.env_step(s, a)
        fresh = self.env_reset(kreset)
        ns = {k: jnp.where(done, fresh[k], ns[k]) for k in ns}
        return ns, reward, done

    def render(self, s):
        """One env state -> (frame, frame) uint8: ball in channel 0
        (weight 1.0), paddle row in channel 1 (weight 0.4), clipped,
        8x nearest upscale with a 2-pixel border at 84."""
        n = self.n
        rows = jnp.arange(n)[:, None]
        cols = jnp.arange(n)[None, :]
        ball = ((rows == s["ball_y"]) & (cols == s["ball_x"]))
        pad = (rows == n - 1) & (jnp.abs(cols - s["paddle_x"])
                                 <= self.paddle_half)
        gray = jnp.clip(ball.astype(jnp.float32)
                        + jnp.float32(0.4) * pad.astype(jnp.float32),
                        0.0, 1.0)
        if self.frame == 84:
            gray = jnp.repeat(jnp.repeat(gray, 8, axis=0), 8, axis=1)
            gray = jnp.pad(gray, ((2, 2), (2, 2)))
        elif self.frame != n:
            raise ValueError(f"frame size {self.frame} for a {n}x{n} grid")
        return (gray * 255.0).astype(jnp.uint8)

    def push(self, stack, frame):
        return jnp.concatenate([stack[..., 1:], frame[..., None]], axis=-1)

    # -------------------------------------------------------------- network

    def param_shapes(self) -> Dict[str, Tuple[Tuple[int, ...], str, float]]:
        """name -> (shape, init, scale): "normal" leaves are scaled by
        1/sqrt(fan_in), "zeros" are zero, "const" hold sigma0/sqrt(fan_in)."""
        v = self.variant
        sig0 = float(v["noisy_sigma0"])
        out: Dict[str, Tuple[Tuple[int, ...], str, float]] = {}

        def linear(name, d_in, d_out):
            out[f"{name}_w"] = ((d_in, d_out), "normal", 1 / np.sqrt(d_in))
            out[f"{name}_b"] = ((d_out,), "zeros", 0.0)
            if v["noisy"]:
                out[f"{name}_w_sigma"] = ((d_in, d_out), "const",
                                          sig0 / float(np.sqrt(d_in)))
                out[f"{name}_b_sigma"] = ((d_out,), "const",
                                          sig0 / float(np.sqrt(d_in)))

        size, ch = self.frame, self.K
        for i, (oc, k, s) in enumerate(self.convs):
            out[f"conv{i}_w"] = ((k, k, ch, oc), "normal",
                                 1 / np.sqrt(k * k * ch))
            out[f"conv{i}_b"] = ((oc,), "zeros", 0.0)
            size = (size - k) // s + 1
            ch = oc
        linear("fc", size * size * ch, self.hidden)
        A, K = self.n_actions, self.atoms
        if v["dueling"]:
            linear("val", self.hidden, K)
            linear("adv", self.hidden, A * K)
        else:
            linear("out", self.hidden, A * K)
        return out

    def init_params(self, key):
        shapes = self.param_shapes()
        names = sorted(shapes)
        keys = jax.random.split(key, len(names))
        params = {}
        for k, name in zip(keys, names):
            shape, init, scale = shapes[name]
            if init == "zeros":
                x = jnp.zeros(shape, jnp.float32)
            elif init == "const":
                x = jnp.full(shape, scale, jnp.float32)
            else:
                x = (jnp.float32(scale)
                     * jax.random.normal(k, shape, jnp.float32))
            params[name] = x.astype(self.dtype)
        return params

    def _affine(self, p, name, x, key):
        w, b = p[f"{name}_w"], p[f"{name}_b"]
        if self.variant["noisy"] and key is not None:
            kin, kout = jax.random.split(key)

            def f(k, m):
                e = jax.random.normal(k, (m,), jnp.float32)
                return jnp.sign(e) * jnp.sqrt(jnp.abs(e))

            ein, eout = f(kin, w.shape[0]), f(kout, w.shape[1])
            w = w + p[f"{name}_w_sigma"] * jnp.outer(ein, eout).astype(
                self.dtype)
            b = b + p[f"{name}_b_sigma"] * eout.astype(self.dtype)
        return jnp.dot(x, w, precision=self.prec) + b

    def logits(self, p, frames, key=None):
        """(B, H, W, K) uint8 -> (B, A, atoms) logits in ``dtype``."""
        dt = self.dtype
        x = frames.astype(dt) / jnp.asarray(255.0, dt)
        for i, (_, _, s) in enumerate(self.convs):
            x = lax.conv_general_dilated(
                x, p[f"conv{i}_w"], (s, s), "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=self.prec)
            x = jax.nn.relu(x + p[f"conv{i}_b"])
        x = x.reshape(x.shape[0], -1)
        sub = (lambda i: None) if key is None else (
            lambda i: jax.random.fold_in(key, i))
        x = jax.nn.relu(self._affine(p, "fc", x, sub(0)))
        A, K = self.n_actions, self.atoms
        if self.variant["dueling"]:
            val = self._affine(p, "val", x, sub(1))
            adv = self._affine(p, "adv", x, sub(2)).reshape(-1, A, K)
            return val.reshape(-1, 1, K) + adv - jnp.mean(adv, axis=1,
                                                          keepdims=True)
        return self._affine(p, "out", x, sub(1)).reshape(-1, A, K)

    def q_values(self, p, frames, key=None):
        lg = self.logits(p, frames, key)
        if self.atoms > 1:
            return jnp.sum(jax.nn.softmax(lg.astype(jnp.float32), axis=-1)
                           * self.z, axis=-1)
        return lg[..., 0].astype(jnp.float32)

    # ------------------------------------------------------------- sampler

    def act_round(self, q_fn, env, stack, key, eps):
        """One synchronized round of W streams."""
        key, kact, kstep = jax.random.split(key, 3)
        q = q_fn(stack)

        def egreedy(q_row, k):
            kr, ka = jax.random.split(k)
            rand = jax.random.randint(ka, (), 0, q_row.shape[-1])
            explore = jax.random.uniform(kr, ()) < eps
            return jnp.where(explore, rand,
                             jnp.argmax(q_row)).astype(jnp.int32)

        actions = jax.vmap(egreedy)(q, jax.random.split(kact, self.W))
        env, rewards, dones = jax.vmap(self.env_step_autoreset)(
            env, actions, jax.random.split(kstep, self.W))
        frame = jax.vmap(self.render)(env)
        next_obs = self.push(stack, frame)
        kept = jnp.where(dones[:, None, None, None], 0, stack).astype(
            stack.dtype)
        new_stack = self.push(kept, frame)
        tr = {"obs": stack, "action": actions, "reward": rewards,
              "next_obs": next_obs, "done": dones}
        return env, new_stack, key, tr

    def nstep(self, staged):
        n = int(self.variant["n_step"])
        if n <= 1:
            return staged
        gamma = float(self.algo["discount"])
        R = staged["reward"].shape[0] - n + 1
        reward = jnp.zeros_like(staged["reward"][:R])
        live = jnp.ones_like(reward)
        done = jnp.zeros_like(staged["done"][:R])
        for k in range(n):
            reward = reward + (gamma ** k) * live * staged["reward"][k:k + R]
            done = done | staged["done"][k:k + R]
            live = live * (1.0 - staged["done"][k:k + R].astype(live.dtype))
        return {"obs": staged["obs"][:R], "action": staged["action"][:R],
                "reward": reward, "next_obs": staged["next_obs"][n - 1:],
                "done": done}

    # -------------------------------------------------------------- replay

    def replay_init(self):
        cap, f, K = self.capacity, self.frame, self.K
        r = {"obs": jnp.zeros((cap, f, f, K), jnp.uint8),
             "action": jnp.zeros((cap,), jnp.int32),
             "reward": jnp.zeros((cap,), jnp.float32),
             "next_obs": jnp.zeros((cap, f, f, K), jnp.uint8),
             "done": jnp.zeros((cap,), jnp.bool_),
             "cursor": jnp.int32(0), "size": jnp.int32(0)}
        if self.variant["prioritized"]:
            r["priority"] = jnp.zeros((_next_pow2(cap),), jnp.float32)
            r["max_priority"] = jnp.float32(1.0)
        return r

    def replay_add(self, r, staged):
        flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in staged.items()}
        cap = self.capacity
        n = flat["action"].shape[0]
        if n > cap:
            flat = {k: v[n - cap:] for k, v in flat.items()}
        idx = (r["cursor"] + jnp.arange(min(n, cap), dtype=jnp.int32)
               + max(n - cap, 0)) % cap
        out = dict(r)
        for k in ("obs", "action", "reward", "next_obs", "done"):
            out[k] = r[k].at[idx].set(flat[k].astype(r[k].dtype))
        if "priority" in r:
            out["priority"] = r["priority"].at[idx].set(r["max_priority"])
        out["cursor"] = (r["cursor"] + n) % cap
        out["size"] = jnp.minimum(r["size"] + n, cap)
        return out

    @staticmethod
    def sum_tree(leaves):
        levels = [leaves]
        while levels[-1].shape[0] > 1:
            levels.append(levels[-1].reshape(-1, 2).sum(axis=1))
        return jnp.concatenate([jnp.zeros((1,), jnp.float32)] + levels[::-1])

    @staticmethod
    def tree_descend(tree, targets):
        P = tree.shape[0] // 2
        idx = jnp.ones(targets.shape, jnp.int32)
        t = targets
        for _ in range(P.bit_length() - 1):
            left = tree[2 * idx]
            go_left = t < left
            idx = jnp.where(go_left, 2 * idx, 2 * idx + 1)
            t = jnp.where(go_left, t, t - left)
        return idx - P

    def sample(self, r, key, tree, beta):
        fields = ("obs", "action", "reward", "next_obs", "done")
        if tree is None:
            idx = jax.random.randint(key, (self.B,), 0,
                                     jnp.maximum(r["size"], 1))
            return {k: r[k][idx] for k in fields}
        total = tree[1]
        size = jnp.maximum(r["size"], 1)
        u = jax.random.uniform(key, (self.B,))
        targets = (jnp.arange(self.B, dtype=jnp.float32) + u) / self.B * total
        idx = jnp.minimum(self.tree_descend(tree, targets), size - 1)
        probs = jnp.maximum(r["priority"][idx] / jnp.maximum(total, 1e-30),
                            1e-30)
        w = (size.astype(jnp.float32) * probs) ** (-beta)
        w = w / jnp.maximum(jnp.max(w), 1e-30)
        out = {k: r[k][idx] for k in fields}
        out["index"] = idx
        out["weight"] = w
        return out

    # ------------------------------------------------------------- learner

    def project(self, p_t, rewards, dones, gamma_n):
        """C51 projection of ``p_t`` (B, K) onto the fixed support."""
        v = self.variant
        K = self.atoms
        vmin, vmax = float(v["v_min"]), float(v["v_max"])
        delta = (vmax - vmin) / (K - 1)
        z = vmin + delta * jnp.arange(K, dtype=jnp.float32)
        tz = jnp.clip(rewards[:, None]
                      + gamma_n * (1.0 - dones[:, None]) * z[None, :],
                      vmin, vmax)
        b = (tz - vmin) / delta
        low = jnp.floor(b)
        frac = b - low
        li = low.astype(jnp.int32)
        ui = jnp.minimum(li + 1, K - 1)
        # one-hot form of the two-sided split (whole mass on l at integer b)
        atoms = jnp.arange(K)
        m = (jnp.sum(p_t[:, :, None] * (1.0 - frac)[:, :, None]
                     * (li[:, :, None] == atoms), axis=1)
             + jnp.sum(p_t[:, :, None] * frac[:, :, None]
                       * (ui[:, :, None] == atoms), axis=1))
        return m

    def loss(self, params, target, batch, noise_key):
        v = self.variant
        dt = self.dtype
        if noise_key is None:
            call = lambda fn, p, o, i: fn(p, o)  # noqa: E731
        else:
            call = lambda fn, p, o, i: fn(  # noqa: E731
                p, o, jax.random.fold_in(noise_key, i))
        gamma_n = float(self.algo["discount"]) ** int(v["n_step"])
        rewards = batch["reward"].astype(dt)
        dones = batch["done"].astype(dt)
        act = batch["action"]
        if self.atoms > 1:
            lg = call(self.logits, params, batch["obs"], 0)
            logp = jax.nn.log_softmax(lg, axis=-1)
            logp_a = jnp.take_along_axis(logp, act[:, None, None],
                                         axis=1)[:, 0]
            tprobs = jax.nn.softmax(call(self.logits, target,
                                         batch["next_obs"], 1), axis=-1)
            z = self.z.astype(dt)
            if v["double"]:
                on = jax.nn.softmax(call(self.logits, params,
                                         batch["next_obs"], 2), axis=-1)
                q_next = jnp.sum(on * z, axis=-1)
            else:
                q_next = jnp.sum(tprobs * z, axis=-1)
            a_star = jnp.argmax(q_next, axis=-1)
            p_t = jnp.take_along_axis(tprobs, a_star[:, None, None],
                                      axis=1)[:, 0]
            m = lax.stop_gradient(self.project(lax.stop_gradient(p_t),
                                               rewards, dones, gamma_n))
            per = -jnp.sum(m * logp_a, axis=-1)
        else:
            q = call(self.logits, params, batch["obs"], 0)[..., 0]
            qa = jnp.take_along_axis(q, act[:, None], axis=1)[:, 0]
            q_next = call(self.logits, target, batch["next_obs"], 1)[..., 0]
            if v["double"]:
                on = call(self.logits, params, batch["next_obs"], 2)[..., 0]
                boot = jnp.take_along_axis(
                    q_next, jnp.argmax(on, axis=-1)[:, None], axis=1)[:, 0]
            else:
                boot = jnp.max(q_next, axis=-1)
            y = rewards + gamma_n * jnp.where(batch["done"], 0.0, boot)
            td = lax.stop_gradient(y) - qa
            per = jnp.where(jnp.abs(td) <= 1.0, 0.5 * td * td,
                            jnp.abs(td) - 0.5)
            per_abs = jnp.abs(td)
        if "weight" in batch:
            loss = jnp.mean(batch["weight"].astype(dt) * per)
        else:
            loss = jnp.mean(per)
        signal = per if self.atoms > 1 else per_abs
        return loss, lax.stop_gradient(signal)

    def opt_init(self, params):
        zeros = lambda: {k: jnp.zeros_like(v) for k, v in params.items()}  # noqa: E731
        if self.algo["optimizer"] == "rmsprop":
            return {"s": zeros(), "g": zeros()}
        return {"m": zeros(), "v": zeros(), "step": jnp.int32(0)}

    def opt_update(self, grads, st, params):
        c = self.consts
        lr = float(self.algo["learning_rate"])
        dt = self.dtype
        if self.algo["optimizer"] == "rmsprop":
            d, eps = c["rmsprop_decay"], c["rmsprop_eps"]
            s = {k: d * st["s"][k] + (1 - d) * g * g for k, g in grads.items()}
            m = {k: d * st["g"][k] + (1 - d) * g for k, g in grads.items()}
            new = {k: params[k] - lr * grads[k]
                   / jnp.sqrt(s[k] - m[k] * m[k] + jnp.asarray(eps, dt))
                   for k in params}
            return new, {"s": s, "g": m}
        b1, b2, eps = c["adam_b1"], c["adam_b2"], c["adam_eps"]
        step = st["step"] + 1
        clip = c["adam_grad_clip"]
        if clip:
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

    def update(self, params, target, opt, batch, noise_key):
        (loss, signal), grads = jax.value_and_grad(self.loss, has_aux=True)(
            params, target, batch, noise_key)
        params, opt = self.opt_update(grads, opt, params)
        return params, opt, loss, signal

    # ---------------------------------------------------------------- run

    def _epsilon(self, step):
        c = self.consts
        frac = jnp.clip(step.astype(jnp.float32)
                        / float(self.algo["eps_anneal_steps"]), 0.0, 1.0)
        return c["eps_start"] + (c["eps_end"] - c["eps_start"]) * frac

    def init(self, seed) -> RefState:
        seed = jnp.asarray(seed, jnp.int32)
        kinit, ksampler = jax.random.split(jax.random.PRNGKey(seed))
        params = self.init_params(kinit)
        kreset, kstate = jax.random.split(ksampler)
        env = jax.vmap(self.env_reset)(jax.random.split(kreset, self.W))
        stack = jnp.zeros((self.W, self.frame, self.frame, self.K), jnp.uint8)
        stack = self.push(stack, jax.vmap(self.render)(env))
        n_step = int(self.variant["n_step"])
        prepop = int(self.sched["prepopulate"])
        rounds = max(-(-prepop // self.W), 1) + n_step - 1
        zero_q = lambda obs: jnp.zeros((obs.shape[0], self.n_actions))  # noqa: E731

        def body(c, _):
            env, stack, key = c
            env, stack, key, tr = self.act_round(zero_q, env, stack, key,
                                                 jnp.float32(1.0))
            return (env, stack, key), tr

        (env, stack, key), staged = lax.scan(body, (env, stack, kstate),
                                             None, length=rounds)
        replay = self.replay_add(self.replay_init(), self.nstep(staged))
        return RefState(params, self.opt_init(params), replay, env, stack,
                        key, jnp.int32(0), seed)

    def cycle(self, st: RefState):
        v = self.variant
        target = st.params
        snap = st.replay
        noisy = bool(v["noisy"])
        k_act = _key(23, st.seed, st.step) if noisy else None
        q_act = lambda obs: self.q_values(target, obs, k_act)  # noqa: E731

        def act_body(c, i):
            env, stack, key = c
            eps = (jnp.float32(0.0) if noisy
                   else self._epsilon(st.step + i * self.W))
            env, stack, key, tr = self.act_round(q_act, env, stack, key, eps)
            return (env, stack, key), tr

        (env, stack, key), staged = lax.scan(
            act_body, (st.env, st.stack, st.key), jnp.arange(self.rounds))

        keys = jax.random.split(_key(17, st.seed, st.step), self.updates)
        per = bool(v["prioritized"])
        if per:
            tree = self.sum_tree(snap["priority"])
            beta = jnp.minimum(1.0, v["per_beta0"] + (1.0 - v["per_beta0"])
                               * st.step.astype(jnp.float32)
                               / v["per_beta_anneal_steps"])
        else:
            tree, beta = None, None

        def train_body(c, k):
            params, opt, pending = c
            if noisy:
                ks, kn = jax.random.split(k)
            else:
                ks, kn = k, None
            batch = self.sample(snap, ks, tree, beta)
            params, opt, loss, signal = self.update(params, target, opt,
                                                    batch, kn)
            if per:
                mass = ((jnp.abs(signal.astype(jnp.float32)) + v["per_eps"])
                        ** v["per_alpha"])
                pending = pending.at[batch["index"]].max(mass)
            return (params, opt, pending), loss

        pending0 = (jnp.zeros_like(snap["priority"]) if per
                    else jnp.zeros((1,), jnp.float32))
        (params, opt, pending), losses = lax.scan(
            train_body, (st.params, st.opt, pending0), keys)

        replay = st.replay
        if per:
            replay = dict(replay)
            replay["priority"] = jnp.where(pending > 0, pending,
                                           replay["priority"])
            replay["max_priority"] = jnp.maximum(replay["max_priority"],
                                                 jnp.max(pending))
        replay = self.replay_add(replay, self.nstep(staged))
        new = RefState(params, opt, replay, env, stack, key,
                       st.step + self.C, st.seed)
        return new, jnp.mean(losses.astype(jnp.float32))
