"""Smoke run of the main path on a TPU, one process, in phases.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the population fleet on four chips

With no option it needs one TPU chip and runs, in order:

  (a) device check: exits non-zero unless JAX's first device is a TPU;
  (b) both Mosaic kernels at real shapes against their XLA references:
      ``segment_tree`` over 16,384 and 2**20 leaves (integer masses, so
      the two must agree exactly) and ``categorical_projection`` at
      B=32, K=51 (within ``PROJ_ATOL``); ``auto`` must resolve to mosaic;
  (c) training through ``repro.launch.rl_train.main``: the Nature 84x84x4
      spec (examples/specs/dqn_nature84.json) in population mode, as dqn
      and as rainbow, 3 cycles each, checkpointed under ``--out``; the
      loss and the eval return must be finite and the step counter must
      read 3 x 512;
  (d) serving the dqn checkpoint through ``load_policy``/``make_server``:
      a few hundred requests from ``SimulatedClients``, then greedy
      actions checked against the evaluator's choices round by round.

``--chips 4`` runs only the population path across chips: a P=4 dqn
fleet at the same geometry on a 4-device replica mesh for 2 cycles,
each replica compared with the standalone seeds=1 run of its seed on
one chip (within ``FLEET_ATOL``), and the carry's placement printed.

Times printed here are smoke timings, not benchmark numbers. Any failed
check raises, so the process exits non-zero. The last line of standard
output is one JSON object naming the device; it is printed only when
every phase passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SPEC = os.path.join(ROOT, "examples", "specs", "dqn_nature84.json")

PROJ_ATOL = 1e-5      # categorical_projection mosaic vs ref, per entry
FLEET_ATOL = 1e-5     # fleet replica vs standalone run, float leaves
SMOKE_CYCLES = 3
FLEET_CYCLES = 2


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------

def device_check(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke FAILED: no TPU found (JAX's first device is "
            f"{d.platform!r}, {d.device_kind!r}); this script has no CPU "
            "fallback")
    log(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, "
          f"JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class CompileCounter:
    """Process-wide backend-compile seconds and persistent-cache hits."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# (b) kernels
# ---------------------------------------------------------------------------

def kernels_phase():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import backend as kb
    from repro.kernels import ops, ref
    from repro.kernels.segment_tree import tree_build

    for op in ("segment_tree", "categorical_projection"):
        b = kb.choose(op, "auto")
        log(f"[kernels] choose({op!r}, 'auto') = {b}")
        check(b == kb.MOSAIC, f"{op} resolves to {b!r} on a TPU, "
              "expected 'mosaic'")

    for P in (16_384, 1 << 20):
        kp, kt = jax.random.split(jax.random.PRNGKey(P))
        # integer masses, total < 2**24: every prefix sum is exact
        pri = jax.random.randint(kp, (P,), 0, 8).astype(jnp.float32)
        pri = pri.at[0].set(3.0)
        tree = tree_build(pri)
        total = int(tree[1])
        t = jax.random.randint(kt, (512,), 0, total).astype(jnp.float32)
        t = jnp.minimum(t + 0.5, total - 0.25)
        got = np.asarray(ops.segment_tree_sample(tree, t,
                                                 backend=kb.MOSAIC))
        want = np.asarray(ref.segment_tree_sample(tree, t))
        n_diff = int((got != want).sum())
        log(f"[kernels] segment_tree P={P} n=512: mosaic vs ref "
            f"{n_diff} mismatches")
        check(n_diff == 0, f"segment_tree P={P}: {n_diff} indices differ")
        check(bool((np.asarray(pri)[got] > 0).all()),
              f"segment_tree P={P} sampled a zero-mass leaf")

    kp, kr, kd = jax.random.split(jax.random.PRNGKey(51), 3)
    probs = jax.nn.softmax(jax.random.normal(kp, (32, 51)), axis=-1)
    rewards = 3.0 * jax.random.normal(kr, (32,))
    dones = (jax.random.uniform(kd, (32,)) < 0.3).astype(jnp.float32)
    args = (probs, rewards, dones, -10.0, 10.0, 0.99 ** 3)
    got = np.asarray(ops.categorical_projection(*args, backend=kb.MOSAIC))
    want = np.asarray(ops.categorical_projection(*args, backend=kb.REF))
    err = float(np.abs(got - want).max())
    mass = float(np.abs(got.sum(-1) - 1.0).max())
    log(f"[kernels] categorical_projection B=32 K=51: max|mosaic-ref| "
        f"{err:.3g} (limit {PROJ_ATOL}), max|mass-1| {mass:.3g}")
    check(err <= PROJ_ATOL, f"categorical_projection differs by {err}")
    check(mass <= PROJ_ATOL, f"categorical_projection loses mass {mass}")


# ---------------------------------------------------------------------------
# (c) training through the launcher
# ---------------------------------------------------------------------------

def train_phase(variant: str, out: str, spec_path: str = SPEC) -> str:
    """rl_train.main for SMOKE_CYCLES cycles; returns the checkpoint dir."""
    from repro.launch import rl_train

    run = os.path.join(out, variant)
    ckpt = os.path.join(run, "ckpt")
    metrics = os.path.join(run, "metrics.jsonl")
    trace = os.path.join(run, "trace.jsonl")
    with open(spec_path) as f:
        cycle_steps = json.load(f)["schedule"]["cycle_steps"]
    argv = ["--spec", spec_path, "--mode", "population",
            "--cycles", str(SMOKE_CYCLES), "--eval-every",
            str(SMOKE_CYCLES), "--ckpt-dir", ckpt,
            "--metrics-jsonl", metrics, "--trace", trace]
    if variant != "dqn":
        argv += ["--variant", variant]
    log(f"[train:{variant}] rl_train {' '.join(argv)}")
    t0 = time.perf_counter()
    rc = rl_train.main(argv)
    wall = time.perf_counter() - t0
    check(rc == 0, f"rl_train exited {rc} for {variant}")

    with open(metrics) as f:
        rows = [json.loads(line) for line in f]
    check(len(rows) == SMOKE_CYCLES, f"{variant}: {len(rows)} metric rows, "
          f"expected {SMOKE_CYCLES}")
    for r in rows:
        check(math.isfinite(r["loss"]),
              f"{variant}: loss {r['loss']} at cycle {r['cycle']}")
        check(r["step"] == r["cycle"] * cycle_steps,
              f"{variant}: step {r['step']} after cycle {r['cycle']}, "
              f"expected {r['cycle'] * cycle_steps}")
    check("eval" in rows[-1] and math.isfinite(rows[-1]["eval"]),
          f"{variant}: eval return {rows[-1].get('eval')}")

    with open(trace) as f:
        spans = [json.loads(line) for line in f]
    cyc = [s["dur"] / 1e6 for s in spans
           if s.get("t") == "span" and s["name"] == "cycle"]
    check(len(cyc) == SMOKE_CYCLES, f"{variant}: {len(cyc)} cycle spans")
    log(f"[train:{variant}] ok: steps {rows[-1]['step']}/replica, "
        f"losses {[round(r['loss'], 6) for r in rows]}, "
        f"eval {rows[-1]['eval']:+.3f}")
    log(f"[train:{variant}] smoke timing (not a benchmark): first cycle "
        f"{cyc[0]:.3f} s incl. compile, median of later cycles "
        f"{statistics.median(cyc[1:]):.3f} s, rl_train wall {wall:.1f} s")
    return ckpt


# ---------------------------------------------------------------------------
# (d) serving
# ---------------------------------------------------------------------------

def serve_phase(ckpt: str, clients: int = 64, ticks: int = 4,
                rounds: int = 4):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.api.policy_client import SimulatedClients, drive
    from repro.api.serve import ServeSpec, load_policy, make_server
    from repro.core.policy import stream_keys
    from repro.core.synchronized import SamplerState, sync_round
    from repro.envs import make_env
    from repro.envs.preprocess import init_obs_stack, obs_batch, push_frame

    loaded = load_policy(ckpt)
    check(not loaded.skipped, f"torn checkpoints: {loaded.skipped}")
    server = make_server(loaded, ServeSpec(policy="greedy",
                                           max_batch=clients))
    t0 = time.perf_counter()
    n_prog = server.warm_start(clients)
    log(f"[serve] step {loaded.step}: warm start compiled {n_prog} bucket "
        f"programs in {time.perf_counter() - t0:.1f} s")

    stats = drive(server, SimulatedClients(loaded.spec, clients, seed=1),
                  ticks)
    check(stats["actions"] == clients * ticks, f"served {stats['actions']}")
    log(f"[serve] {stats['actions']} requests from {clients} simulated "
        f"clients; smoke timing (not a benchmark): "
        f"{stats['actions_per_s']:.0f} actions/s, p50 "
        f"{stats['p50_ms']:.2f} ms, p99 {stats['p99_ms']:.2f} ms")

    # the evaluator's round-by-round choices (sync_round at eps=0) against
    # the server fed the same raw frames: the tests' serving contract
    spec, pipe = loaded.spec, loaded.pipe
    env = make_env(spec.env, **spec.env_params)
    step = jax.jit(lambda p, s: sync_round(env, loaded.q_forward, p, s,
                                           jnp.float32(0.0), pipe))
    render = jax.jit(lambda st: obs_batch(pipe, env, st))
    kinit, krun = jax.random.split(jax.random.PRNGKey(7))
    states = jax.vmap(env.reset)(jax.random.split(kinit, clients))
    stack = push_frame(init_obs_stack(clients, pipe, loaded.frame_stack),
                       render(states))
    s = SamplerState(states, stack, krun)
    ids = [10_000 + i for i in range(clients)]      # fresh streams
    first = np.ones((clients,), bool)
    mismatches = 0
    for _ in range(rounds):
        frame = np.asarray(render(s.env_states))
        kact = jax.random.split(s.key, 3)[1]
        server.submit_many(ids, frame, first)
        acts = server.flush(keys=np.asarray(stream_keys(kact, clients)))
        s, tr = step(loaded.params, s)
        served = np.array([acts[i] for i in ids], np.int32)
        mismatches += int((served != np.asarray(tr["action"])).sum())
        first = np.asarray(tr["done"])
    log(f"[serve] greedy actions vs evaluate's: {mismatches} mismatches "
        f"in {rounds * clients} requests")
    check(mismatches == 0, f"{mismatches} served actions differ from "
          "the evaluator's")


# ---------------------------------------------------------------------------
# --chips 4: the population fleet across chips
# ---------------------------------------------------------------------------

def fleet_phase(chips: int, spec_path: str = SPEC):
    import jax
    import numpy as np
    from repro.api import ExperimentSpec, build_trainer

    with open(spec_path) as f:
        base = ExperimentSpec.from_json(f.read())
    base = dataclasses.replace(base, mode="population")

    def run(spec):
        tr = build_trainer(spec)
        carry = tr.init_carry()
        for _ in range(FLEET_CYCLES):
            carry, m = tr.cycle(carry)
        jax.block_until_ready(carry)
        return carry, m

    t0 = time.perf_counter()
    fleet, m = run(dataclasses.replace(base, seed=0, seeds=chips))
    log(f"[fleet] P={chips} fleet, {FLEET_CYCLES} cycles: "
        f"{time.perf_counter() - t0:.1f} s incl. compile (smoke timing); "
        f"loss {np.asarray(m['loss']).tolist()}")
    paths, leaves = zip(*jax.tree_util.tree_flatten_with_path(fleet)[0])
    names = [jax.tree_util.keystr(p) for p in paths]
    placements = {}
    for name, leaf in zip(names, leaves):
        ids = tuple(sorted(d.id for d in leaf.devices()))
        placements.setdefault(ids, []).append(name)
    for ids, ns in placements.items():
        log(f"[fleet] {len(ns)} carry leaves on devices {list(ids)}")
    check(list(placements) == [tuple(range(chips))],
          f"fleet carry is not spread over all {chips} devices")
    host = jax.device_get(leaves)

    worst = {n: 0.0 for n in names}
    for r in range(chips):
        solo, _ = run(dataclasses.replace(base, seed=r, seeds=1))
        solo_leaves = jax.device_get(jax.tree.leaves(solo))
        for n, a, b in zip(names, host, solo_leaves):
            a = np.asarray(a[r], np.float64)
            b = np.asarray(b[0], np.float64)
            worst[n] = max(worst[n], float(np.abs(a - b).max(initial=0.0)))
    for (n, d), leaf in zip(worst.items(), host):
        exact = not np.issubdtype(np.asarray(leaf).dtype, np.floating)
        limit = 0.0 if exact else FLEET_ATOL
        log(f"[fleet] max|replica - standalone| {n}: {d:.3g} "
            f"(limit {limit})")
        check(d <= limit, f"replica leaf {n} differs by {d}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the population fleet across chips")
    ap.add_argument("--out", default=os.path.join(ROOT, "runs",
                                                  "chip_smoke"),
                    help="checkpoints, metrics and traces (replaced)")
    args = ap.parse_args(argv)

    device = device_check(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache
    log(f"[cache] persistent compile cache: {compile_cache.enable()}")
    compiles = CompileCounter()
    t0 = time.perf_counter()

    if args.chips == 4:
        fleet_phase(args.chips)
    else:
        kernels_phase()
        if os.path.exists(args.out):
            shutil.rmtree(args.out)
        dqn_ckpt = train_phase("dqn", args.out)
        train_phase("rainbow", args.out)
        serve_phase(dqn_ckpt)

    log(f"[summary] all phases passed in {time.perf_counter() - t0:.1f} s; "
        f"backend compile {compiles.seconds:.1f} s, persistent-cache hits "
        f"{compiles.hits}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
