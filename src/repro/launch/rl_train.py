"""The paper's experiment, driven by a declarative `ExperimentSpec`.

  # everything from one committed spec file (repro.api)
  PYTHONPATH=src python -m repro.launch.rl_train \
      --spec examples/specs/rainbow_fleet.json

  # the same run from flags (flags override spec fields; no --spec
  # means overriding the built-in default spec)
  PYTHONPATH=src python -m repro.launch.rl_train --env catch --cycles 60 \
      --envs 8 --frame-size 10

  # a 4-seed fleet with checkpoint/resume and per-replica metrics
  PYTHONPATH=src python -m repro.launch.rl_train --env pong --seeds 4 \
      --ckpt-dir runs/pong --metrics-jsonl runs/pong/metrics.jsonl --resume

  # a whole sweep (base spec x axis grid) from one manifest; --resume
  # skips completed runs and restores partial fleets bitwise
  PYTHONPATH=src python -m repro.launch.rl_train \
      --sweep examples/specs/catch_lr_seeds_sweep.json --resume

This launcher is a thin shim over ``repro.api``: it resolves
(spec file → flag overrides) into one `ExperimentSpec`, builds the
trainer through ``build_trainer`` (the single construction path shared
with `launch/dryrun.py --arch dqn` and `benchmarks/table4_learning.py`)
and drives the uniform `Trainer` protocol. ``--print-spec`` emits the
fully-resolved spec as canonical JSON — commit that file and the run is
reproducible from it alone.

``--mode`` selects the execution strategy
(baseline/synchronized/concurrent/population; see
docs/experiment_api.md). The default ``population`` vmaps the
concurrent cycle over ``--seeds`` replicas seeded [--seed, --seed + P)
and shards them over visible devices (core/population.py); a --seeds P
fleet is bitwise-equal per replica to P independent --seeds 1 runs
(tests/test_population.py). --ckpt-dir checkpoints the full carry every
--ckpt-every cycles and stores the resolved spec beside it; --resume
restarts from the latest checkpoint bitwise-identically to the
uninterrupted run, and fails with a field-level spec diff when the
requested spec no longer matches the stored one. --metrics-jsonl
appends one JSON line per (cycle, replica).

--frame-size 84 uses the exact Nature-CNN input geometry (84x84x4).
The optimizer defaults to AdamW for fast convergence on the JAX envs;
--optimizer rmsprop (alias --paper-optimizer) selects Mnih's centered
RMSProp (2.5e-4), faithful but tuned for 200M-frame Atari budgets —
--optimizer overrides the spec's choice in either direction.

--variant {dqn,double,dueling,per,c51,noisy,rainbow_lite,rainbow}
selects the off-policy variant preset (configs/dqn_nature.VARIANTS;
matrix in docs/variants.md). --dryrun shrinks everything to a few
seconds for the CI variant smoke job.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.api import (ExperimentSpec, SpecCompatError, SweepSpec,
                       build_trainer, check_resume_compat, load_run_spec,
                       run_sweep, save_run_spec)
from repro import compile_cache
from repro.api.spec import MODES
from repro.configs.dqn_nature import VARIANTS, get_variant
from repro.checkpoint import (latest_step, restore_latest, save_checkpoint,
                              trim_metrics_jsonl)
from repro.telemetry import chrome_path_for, make_tracer


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    # Spec-level I/O. Flags below override spec fields; their argparse
    # defaults are all None so "not given" is distinguishable from an
    # explicit value (the spec file — or the ExperimentSpec defaults —
    # win for omitted flags).
    ap.add_argument("--spec", default=None, metavar="FILE",
                    help="ExperimentSpec JSON to start from "
                         "(repro.api; flags override its fields)")
    ap.add_argument("--sweep", default=None, metavar="FILE",
                    help="SweepSpec manifest (base spec x axis grid; "
                         "docs/sweeps.md): expand, pack same-except-seed "
                         "runs into shared fleets, run them all; "
                         "--ckpt-dir overrides the manifest's root dir, "
                         "--resume continues a partial sweep")
    ap.add_argument("--print-spec", action="store_true",
                    help="print the fully-resolved spec as canonical "
                         "JSON and exit (commit it, re-run with --spec)")
    ap.add_argument("--mode", default=None, choices=list(MODES),
                    help="execution strategy (docs/experiment_api.md)")
    ap.add_argument("--env", default=None)
    ap.add_argument("--cycles", type=int, default=None)
    ap.add_argument("--cycle-steps", type=int, default=None)
    ap.add_argument("--envs", type=int, default=None)
    ap.add_argument("--env-param", action="append", default=None,
                    metavar="KEY=VALUE",
                    help="static EnvParams override, repeatable "
                         "(e.g. --env-param size=16 --env-param "
                         "paddle_width=5); invalid names/values fail "
                         "listing the game's valid ranges")
    ap.add_argument("--obs-mode", default=None,
                    choices=["pixels", "vector"],
                    help="what one observation is: rendered uint8 "
                         "frames or the env's float32 state vector")
    ap.add_argument("--frame-size", type=int, default=None, choices=[10, 84])
    ap.add_argument("--optimizer", default=None,
                    choices=["adamw", "rmsprop"],
                    help="override the spec's optimizer either way")
    ap.add_argument("--paper-optimizer", action="store_true",
                    help="Mnih's centered RMSProp instead of AdamW "
                         "(alias for --optimizer rmsprop)")
    ap.add_argument("--eval-every", type=int, default=None)
    ap.add_argument("--prepopulate", type=int, default=None)
    ap.add_argument("--seed", type=int, default=None,
                    help="base replica seed (replica r runs seed+r)")
    ap.add_argument("--seeds", type=int, default=None,
                    help="population size P: the concurrent cycle is "
                         "vmapped over P replicas and sharded over "
                         "visible devices (core/population.py)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint the full carry here (the resolved "
                         "spec is stored beside the checkpoints)")
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="cycles between checkpoints (with --ckpt-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir "
                         "(bitwise-identical to the uninterrupted run)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append per-(cycle, replica) metrics as JSON lines")
    ap.add_argument("--variant", default=None, choices=sorted(VARIANTS),
                    help="off-policy variant preset (configs/dqn_nature)")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["auto", "pallas", "interpret", "ref",
                             "mosaic", "triton"],
                    help="segment-tree kernel request for PER variants "
                         "(REPRO_KERNEL_BACKEND env var overrides)")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="record a phase trace: JSONL to FILE plus a "
                         "Chrome/Perfetto twin beside it (summarize "
                         "with launch/trace_report.py; with --sweep, "
                         "any value enables per-run traces under "
                         "runs/<id>/trace.jsonl)")
    ap.add_argument("--dryrun", action="store_true",
                    help="one tiny cycle per stage (CI variant smoke)")
    ap.add_argument("--compute-dtype", default=None,
                    choices=["float32", "bfloat16"],
                    help="Q-network compute dtype (paper default f32; "
                         "bf16 halves actor-inference bandwidth)")
    return ap.parse_args(argv)


def _parse_env_params(pairs):
    """--env-param KEY=VALUE list -> dict (numbers parsed as JSON)."""
    if not pairs:
        return None
    out = {}
    for p in pairs:
        if "=" not in p:
            raise ValueError(
                f"--env-param expects KEY=VALUE, got {p!r}")
        k, v = p.split("=", 1)
        try:
            out[k] = json.loads(v)
        except ValueError:
            out[k] = v
    return out


def resolve_spec(args) -> ExperimentSpec:
    """(spec file or defaults) + flag overrides -> one ExperimentSpec."""
    if args.spec:
        with open(args.spec) as f:
            spec = ExperimentSpec.from_json(f.read())
    else:
        spec = ExperimentSpec()

    def sub(section, **kw):
        changed = {k: v for k, v in kw.items() if v is not None}
        if not changed:
            return section
        return dataclasses.replace(section, **changed)

    top = {k: v for k, v in {
        "mode": args.mode, "env": args.env, "envs": args.envs,
        "env_params": _parse_env_params(args.env_param),
        "obs_mode": args.obs_mode,
        "frame_size": args.frame_size, "seed": args.seed,
        "seeds": args.seeds,
        "variant": get_variant(args.variant) if args.variant else None,
    }.items() if v is not None}
    spec = dataclasses.replace(
        spec, **top,
        schedule=sub(spec.schedule, cycles=args.cycles,
                     cycle_steps=args.cycle_steps,
                     prepopulate=args.prepopulate,
                     eval_every=args.eval_every),
        algo=sub(spec.algo,
                 optimizer=args.optimizer or
                 ("rmsprop" if args.paper_optimizer else None)),
        checkpoint=sub(spec.checkpoint, dir=args.ckpt_dir,
                       every=args.ckpt_every),
        metrics=sub(spec.metrics, jsonl=args.metrics_jsonl),
        exec=sub(spec.exec, compute_dtype=args.compute_dtype,
                 kernel_backend=args.kernel_backend))

    if args.dryrun:
        spec = dataclasses.replace(
            spec, envs=4,
            schedule=dataclasses.replace(spec.schedule, cycles=2,
                                         cycle_steps=32, prepopulate=64,
                                         eval_every=2))
    return spec


def run_sweep_cli(args) -> int:
    """--sweep FILE: load the manifest and hand off to the sweep runner
    (repro.api.sweep). Prints one summary line the CI smoke job greps:
    resume idempotence means a second --resume pass reports trained=0."""
    if args.spec:
        print("--sweep and --spec are mutually exclusive (the manifest "
              "carries its own base spec)", flush=True)
        return 2
    try:
        with open(args.sweep) as f:
            sweep = SweepSpec.from_json(f.read())
        results = run_sweep(sweep, resume=args.resume,
                            root=args.ckpt_dir or None,
                            trace=bool(args.trace))
    except (SpecCompatError, ValueError) as e:
        print(f"sweep failed: {e}", flush=True)
        return 2
    trained = sum(1 for r in results if not r["skipped"])
    skipped = len(results) - trained
    print(f"SWEEP OK runs={len(results)} trained={trained} "
          f"skipped={skipped}", flush=True)
    return 0


def main(argv=None):
    args = parse_args(argv)
    if args.sweep:
        return run_sweep_cli(args)
    try:
        spec = resolve_spec(args)
    except ValueError as e:
        print(f"invalid arguments: {e}", flush=True)
        return 2
    if args.print_spec:
        print(spec.to_json(), end="")
        return 0
    try:
        # unknown envs / bad EnvParams / net-obs mismatches fail here
        # with the valid games and param ranges listed (repro.api.spec)
        spec.validate()
    except ValueError as e:
        print(f"invalid spec: {e}", flush=True)
        return 2

    # With --trace FILE the tracer writes JSONL + a Chrome/Perfetto twin;
    # without it this is a counter-only tracer (zero writes) so the
    # throughput lines below work on every run. Tracing is host-side
    # only — a traced run is bitwise-identical to an untraced one
    # (tests/test_telemetry.py).
    tracer = make_tracer(args.trace, meta={
        "kind": "rl_train", "env": spec.env, "mode": spec.mode,
        "variant": spec.variant.name, "seeds": spec.seeds,
        "cycles": spec.schedule.cycles,
        "cycle_steps": spec.schedule.cycle_steps})

    with tracer.span("init", phase="build_trainer"):
        trainer = build_trainer(spec)
    sched = spec.schedule
    ckpt_dir = spec.checkpoint.dir
    P = trainer.replicas
    seeds_host = [spec.seed + r for r in range(P)]

    start_cycle = 0
    carry = None
    last = (latest_step(ckpt_dir) if args.resume and ckpt_dir else None)
    if last is not None:
        try:
            stored = load_run_spec(ckpt_dir)
            if stored is not None:
                check_resume_compat(stored, spec)
        except SpecCompatError as e:
            print(f"cannot resume {ckpt_dir}: {e}", flush=True)
            return 2
    if ckpt_dir:
        # before any expensive init: refuses to overwrite a different
        # run's spec while its checkpoints still sit beside it
        try:
            save_run_spec(ckpt_dir, spec)
        except SpecCompatError as e:
            print(f"refusing to reuse {ckpt_dir}: {e}", flush=True)
            return 2
    if last is not None:
        # restore needs only the carry's tree *structure*, so build the
        # template abstractly — no param init, no prepopulate scan.
        # A torn checkpoint (crash mid-save on an old layout, partial
        # copy, disk-full) is skipped with a warning and the walk falls
        # back to the newest step that still restores.
        with tracer.span("init", phase="restore"):
            step, carry, skipped = restore_latest(ckpt_dir,
                                                  trainer.init_template())
        for s in skipped:
            print(f"WARNING: skipped unrestorable checkpoint {s}",
                  flush=True)
        if carry is not None:
            start_cycle = step
            print(f"resumed {ckpt_dir} at cycle {step}", flush=True)
        else:
            print(f"no restorable checkpoint in {ckpt_dir}; "
                  "starting fresh", flush=True)
    if carry is None:
        with tracer.span("init", phase="init_carry"):
            carry = trainer.init_carry()
            if tracer.enabled:
                tracer.fence(carry)

    metrics_f = None
    if spec.metrics.jsonl:
        os.makedirs(os.path.dirname(spec.metrics.jsonl) or ".",
                    exist_ok=True)
        if os.path.exists(spec.metrics.jsonl):
            trim_metrics_jsonl(spec.metrics.jsonl, start_cycle)
        metrics_f = open(spec.metrics.jsonl, "a", buffering=1)

    def emit(i, m, evals=None):
        if metrics_f is None:
            return
        # one bulk device->host transfer per cycle, not 6 per replica
        mh = jax.device_get(m)
        steps = jax.device_get(trainer.steps(carry))
        evh = None if evals is None else jax.device_get(evals)
        for r in range(P):
            row = {"cycle": i + 1, "env": spec.env, "mode": spec.mode,
                   "variant": spec.variant.name,
                   "seed": seeds_host[r], "step": int(steps[r]),
                   "loss": float(mh["loss"][r]),
                   "reward": float(mh["reward"][r]),
                   "episodes": float(mh["episodes"][r])}
            if evh is not None:
                row["eval"] = float(evh[r])
            metrics_f.write(json.dumps(row) + "\n")

    t0 = time.time()
    win_t, win_counters = t0, tracer.counters
    try:
        with tracer.span("train", start_cycle=start_cycle,
                         cycles=sched.cycles):
            for i in range(start_cycle, sched.cycles):
                with tracer.span("cycle", index=i + 1):
                    carry, m = trainer.cycle(carry)
                    if tracer.enabled:
                        tracer.fence(m)
                tracer.count("cycles", 1)
                tracer.count("env_steps", P * sched.cycle_steps)
                evals = None
                if (i + 1) % sched.eval_every == 0 or i == sched.cycles - 1:
                    with tracer.span("eval", index=i + 1):
                        evals = trainer.eval(carry, trainer.eval_key(i))
                        if tracer.enabled:
                            tracer.fence(evals)
                    steps_now = trainer.steps(carry)
                    sps = (int(jnp.sum(steps_now))
                           - P * start_cycle * sched.cycle_steps) \
                        / max(time.time() - t0, 1e-9)
                    r_mean = float(jnp.mean(evals))
                    r_span = (float(jnp.min(evals)), float(jnp.max(evals)))
                    print(f"[{spec.variant.name}] cycle {i+1:4d} "
                          f"steps {int(steps_now[0]):7d} x{P} "
                          f"eval {r_mean:+.2f} "
                          f"[{r_span[0]:+.2f},{r_span[1]:+.2f}] "
                          f"loss {float(jnp.mean(m['loss'])):.4f} "
                          f"eps {float(jnp.mean(m['eps'])):.2f} | "
                          f"{sps:.0f} env-steps/s", flush=True)
                if metrics_f is not None:
                    with tracer.span("metrics", index=i + 1):
                        emit(i, m, evals)
                if ckpt_dir and ((i + 1) % spec.checkpoint.every == 0
                                 or i == sched.cycles - 1):
                    with tracer.span("checkpoint", index=i + 1):
                        save_checkpoint(ckpt_dir, i + 1, carry)
                if (i + 1) % spec.checkpoint.every == 0 \
                        or i == sched.cycles - 1:
                    # per-interval throughput from the tracer counters:
                    # long runs stay observable without a trace file
                    now, c = time.time(), tracer.counters
                    dc = c.get("cycles", 0) - win_counters.get("cycles", 0)
                    ds = (c.get("env_steps", 0)
                          - win_counters.get("env_steps", 0))
                    dt = max(now - win_t, 1e-9)
                    print(f"[throughput] cycle {i+1:4d}: "
                          f"{dc / dt:.2f} cycles/s, "
                          f"{ds / dt:.0f} env-steps/s "
                          f"(last {int(dc)} cycle(s))", flush=True)
                    win_t, win_counters = now, c
    finally:
        tracer.close()
        if metrics_f is not None:
            metrics_f.close()
    if args.trace:
        print(f"trace written: {args.trace} (+ Perfetto twin "
              f"{chrome_path_for(args.trace)}); summarize with "
              "python -m repro.launch.trace_report", flush=True)
    if args.dryrun:
        print(f"DRYRUN OK variant={spec.variant.name}", flush=True)
    return 0


if __name__ == "__main__":
    compile_cache.enable()      # the CLI only; in-process callers choose
    raise SystemExit(main())
