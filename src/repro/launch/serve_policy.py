"""Serve a checkpointed policy to many concurrent client streams.

  # checkpoint a run, then serve it
  PYTHONPATH=src python -m repro.launch.rl_train --env catch --dryrun \
      --ckpt-dir runs/catch
  PYTHONPATH=src python -m repro.launch.serve_policy --ckpt-dir runs/catch \
      --clients 256 --ticks 100 --warm-start

A server is a spec plus a carry (``repro.api.serve``): the run's
``spec.json`` + the newest *restorable* ``step_*.npz`` in ``--ckpt-dir``
fully determine the serving network, observation pipeline and
frame-stack discipline — nothing else crosses the training/serving
boundary. Torn checkpoints (a crash mid-write) are skipped with a named
warning, exactly like ``rl_train --resume``.

Client load is the in-process simulated fleet
(``repro.api.policy_client``): ``--clients`` concurrent streams driven
by the jitted envs, each sending raw observations and receiving actions
from the server's dynamic microbatches. ``--warm-start`` pre-compiles
every batch bucket so no serve tick ever recompiles (required for
honest latency numbers; without it the first tick per bucket pays XLA
compilation). ``--policy`` selects greedy / egreedy (``--eps``) /
noisy (NoisyNet checkpoints only); ``--replica`` picks the population
member to serve. ``--smoke`` asserts the round trip (used by CI).

Latency/throughput guidance and the recorded BENCH_7 trajectory live in
docs/serving.md; the measuring harness is benchmarks/serve_policy.py.
"""

from __future__ import annotations

import argparse

from repro import compile_cache
from repro.api import ExperimentSpec, ServeSpec, POLICIES
from repro.api.policy_client import SimulatedClients, drive
from repro.api.serve import load_policy, make_server
from repro.telemetry import make_tracer


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", required=True,
                    help="training checkpoint dir (spec.json + step_*.npz)")
    ap.add_argument("--spec", default=None, metavar="FILE",
                    help="ExperimentSpec JSON overriding the stored "
                         "spec.json (pre-API checkpoint dirs)")
    ap.add_argument("--step", type=int, default=None,
                    help="serve this checkpoint step (default: newest "
                         "restorable)")
    ap.add_argument("--replica", type=int, default=0,
                    help="population checkpoints: which replica to serve")
    ap.add_argument("--policy", default="egreedy", choices=list(POLICIES))
    ap.add_argument("--eps", type=float, default=0.05,
                    help="exploration rate for --policy egreedy")
    ap.add_argument("--max-batch", type=int, default=1024,
                    help="microbatch ceiling per jitted inference call")
    ap.add_argument("--clients", type=int, default=64,
                    help="simulated concurrent client streams")
    ap.add_argument("--ticks", type=int, default=50,
                    help="serve ticks to drive")
    ap.add_argument("--seed", type=int, default=0,
                    help="serve-side RNG seed (client fleet uses seed+1)")
    ap.add_argument("--warm-start", action="store_true",
                    help="pre-compile every batch bucket + pre-size the "
                         "stream table before serving")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="record queue-wait vs compute spans per flush "
                         "(JSONL + Perfetto twin; "
                         "launch/trace_report.py summarizes)")
    ap.add_argument("--smoke", action="store_true",
                    help="assert the round trip and print SERVE OK (CI)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    spec = None
    if args.spec:
        with open(args.spec) as f:
            spec = ExperimentSpec.from_json(f.read())
    try:
        loaded = load_policy(args.ckpt_dir, spec=spec, step=args.step,
                             replica=args.replica)
    except (ValueError, FileNotFoundError) as e:
        print(f"cannot serve {args.ckpt_dir}: {e}", flush=True)
        return 2
    for s in loaded.skipped:
        print(f"WARNING: skipped unrestorable checkpoint {s}", flush=True)
    serve = ServeSpec(policy=args.policy, eps=args.eps,
                      max_batch=args.max_batch, replica=args.replica,
                      seed=args.seed)
    tracer = make_tracer(args.trace, meta={
        "kind": "serve_policy", "env": loaded.spec.env,
        "variant": loaded.spec.variant.name, "policy": args.policy,
        "clients": args.clients, "max_batch": args.max_batch})
    try:
        server = make_server(loaded, serve, tracer=tracer)
    except ValueError as e:
        print(f"invalid serving config: {e}", flush=True)
        return 2
    print(f"serving {loaded.spec.env}/{loaded.spec.variant.name} "
          f"step {loaded.step} ({loaded.pipe.mode} obs, "
          f"policy={args.policy})", flush=True)
    if args.warm_start:
        n = server.warm_start(args.clients)
        print(f"warm start: {n} bucket programs compiled, stream table "
              f"sized for {args.clients}", flush=True)

    clients = SimulatedClients(loaded.spec, args.clients,
                               seed=args.seed + 1)
    try:
        stats = drive(server, clients, args.ticks)
    finally:
        tracer.close()
    if args.trace:
        print(f"trace written: {args.trace}", flush=True)
    print(f"{stats['clients']} streams x {stats['ticks']} ticks: "
          f"{stats['actions_per_s']:.0f} actions/s, "
          f"latency p50 {stats['p50_ms']:.2f} ms "
          f"p99 {stats['p99_ms']:.2f} ms | "
          f"{stats['episodes']} episodes finished, "
          f"mean return {stats['mean_return']:+.2f}", flush=True)

    if args.smoke:
        assert stats["actions"] == args.clients * args.ticks, stats
        assert stats["actions_per_s"] > 0, stats
        print(f"SERVE OK policy={args.policy} obs={loaded.pipe.mode} "
              f"clients={args.clients} ticks={args.ticks}", flush=True)
    return 0


if __name__ == "__main__":
    compile_cache.enable()      # the CLI only; in-process callers choose
    raise SystemExit(main())
