"""Readings the output check's limits are set from, at a cell's own size.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--runs program,program_bf16,reference_bf16,half_batch] [--out FILE]

For each seed this drives the program through the first three cycles
exactly as a benchmark run's set-up does, runs the float32 reference
from the same seed, and prints the three numbers of
``bench/reference/compare.py`` for each kind of run:

* ``program``: the configuration as stated (the lower readings);
* ``program_bf16``: the program's own lower-precision path, its
  compute dtype set to bfloat16. On a TPU the float32 configuration
  already multiplies in single bfloat16 passes, and this path reads as
  the program does (PERF.md, section 2);
* ``reference_bf16``: the reference computed in bfloat16 (parameters,
  optimizer state and activations) put in the program's place: the
  control;
* ``half_batch``: a fault planted in the program: each minibatch keeps
  only its first half, and the loss is the mean over that half.

No window is run. Every run of one call shares one process, so the
compiled programs are made once. One JSON line per (seed, run) goes to
standard output and to ``--out``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

RUNS = ("program", "program_bf16", "reference_bf16", "half_batch")


@contextlib.contextmanager
def half_batch():
    """Plant the fault: the cycle's minibatch sampler returns the first
    half of each batch (the program's own samplers, wrapped)."""
    from repro.core import concurrent

    saved = concurrent.replay_sample, concurrent.per_sample

    def uniform(state, key, n):
        return {k: v[:n // 2] for k, v in saved[0](state, key, n).items()}

    def prioritized(state, key, n, beta, **kw):
        return {k: v[:n // 2]
                for k, v in saved[1](state, key, n, beta, **kw).items()}

    concurrent.replay_sample, concurrent.per_sample = uniform, prioritized
    try:
        yield
    finally:
        concurrent.replay_sample, concurrent.per_sample = saved


def calibrate(workload: str, seeds, runs, root: Path = None,
              require_tpu: bool = True):
    """Yield one dict of readings per (seed, run), as ``main`` prints."""
    import jax
    import jax.numpy as jnp

    from bench import cells, harness
    from bench.reference import compare
    from repro import compile_cache
    from repro.api import ExperimentSpec, build_trainer

    unknown = sorted(set(runs) - set(RUNS))
    if unknown:
        raise SystemExit(f"unknown runs {unknown}; known: {RUNS}")
    cell = cells.load_cell(workload, root or cells.BENCH_DIR)
    devices = harness.device_check(cell.chips, require_tpu)[:cell.chips]
    if require_tpu:
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in seeds:
        spec = ExperimentSpec.from_dict(cells.spec_dict(cell, seed))
        replica_seeds = [spec.seed + r for r in range(spec.seeds)]
        t = time.perf_counter()
        refs = harness.reference_runs(cell, replica_seeds, devices)
        ref_s = time.perf_counter() - t
        for kind in runs:
            t = time.perf_counter()
            if kind == "reference_bf16":
                got = harness.reference_runs(cell, replica_seeds, devices,
                                             dtype=jnp.bfloat16)
            else:
                run_spec = spec
                if kind == "program_bf16":
                    run_spec = dataclasses.replace(spec, exec=dataclasses.replace(
                        spec.exec, compute_dtype="bfloat16"))
                fault = (half_batch() if kind == "half_batch"
                         else contextlib.nullcontext())
                with fault:
                    trainer = build_trainer(run_spec)
                    carry, probe = harness.first_cycles(trainer)
                del carry, trainer
                got = [probe.replica(r) for r in range(len(replica_seeds))]
            per = [compare.readings(got[r], refs[r])
                   for r in range(len(replica_seeds))]
            yield {"workload": cell.name, "seed": seed, "run": kind,
                   "readings": compare.worst(per), "per_replica": per,
                   "loss_program": [g["loss"].tolist() for g in got],
                   "loss_reference": [r["loss"].tolist() for r in refs],
                   "still_leaves": compare.still_leaves(refs[0]),
                   "seconds": time.perf_counter() - t,
                   "reference_seconds": ref_s}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated benchmark seeds")
    p.add_argument("--runs", default="program,program_bf16,reference_bf16")
    p.add_argument("--out", default="")
    args = p.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    seeds = [int(s) for s in args.seeds.split(",")]
    with contextlib.ExitStack() as stack:
        out = (stack.enter_context(open(args.out, "a")) if args.out
               else None)
        for line in calibrate(args.workload, seeds, args.runs.split(",")):
            text = json.dumps(line)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
