"""Benchmark harness — one entry per recorded-trajectory deliverable.

Prints ``name,us_per_call,derived`` CSV rows. Fast by default;
``--full`` runs the larger Table-1 geometry (84x84 Nature CNN) and
longer learning runs.

  PYTHONPATH=src python -m benchmarks.run [--full]
  PYTHONPATH=src python -m benchmarks.run --sections cycle_time \
      --record BENCH_9.json --trace bench_trace.jsonl

Two section tiers (the ``--sections`` grammar accepts names from both):

* **SECTIONS** (the default set) — every section whose rows fold into
  the committed ``BENCH_<n>.json`` trajectory: ``env_throughput``,
  ``serve_policy``, ``cycle_time``, ``per_ops``.
* **LEGACY_SECTIONS** — the original paper-table reproductions
  (``table1``, ``transactions``, ``table4``, ``roofline``, ``perf``).
  They print their human-readable tables and contribute CSV rows, but
  they are *not* part of the recorded trajectory (their geometries are
  proxies tuned per table, not comparable across PRs) — run them via
  ``--sections`` or ``--legacy``. This split is why ``--record`` output
  and the ``--sections`` help no longer disagree.

``--record FILE`` writes rows + metadata as JSON; the meta block
carries full provenance (git SHA + dirty flag, platform/CPU model,
Python version — ``repro.telemetry.provenance``) so successive
``BENCH_<n>.json`` files are attributable evidence, not bare numbers.

``--trace FILE`` records a phase trace of the harness itself: each
section runs inside a span, and every recorded row is mirrored into the
trace as a same-named span (``Tracer.point``) — which is what lets
``trace_report --against BENCH_<n>.json`` match spans to committed rows
by name and act as the perf-regression gate CI runs.
"""

from __future__ import annotations

import argparse
import json
import sys

# The recorded trajectory (default set): rows comparable across PRs.
SECTIONS = ("env_throughput", "serve_policy", "cycle_time", "per_ops")
# Paper-table reproductions: printable, row-emitting, but not recorded.
LEGACY_SECTIONS = ("table1", "transactions", "table4", "roofline", "perf")


def _run_section(section: str, args, rows) -> None:
    """Execute one section, appending its ``(name, us, derived)``
    rows. Imports stay inside each branch so a section's dependencies
    load only when it runs."""
    if section == "table1":
        from benchmarks import table1_speed
        steps = 2000 if args.full else 600
        fs = 84 if args.full else 10
        print(f"# Table 1 speed ablation ({steps} steps, frame {fs})",
              flush=True)
        t1 = table1_speed.run_table1(steps=steps, frame_size=fs)
        print(table1_speed.format_tables(t1), flush=True)
        for r in t1:
            rows.append((f"table1_{r['variant']}_w{r['threads']}",
                         r["us_per_step"], f"speedup={r['speedup']:.2f}x"))

    elif section == "transactions":
        from benchmarks import transactions
        print("\n# Transaction scaling (sync => independent of W)",
              flush=True)
        for r in transactions.main():
            rows.append(
                (f"transactions_{'sync' if r['synchronized'] else 'std'}"
                 f"_w{r['threads']}", 0.0,
                 f"tx_per_step={r['tx_per_step']:.3f}"))

    elif section == "table4":
        from benchmarks import table4_learning
        cycles = 80 if args.full else 40
        print(f"\n# Table 4 learning proxy ({cycles} cycles/env)",
              flush=True)
        for r in table4_learning.main(cycles=cycles):
            rows.append((f"table4_{r['env']}", 0.0,
                         f"norm={r['normalized_pct']:.1f}%"))

    elif section == "roofline":
        from benchmarks import roofline_table
        print("\n# Roofline (single-pod 16x16 baseline, from dry-run)",
              flush=True)
        for r in roofline_table.main():
            if "error" in r:
                rows.append((f"roofline_{r['name']}", 0.0, "ERROR"))
            else:
                rows.append((f"roofline_{r['name']}", r["step_s"] * 1e6,
                             f"dominant={r['dominant']}"))

    elif section == "perf":
        from benchmarks import perf_table
        print("\n# Perf iterations (dry-run variants; see EXPERIMENTS.md "
              "§Perf)", flush=True)
        for r in perf_table.main():
            rows.append((f"perf_{r['pair']}_{r['variant']}",
                         r["step_s"] * 1e6, f"speedup={r['speedup']:.2f}x"))

    elif section == "env_throughput":
        from benchmarks import env_throughput
        steps = 256 if args.full else 128
        print(f"\n# Env throughput (W grid {env_throughput.W_GRID}, "
              f"{steps}-step scans)", flush=True)
        for r in env_throughput.run_benchmark(steps=steps):
            rows.append((r["name"], r["us_per_call"], r["derived"]))

    elif section == "serve_policy":
        from benchmarks import serve_policy
        ticks = 40 if args.full else 20
        print(f"\n# Policy serving (client grid "
              f"{serve_policy.CLIENT_GRID}, batch grid "
              f"{serve_policy.BATCH_GRID}, {ticks} ticks)", flush=True)
        for r in serve_policy.run_benchmark(ticks=ticks):
            rows.append((r["name"], r["us_per_call"], r["derived"]))

    elif section == "cycle_time":
        from benchmarks import cycle_time
        print("\n# Trainer cycle time (build_trainer path; p4 = packed "
              "4-replica fleet)", flush=True)
        for r in cycle_time.run_benchmark(full=args.full):
            rows.append((r["name"], r["us_per_call"], r["derived"]))

    elif section == "per_ops":
        from benchmarks import c51_projection, per_sampling
        caps = "1024,16384,262144" if args.full else "1024,16384"
        batches = "32,256,2048" if args.full else "32,256"
        print(f"\n# PER sampling (caps {caps})", flush=True)
        for r in per_sampling.main(["--capacities", caps]):
            rows.append((f"per_sample_cap{r['capacity']}_{r['sampler']}",
                         r["us_per_call"], f"sampler={r['sampler']}"))
        print(f"\n# C51 projection (batches {batches})", flush=True)
        for r in c51_projection.main(["--batches", batches]):
            rows.append((f"c51_proj_b{r['batch']}_{r['backend']}",
                         r["us_per_call"], f"atoms={r['atoms']}"))

    else:                                     # pragma: no cover
        raise ValueError(f"unhandled section {section!r}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip-learning", action="store_true")
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset of the recorded set "
                         f"{','.join(SECTIONS)} and/or the legacy "
                         f"paper-table set {','.join(LEGACY_SECTIONS)} "
                         "(default: the recorded set)")
    ap.add_argument("--legacy", action="store_true",
                    help="also run every LEGACY_SECTIONS entry")
    ap.add_argument("--record", default=None, metavar="FILE",
                    help="write rows + provenance metadata as JSON "
                         "(the committed BENCH_<n>.json trajectory)")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="record a phase trace of the harness: section "
                         "spans + one same-named span per recorded row "
                         "(feeds trace_report --against BENCH_<n>.json)")
    args = ap.parse_args(argv)

    known = SECTIONS + LEGACY_SECTIONS
    if args.sections is None:
        sections = list(SECTIONS)
        if args.legacy:
            sections += list(LEGACY_SECTIONS)
    else:
        sections = [s.strip() for s in args.sections.split(",") if s.strip()]
        unknown = [s for s in sections if s not in known]
        if unknown:
            ap.error(f"unknown sections {unknown}; recorded: {SECTIONS}, "
                     f"legacy: {LEGACY_SECTIONS}")
        if args.legacy:
            sections += [s for s in LEGACY_SECTIONS if s not in sections]
    if args.skip_learning and "table4" in sections:
        sections.remove("table4")

    from repro.telemetry import make_tracer
    tracer = make_tracer(args.trace, meta={"kind": "benchmarks",
                                           "sections": ",".join(sections),
                                           "full": args.full})

    rows = []
    try:
        for section in sections:
            before = len(rows)
            with tracer.span(section):
                _run_section(section, args, rows)
                # mirror each recorded row into the trace as a span of
                # the same name: the bench-regression gate matches on it
                for name, us, derived in rows[before:]:
                    tracer.point(name, us, derived=derived)
    finally:
        tracer.close()

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")

    if args.record:
        import jax
        from repro.telemetry import provenance
        payload = {
            "meta": {
                "argv": list(argv) if argv is not None else sys.argv[1:],
                "backend": jax.default_backend(),
                "jax_version": jax.__version__,
                "sections": sections,
                **provenance(),
            },
            "rows": [{"name": n, "us_per_call": round(us, 2),
                      "derived": d} for n, us, d in rows],
        }
        with open(args.record, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"recorded {len(rows)} rows -> {args.record}", flush=True)
    if args.trace:
        print(f"trace written: {args.trace}", flush=True)


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
