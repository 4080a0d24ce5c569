"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout on a machine that holds the chips
the cell asks for (``bench/workloads/<cell>.json``). It refuses to
measure anywhere but on a TPU. With ``--trace 0`` the last line of
standard output is one JSON object with the cell's end-to-end metrics;
with ``--trace 1`` it has the per-layer metrics, read from a profile of
a few cycles after the window, and ``breakdown``. The numbers the output
check compared, each beside its limit, end standard error and the line.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    # libtpu logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import harness
    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t0=T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
