"""Record the scoped test trace of the tiny R2D2 cell on one chip.

    python tests/bench/record_scoped_r2d2.py <out_dir>

Builds the tiny cell of ``tiny_r2d2.py`` (10x10 frames, the
``r2d2_tiny`` net, one replica of W=4 envs, C=32), drives its first
cycles as the harness's set-up does, profiles one more cycle inside the
harness's annotations (``bench.harness._profile``), trims the trace as
``record_scoped.py`` does and writes it to
``<out_dir>/scoped_r2d2_tpu.xplane.pb``. It then prints what the
readers of the recurrent cell read from the written file;
``test_bench_r2d2.py`` reads the file back and expects the same.
"""

import json
import shutil
import sys
import tempfile
from pathlib import Path

from record_scoped import SEED, trim
from tiny_r2d2 import CELL, make

from bench import cells, harness  # noqa: E402
from bench import trace as tr  # noqa: E402

READERS = ("lstm_ms", "sequence_sample_gbps", "learn_ms", "act_ms")


def main(out: Path) -> None:
    from repro.api import ExperimentSpec, build_trainer
    harness.device_check(1)
    with tempfile.TemporaryDirectory() as tmp:
        bench = make(Path(tmp))
        cell = cells.load_cell(CELL, bench)
        spec = ExperimentSpec.from_dict(cells.spec_dict(cell, SEED))
        trainer = build_trainer(spec)
        carry, _ = harness.first_cycles(trainer)
        carry, trace_dir, path = harness._profile(trainer, carry, 1)
        dest = out / "scoped_r2d2_tpu.xplane.pb"
        dest.write_bytes(trim(Path(path).read_bytes()))
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"{dest}: {dest.stat().st_size} bytes", flush=True)
        ctx = {"trace": tr.read(str(dest)), "trace_path": str(dest),
               "cell": cell, "chips": 1}
        print(json.dumps({m: cells.load_reader(m, bench)(ctx)
                          for m in READERS}), flush=True)


if __name__ == "__main__":
    main(Path(sys.argv[1]))
