"""Does each configuration fit one v5e? A compile for a described chip.

    JAX_PLATFORMS=cpu python bench/memfit.py [--replay 100000] [config ...]

For each configuration (all of ``bench/configs`` by default) this
compiles, with libtpu's compiler for a described v5e and no chip, the
two programs a run holds on the device: the init program (replay,
sampler and the prepopulate scan) and the C-cycle. It prints each
program's ``memory_analysis()``: argument, output, temporary and alias
bytes, and their sum against the chip's 16 GiB. These are compile
figures, not chip readings.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

V5E_HBM = 16 * 1024 ** 3


def _fig(mem) -> dict:
    out = {k: int(getattr(mem, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}
    out["total_bytes"] = (out["argument_size_in_bytes"]
                          + out["output_size_in_bytes"]
                          + out["temp_size_in_bytes"]
                          - out["alias_size_in_bytes"])
    out["total_gib"] = out["total_bytes"] / 1024 ** 3
    out["fits"] = out["total_bytes"] < V5E_HBM
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("configs", nargs="*")
    p.add_argument("--replay", type=int, default=0,
                   help="replay capacity to try (0: the file's)")
    args = p.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import cells
    from repro.api import ExperimentSpec, build_trainer

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    report = {}
    for name in args.configs or cells.names("configs"):
        cfg = json.loads((cells.BENCH_DIR / "configs"
                          / f"{name}.json").read_text())
        spec_d = dict(cfg["spec"], env="pong", envs=8, seeds=1, seed=0)
        if args.replay:
            spec_d["algo"] = dict(spec_d["algo"], replay_capacity=args.replay)
        spec = ExperimentSpec.from_dict(spec_d)
        spec = dataclasses.replace(spec, exec=dataclasses.replace(
            spec.exec, kernel_backend="mosaic"))
        trainer = build_trainer(spec)
        init = jax.jit(trainer._init, out_shardings=chip).lower().compile()
        carry = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            trainer.init_template())
        cycle = trainer.cycle.lower(carry).compile()
        report[name] = {
            "replay_capacity": spec.algo.replay_capacity,
            "init_prepopulate": _fig(init.memory_analysis()),
            "cycle": _fig(cycle.memory_analysis()),
        }
        print(json.dumps({name: report[name]}, indent=1), flush=True)
    return 0 if all(r[k]["fits"] for r in report.values()
                    for k in ("init_prepopulate", "cycle")) else 1


if __name__ == "__main__":
    sys.exit(main())
