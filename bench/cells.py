"""Find the benchmark's configurations, cells and metric readers by name.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` uses:

* ``bench/configs/<config>.json``: the model and its settings, as run;
* ``bench/workloads/<cell>.json``: the configuration and the traffic
  it runs, the chips it needs, and why it exists;
* ``bench/traffic/<traffic>.json``: the replicas (seeds) a run trains
  side by side, the env streams of each, and the env;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric;
* ``bench/limits/<config>.json``: the limits of the output check;
* ``bench/reference/<config>.py``: the configuration's own plain
  reference (its ``Reference`` class, see ``bench/reference``), where
  the shared ``bench/reference/cycle.py`` does not fit it;
* ``bench/counts/<config>.py``: the configuration's own FLOP count (its
  ``flops_per_env_step(config, n_actions)``), where the shared
  ``bench/counts/network.py`` does not fit it.

Adding any of them is adding a file; nothing here lists them. A
configuration without a reference or count of its own takes the shared
one, so no configuration is named after another module of those two
directories (``compare``, ``replay``, ``segment_tree``, ...).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# Python ints are unbounded but the program's seeds are int32: a cell's
# seed maps into [0, SEED_SPACE) and its replicas take the next P seeds.
SEED_SPACE = 2 ** 31 - 64

# the shared module of each kind that a configuration may replace with
# one of its own, ``<kind>/<config>.py``
SHARED = {"reference": "cycle", "counts": "network"}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: Dict[str, Any]
    workload: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    # the ``bench`` directory the cell's files were read from
    root: Path = BENCH_DIR

    @property
    def config_name(self) -> str:
        return str(self.workload["config"])

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def replicas(self) -> int:
        return int(self.traffic["replicas"])

    @property
    def envs(self) -> int:
        return int(self.traffic["envs"])


def _read_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def names(kind: str, root: Path = BENCH_DIR) -> List[str]:
    """The names of every ``configs``/``workloads``/``traffic`` JSON
    file or ``metrics`` reader under ``root``."""
    suffix = ".py" if kind == "metrics" else ".json"
    return sorted(p.name[:-len(suffix)] for p in (root / kind).glob("*" + suffix)
                  if not p.name.startswith("_"))


def load_cell(name: str, root: Path = BENCH_DIR) -> Cell:
    path = root / "workloads" / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{', '.join(names('workloads', root))}")
    workload = _read_json(path)
    config = _read_json(root / "configs" / f"{workload['config']}.json")
    traffic = _read_json(root / "traffic" / f"{workload['traffic']}.json")
    limits_path = root / "limits" / f"{workload['config']}.json"
    limits = _read_json(limits_path) if limits_path.is_file() else {}
    return Cell(name, config, workload, traffic, limits, root)


def program_seed(seed: int, replicas: int) -> int:
    """The first replica's seed in the program's int32 seed space."""
    if seed < 0:
        raise SystemExit(f"--seed must be a whole number >= 0, got {seed}")
    return seed % (SEED_SPACE - replicas)


def spec_dict(cell: Cell, seed: int) -> Dict[str, Any]:
    """The ``ExperimentSpec`` fields of one run: the configuration's
    spec, the cell's traffic, and the seed."""
    spec = json.loads(json.dumps(cell.config["spec"]))
    traffic = cell.traffic
    spec["env"] = traffic["env"]
    spec["envs"] = int(traffic["envs"])
    spec["seeds"] = int(traffic["replicas"])
    spec["seed"] = program_seed(seed, int(traffic["replicas"]))
    return spec


def load_module(path: Path):
    """The Python file at ``path``, loaded by its path, so that the
    files of the checkout given as ``root`` run and not those of the
    ``bench`` package on ``sys.path``."""
    path = Path(path).resolve()
    name = "bench_file_" + re.sub(r"\W", "_", str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered, as an import would be: a dataclass needs its module
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: Path = BENCH_DIR) -> Callable:
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    return load_module(root / "metrics" / f"{metric}.py").read


def config_file(kind: str, config: str, root: Path = BENCH_DIR) -> Path:
    """``<kind>/<config>.py`` where the configuration brings one, else
    the shared module of that kind (``SHARED``)."""
    own = root / kind / f"{config}.py"
    return own if own.is_file() else root / kind / f"{SHARED[kind]}.py"


def shared_reference(root: Path = BENCH_DIR) -> type:
    """The ``Reference`` class of ``reference/cycle.py``, for a
    configuration's own reference to subclass."""
    return load_module(root / "reference" / f"{SHARED['reference']}.py"
                       ).Reference


def load_reference(config: str, root: Path = BENCH_DIR) -> type:
    """The ``Reference`` class the output check runs for ``config``."""
    return load_module(config_file("reference", config, root)).Reference


def load_count(config: str, root: Path = BENCH_DIR) -> Callable:
    """The ``flops_per_env_step(config, n_actions)`` of ``config``."""
    return load_module(config_file("counts", config, root)
                       ).flops_per_env_step


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _read_json(root / "BENCHMARK.json")


def per_layer_for(cell: str, bench: Dict[str, Any]) -> List[str]:
    """The per-layer metrics ``BENCHMARK.json`` asks of ``cell``."""
    out = []
    for m in bench.get("per_layer", []):
        if "workloads" not in m or cell in m["workloads"]:
            out.append(m["name"])
    return out


def end_to_end_for(cell: str, bench: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [m for m in bench.get("end_to_end", [])
            if "workloads" not in m or cell in m["workloads"]]
