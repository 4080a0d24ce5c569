"""A tiny benchmark cell for the harness tests on the CPU.

``make(tmp_path, config)`` copies ``bench/`` and ``BENCHMARK.json``
into ``tmp_path`` and adds one configuration shrunk from ``config``
(10x10 frames, a one-conv net, C=32, replay 256) with its traffic and
its cell, ``tiny.p1``. The output check's limits are the real
configuration's, copied. ``add_config`` adds the same configuration
under another name, with a reference or FLOP count of its own.
"""

import copy
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELL = "tiny.p1"


def make(tmp_path: Path, config: str) -> Path:
    """Returns the copied ``bench`` directory, holding cell ``tiny.p1``."""
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    cfg = json.loads((bench / "configs" / f"{config}.json").read_text())
    tiny = copy.deepcopy(cfg)
    tiny["name"] = "tiny"
    tiny["spec"].update(frame_size=10, net="tiny")
    tiny["spec"]["schedule"].update(cycle_steps=32, prepopulate=64)
    tiny["spec"]["algo"].update(replay_capacity=256, train_period=2)
    tiny["network"] = {"frame_size": 10, "frame_stack": 2,
                       "convs": [[8, 3, 1]], "hidden": 16}
    (bench / "configs" / "tiny.json").write_text(json.dumps(tiny))
    (bench / "traffic" / "tiny4.json").write_text(json.dumps(
        {"name": "tiny4", "replicas": 1, "envs": 4, "env": "pong"}))
    (bench / "workloads" / f"{CELL}.json").write_text(json.dumps(
        {"name": CELL, "config": "tiny", "traffic": "tiny4", "chips": 1,
         "why": "harness tests on the CPU"}))
    limits = bench / "limits" / f"{config}.json"
    if limits.is_file():
        shutil.copy(limits, bench / "limits" / "tiny.json")
    return bench


def add_config(bench: Path, name: str, reference: str = "",
               count: str = "") -> str:
    """Adds configuration ``name``, the tiny one under another name,
    with its limits and its cell ``<name>.p1``, and, where given, the
    source of its own ``reference/<name>.py`` and ``counts/<name>.py``.
    Returns the cell's name."""
    cfg = json.loads((bench / "configs" / "tiny.json").read_text())
    cfg["name"] = name
    (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    limits = bench / "limits" / "tiny.json"
    if limits.is_file():
        shutil.copy(limits, bench / "limits" / f"{name}.json")
    cell = f"{name}.p1"
    (bench / "workloads" / f"{cell}.json").write_text(json.dumps(
        {"name": cell, "config": name, "traffic": "tiny4", "chips": 1,
         "why": "harness tests on the CPU"}))
    if reference:
        (bench / "reference" / f"{name}.py").write_text(reference)
    if count:
        (bench / "counts" / f"{name}.py").write_text(count)
    return cell
