"""A tiny ``r2d2-nature`` cell for the CPU tests and the recorded trace.

``make(tmp_path)`` copies ``bench/`` and ``BENCHMARK.json`` into
``tmp_path`` and adds configuration ``tiny-r2d2``, ``r2d2-nature``
shrunk to 10x10 frames, the ``r2d2_tiny`` net (one 8-channel 3x3 conv,
an LSTM and dueling layers of 16), sequences of 4 + 8 + 1 steps
starting every 4 steps, 8 sequence starts per stream, C=32 and a target
synced every second cycle, with its own reference (a copy of
``r2d2-nature``'s), the cell's limits, and cell ``tiny-r2d2.w4``: one
replica of W=4 streams of ``pong``.
"""

import copy
import json
import shutil
from pathlib import Path

from tiny_cell import ROOT

CONFIG = "r2d2-nature"
TINY = "tiny-r2d2"
CELL = f"{TINY}.w4"


def tiny_config() -> dict:
    cfg = json.loads((ROOT / "bench" / "configs"
                      / f"{CONFIG}.json").read_text())
    tiny = copy.deepcopy(cfg)
    tiny["name"] = TINY
    spec = tiny["spec"]
    spec.update(frame_size=10, net="r2d2_tiny")
    spec["variant"].update(sequence_length=8, burn_in=4, sequence_period=4)
    spec["schedule"].update(cycle_steps=32, prepopulate=64, target_cycles=2)
    spec["algo"].update(replay_capacity=32, minibatch_size=4, train_period=8)
    tiny["network"].update(frame_size=10, convs=[[8, 3, 1]], lstm=16,
                           dueling_hidden=16)
    return tiny


def make(tmp_path: Path) -> Path:
    """Returns the copied ``bench`` directory, holding cell ``CELL``."""
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    (bench / "configs" / f"{TINY}.json").write_text(json.dumps(tiny_config()))
    shutil.copy(bench / "reference" / f"{CONFIG}.py",
                bench / "reference" / f"{TINY}.py")
    shutil.copy(bench / "limits" / f"{CONFIG}.json",
                bench / "limits" / f"{TINY}.json")
    (bench / "traffic" / "w4.json").write_text(json.dumps(
        {"name": "w4", "replicas": 1, "envs": 4, "env": "pong"}))
    (bench / "workloads" / f"{CELL}.json").write_text(json.dumps(
        {"name": CELL, "config": TINY, "traffic": "w4", "chips": 1,
         "why": "the reference against the program on the CPU"}))
    return bench
