"""``bench/run.py`` end to end at a tiny size on the CPU: it refuses to
measure without a TPU, and with the device check skipped a sound run is
correct while each fault planted in the timed path is not."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from tiny_cell import CELL, ROOT, make

from bench import harness


def _cli(cwd, workload="dqn-nature.p1"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_to_measure_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in bench["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def _run(bench, build=None):
    return harness.run(CELL, 2 ** 32 + 3, 0.5, False, root=bench,
                       require_tpu=False, build=build)


@pytest.mark.parametrize("config", ["dqn-nature", "rainbow-nature"])
def test_sound_run_is_correct(tmp_path, config):
    res = _run(make(tmp_path, config))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    names = [m["name"] for m in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["end_to_end"]]
    assert sorted(res["metrics"]) == sorted(names)
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


class _Unchanged:
    """The timed path with its step broken: the cycle returns the state
    it was given."""

    def __init__(self, trainer):
        self._t = trainer

    def init_carry(self):
        return self._t.init_carry()

    def cycle(self, carry):
        _, m = self._t.cycle(carry)
        return carry, m


def test_state_left_unchanged_is_not_correct(tmp_path):
    from repro.api import build_trainer
    res = _run(make(tmp_path, "dqn-nature"),
               build=lambda spec: _Unchanged(build_trainer(spec)))
    assert res["correct"] is False
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("config", ["dqn-nature", "rainbow-nature"])
def test_half_the_batch_left_out_is_not_correct(tmp_path, config):
    from bench.calibrate import half_batch
    bench = make(tmp_path, config)
    with half_batch():
        res = _run(bench)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


class _Device:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_hbm_peak_counts_the_programs_reserved_memory():
    dev = _Device({"peak_bytes_in_use": 8_523_706_368,
                   "peak_bytes_reserved": 7_527_481_344, "bytes_in_use": 1})
    assert harness.hbm_peak_bytes(dev) == 16_051_187_712
    assert harness.hbm_peak_bytes(_Device({"peak_bytes_in_use": 5})) == 5
    assert harness.hbm_peak_bytes(_Device(None)) == 0


def test_cycle_times_name_the_slow_cycles():
    got = harness.cycle_times([0.0, 0.5, 1.0, 1.6, 2.1])
    assert got["median"] == pytest.approx(0.5)
    assert got["min"] == pytest.approx(0.5) and got["max"] == pytest.approx(0.6)
    assert got["slow"] == [2]
