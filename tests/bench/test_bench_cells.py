"""The benchmark's files: found by name, consistent with BENCHMARK.json,
and turned into the spec the program runs."""

import inspect
import json
import shutil

import pytest

from tiny_cell import ROOT, add_config, make

from bench import cells

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_named_file_exists_and_agrees():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert c["source"] == cfg["source"]
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
    for w in BENCH["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.workload["config"] == w["config"]
        assert cell.workload["traffic"] == w["traffic"]
        assert cell.chips == w["chips"]
        assert cell.limits["limits"], f"{w['name']} has no output limits"
    for m in BENCH["per_layer"]:
        assert callable(cells.load_reader(m["name"]))
    assert set(cells.names("workloads")) == {w["name"]
                                             for w in BENCH["workloads"]}


def test_new_files_are_found_without_an_edit(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    cfg = json.loads((bench / "configs" / "dqn-nature.json").read_text())
    cfg["name"] = "dqn-other"
    (bench / "configs" / "dqn-other.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "p2.json").write_text(json.dumps(
        {"name": "p2", "replicas": 2, "envs": 16, "env": "pong"}))
    (bench / "workloads" / "dqn-other.p2.json").write_text(json.dumps(
        {"name": "dqn-other.p2", "config": "dqn-other", "traffic": "p2",
         "chips": 1, "why": "x"}))
    (bench / "metrics" / "replicas_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['cell'].replicas)\n")
    assert "dqn-other" in cells.names("configs", bench)
    assert "dqn-other.p2" in cells.names("workloads", bench)
    assert "replicas_seen" in cells.names("metrics", bench)
    cell = cells.load_cell("dqn-other.p2", bench)
    assert (cell.replicas, cell.envs) == (2, 16)
    reader = cells.load_reader("replicas_seen", bench)
    assert reader({"cell": cell}) == 2.0


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_spec_round_trips_through_experiment_spec(workload):
    from repro.api import ExperimentSpec
    cell = cells.load_cell(workload)
    d = cells.spec_dict(cell, 3_000_000_007)
    spec = ExperimentSpec.from_dict(d)
    spec.validate()
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    assert spec.seeds == cell.replicas and spec.envs == cell.envs
    assert 0 <= spec.seed and spec.seed + spec.seeds - 1 < 2 ** 31
    algo = cell.config["spec"]["algo"]
    assert spec.algo.replay_capacity == algo["replay_capacity"]
    assert spec.dqn_config().target_update_period == \
        cell.config["spec"]["schedule"]["cycle_steps"]


def _on_shared_reference(config, bench=cells.BENCH_DIR):
    return cells.config_file("reference", config, bench) == (
        bench / "reference" / "cycle.py")


def configs_without_their_test(bench, tests):
    """The configurations in ``bench`` that bring their own reference
    but no ``test_config_<config>.py`` with a test in ``tests``."""
    def has_test(config):
        path = tests / f"test_config_{config}.py"
        return path.is_file() and "def test_" in path.read_text()
    return [c for c in cells.names("configs", bench)
            if not _on_shared_reference(c, bench) and not has_test(c)]


@pytest.mark.parametrize("config", [c for c in cells.names("configs")
                                    if _on_shared_reference(c)])
def test_config_network_and_constants_are_the_programs(config):
    """The shared reference and count read the network and the optimizer
    constants from the configuration file; they must be what the
    program builds. A configuration with a reference of its own is
    checked by its own ``test_config_<config>.py``."""
    from repro.config import DQNConfig
    from repro.configs.dqn_nature import cnn_geometry
    from repro.optim import adamw, centered_rmsprop
    cfg = json.loads((ROOT / "bench" / "configs"
                      / f"{config}.json").read_text())
    net = cfg["network"]
    geo = cnn_geometry(cfg["spec"]["net"], cfg["spec"]["frame_size"], 3)
    assert [list(c) for c in geo.convs] == net["convs"]
    assert (geo.hidden, geo.frame_stack, geo.frame_size) == (
        net["hidden"], net["frame_stack"], net["frame_size"])
    k = cfg["program_constants"]
    rms = inspect.signature(centered_rmsprop).parameters
    adam = inspect.signature(adamw).parameters
    assert (k["rmsprop_decay"], k["rmsprop_eps"]) == (
        rms["decay"].default, rms["eps"].default)
    assert (k["adam_b1"], k["adam_b2"], k["adam_eps"],
            k["adam_grad_clip"]) == (adam["b1"].default, adam["b2"].default,
                                     adam["eps"].default,
                                     adam["grad_clip"].default)
    assert (k["eps_start"], k["eps_end"]) == (DQNConfig.eps_start,
                                              DQNConfig.eps_end)


def test_every_configuration_with_its_own_reference_has_its_own_test():
    assert configs_without_their_test(cells.BENCH_DIR, ROOT / "tests"
                                      / "bench") == []
    for config in cells.names("configs"):
        assert isinstance(cells.load_reference(config), type)
        assert callable(cells.load_count(config))


def test_own_reference_without_its_test_is_found(tmp_path):
    bench = make(tmp_path, "dqn-nature")
    tests = tmp_path / "tests"
    tests.mkdir()
    add_config(bench, "tiny-own", reference="Reference = object\n")
    add_config(bench, "tiny-shared", count="flops_per_env_step = abs\n")
    assert configs_without_their_test(bench, tests) == ["tiny-own"]
    (tests / "test_config_tiny-own.py").write_text(
        "def test_program_check():\n    pass\n")
    assert configs_without_their_test(bench, tests) == []


@pytest.mark.parametrize("seed,replicas", [(0, 1), (2 ** 31 + 5, 1),
                                           (2 ** 32 + 17, 4),
                                           (2 ** 31 - 65, 4)])
def test_large_seeds_map_into_the_programs_seed_space(seed, replicas):
    s = cells.program_seed(seed, replicas)
    assert 0 <= s and s + replicas - 1 <= 2 ** 31 - 1
    assert cells.program_seed(seed, replicas) == s


def test_benchmark_json_keeps_the_format():
    import re
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    workloads = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", workloads)) <= workloads
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert name.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
