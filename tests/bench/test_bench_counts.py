"""FLOP and byte counts, the peaks table, and the readers built on them."""

import json

import pytest

from tiny_cell import ROOT

from bench import cells, counts, trace
from bench.counts import categorical_projection, network, segment_tree

NATURE = json.loads((ROOT / "bench" / "configs" / "dqn-nature.json")
                    .read_text())["network"]


def test_nature_cnn_forward_is_18_7_mflop():
    # conv1 6.55 + conv2 5.31 + conv3 3.61 + fc 3.21 MFLOP, and the
    # 3-action head
    assert network.forward_flops(NATURE, 3) / 1e6 == pytest.approx(18.7,
                                                                  abs=0.1)


def test_forward_flops_per_layer():
    one_conv = dict(NATURE, convs=[[32, 8, 4]], hidden=1)
    assert network.forward_flops(one_conv, 1) == pytest.approx(
        2 * (20 * 20 * 32 * 8 * 8 * 4 + 20 * 20 * 32 + 1), rel=0)


@pytest.mark.parametrize("B,F,double,expect", [
    (32, 4, False, 1 + 8 * 4), (32, 4, True, 1 + 8 * 5),
    (32, 2, False, 1 + 16 * 4), (64, 4, True, 1 + 16 * 5),
    (32, 1, False, 1 + 32 * 4)])
def test_learner_multiple_follows_F_minibatch_and_double(B, F, double,
                                                         expect):
    assert network.forwards_per_env_step(B, F, double) == expect


def test_flops_per_env_step_of_the_configs():
    for name, double, atoms in (("dqn-nature", False, 1),
                                ("rainbow-nature", True, 51)):
        cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                         .read_text())
        fwd = network.forward_flops(cfg["network"], 3, atoms,
                                    cfg["spec"]["variant"]["dueling"])
        assert network.flops_per_env_step(cfg, 3) == pytest.approx(
            fwd * (1 + 32 / 4 * (5 if double else 4)))


def test_kernel_work_is_the_algorithms():
    f, b = segment_tree.work(32, 131072)
    assert (f, b) == (2 * 32 * 17, 4 * 32 * 17 + 8 * 32)
    f, b = categorical_projection.work(32, 51)
    assert (f, b) == (13 * 32 * 51, 4 * (2 * 32 * 51 + 2 * 32))


def _fake_trace(kernel_name: str, calls: int, each_ns: float):
    evs = [(i * 1000.0, i * 1000.0 + each_ns, kernel_name)
           for i in range(calls)]
    return trace.Trace({"/device:TPU:0": evs}, [], (0.0, calls * 1000.0))


_CALL = ' custom-call(f32[8,128] %a), custom_call_target="tpu_custom_call"'


@pytest.mark.parametrize("metric,pattern_names", [
    ("segment_tree_roofline", ("%_seg_kernel.3 = s32[32,128]{1,0}" + _CALL,
                               "%_lambda_.1 = s32[1,32,128]{2,1,0}" + _CALL)),
    ("categorical_projection_roofline", (
        "%_proj_kernel.2 = f32[32,128]{1,0}" + _CALL,
        "%_lambda_.4 = f32[1,32,128]{2,1,0}" + _CALL))])
def test_kernel_roofline_does_not_depend_on_backend(metric, pattern_names):
    """Two backends name their op differently and take the same time:
    the share is the same, since the work counted is the algorithm's."""
    read = cells.load_reader(metric)
    cell = cells.load_cell("rainbow-nature.p1")
    peak = counts.peaks("TPU v5 lite")
    shares = [read({"trace": _fake_trace(n, 10, 500.0), "cell": cell,
                    "peak": peak}) for n in pattern_names]
    assert shares[0] == pytest.approx(shares[1])
    assert 0 < shares[0] < 100


def test_roofline_reader_finds_nothing_in_a_cell_without_the_kernel():
    read = cells.load_reader("segment_tree_roofline")
    cell = cells.load_cell("dqn-nature.p1")
    assert read({"trace": _fake_trace("fusion.3", 5, 100.0), "cell": cell,
                 "peak": counts.peaks("TPU v5 lite")}) is None


def test_peaks_table():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
    # a least time is bounded by bytes for these kernels
    assert counts.least_seconds(1.0, 819e9, p) == pytest.approx(1.0)


def test_cycle_mfu_reader():
    read = cells.load_reader("cycle_mfu")
    cell = cells.load_cell("dqn-nature.p1")
    peak = counts.peaks("TPU v5 lite")
    got = read({"cell": cell, "env_steps_per_s": 10_000.0, "chips": 4,
                "peak": peak, "n_actions": 3})
    flops = network.flops_per_env_step(cell.config, 3)
    assert got == pytest.approx(100 * flops * 1e4 / (4 * 197e12))
