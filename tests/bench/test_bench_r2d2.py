"""The recurrent cell's counts and readers: the FLOP count and the
sequence bytes worked out by hand, and ``lstm_ms`` /
``sequence_sample_gbps`` on a cycle of the tiny R2D2 cell recorded on
one TPU v5e (``record_scoped_r2d2.py``), and silent where the cycle has
no recurrent net."""

import math

import pytest

from tiny_cell import CELL as TINY_FF, ROOT, make as make_ff
from tiny_r2d2 import CELL, make

from bench import cells, scopes, wide_scopes
from bench import trace as tr

SCOPES = scopes.SCOPES
TESTDATA = ROOT / "bench" / "testdata"
SCOPED = TESTDATA / "scoped_r2d2_tpu.xplane.pb"
SCOPED_DQN = TESTDATA / "scoped_dqn_tpu.xplane.pb"
# what record_scoped_r2d2.py printed for the file on the chip
RECORDED = {"lstm_ms": 0.055981, "sequence_sample_gbps": 0.5118605996664738}

# one step-forward at A=3, multiply-accumulates
CONV = 20 * 20 * 32 * 8 * 8 * 4 + 9 * 9 * 64 * 4 * 4 * 32 + 7 * 7 * 64 * 3 * 3 * 64
LSTM = (3136 + 3 + 1) * 2048 + 512 * 2048
DUELING = 2 * 512 * 512 + 512 * (1 + 3)
STEP_FLOPS = 2 * (CONV + LSTM + DUELING)


def test_flop_count_by_hand():
    count = cells.load_module(ROOT / "bench" / "counts" / "r2d2-nature.py")
    cfg = cells.load_cell("r2d2-nature.w256").config
    assert count.step_forward_flops(cfg["network"], 3) == STEP_FLOPS \
        == 31_485_952
    assert count.forwards_per_sequence(cfg["spec"]["variant"]) == \
        121 + 2 * 80 + 121
    got = cells.load_count("r2d2-nature")(cfg, 3)
    assert got == STEP_FLOPS * (1 + 64 * 402 / 2048) == 427_028_224


def test_sequence_bytes_by_hand():
    from bench.counts import sequence_replay
    cfg = cells.load_cell("r2d2-nature.w256").config
    one = 124 * (84 * 84 + 4 + 4 + 1) + 2 * 512 * 4 + 4 + 4
    assert sequence_replay.sequence_bytes(cfg) == one == 880_164
    assert sequence_replay.sample_bytes_per_cycle(cfg) == 50 * 64 * one


def _ctx(tmp_path, path=SCOPED):
    cell = cells.load_cell(CELL, make(tmp_path))
    return {"trace": tr.read(str(path)), "trace_path": str(path),
            "cell": cell, "chips": 1}


def test_recurrent_readers_on_a_recorded_cycle(tmp_path, capsys):
    ctx = _ctx(tmp_path)
    for name, want in RECORDED.items():
        got = cells.load_reader(name)(ctx)
        assert got is not None and math.isfinite(got) and got > 0, name
        assert got == pytest.approx(want, rel=1e-9), name
    table = ctx["wide_scopes"]
    assert set(table["mean"]) == set(wide_scopes.RECURRENT)
    learn = scopes.per_cycle_s(ctx, "learn")
    assert wide_scopes.per_cycle_s(ctx, "learn/lstm") < learn
    assert wide_scopes.per_cycle_s(ctx, "learn/burn_in") < learn
    assert scopes.SCOPES == SCOPES
    assert "[bench] recurrent scopes: " in capsys.readouterr().err


def test_recurrent_readers_are_silent_without_a_recurrent_net(tmp_path):
    """On a feed-forward cell's cycle (the recorded tiny dqn cycle, as
    the parent's program would give) both readers give nothing."""
    cell = cells.load_cell(TINY_FF, make_ff(tmp_path, "dqn-nature"))
    ctx = {"trace": tr.read(str(SCOPED_DQN)), "trace_path": str(SCOPED_DQN),
           "cell": cell, "chips": 1}
    assert cells.load_reader("lstm_ms")(ctx) is None
    assert cells.load_reader("sequence_sample_gbps")(ctx) is None
    assert ctx["wide_scopes"]["mean"] == {}
    assert scopes.SCOPES == SCOPES
