"""The scope reduction: op_name paths to scopes, op attribution with
the container fallback, the trace's own op metadata, the clock offset,
the replay byte counts and the per-layer readers on traces recorded on
one TPU v5e."""

import math
import shutil
import tempfile

import pytest

from tiny_cell import CELL, ROOT, make

from bench import cells, scopes
from bench import trace as tr
from bench.counts import peaks, replay

TESTDATA = ROOT / "bench" / "testdata"
SMALL = TESTDATA / "small_tpu.xplane.pb"
SCOPED = {v: TESTDATA / f"scoped_{v}_tpu.xplane.pb"
          for v in ("dqn", "rainbow")}
CONFIGS = {"dqn": "dqn-nature", "rainbow": "rainbow-nature"}
NEW = ("act_ms", "learn_ms", "flush_ms", "per_tree_ms", "render_share",
       "replay_sample_gbps")


@pytest.mark.parametrize("path,want", [
    ("jit(cycle)/act/while/body/closed_call/render/sin:", "act/render"),
    ("jit(cyc)/vmap(act)/while/body/closed_call/render/sin:", "act/render"),
    ("jit(cycle)/shard_map(vmap(learn))/while/body/sample/gather:",
     "learn/sample"),
    ("jit(cycle)/shard_map/vmap(flush)/add:", "flush"),
    ("jit(cycle)/vmap(learn)/while/body/closed_call/update/"
     "transpose(jvp())/reduce_sum:", "learn/update"),
    ("jit(cycle)/vmap(per_tree)/reduce_sum:", "per_tree"),
    ("jit(cycle)/act/while:", "act"),
    ("jit(cycle)/flush:", "flush"),
    ("jit(cycle)/act/while/body/learn/add:", "act"),
    ("jit(cycle)/render/sin:", None),
    ("jit(<lambda>)/pallas_call:", None),
    ("", None),
    (None, None),
])
def test_scope_of(path, want):
    assert scopes.scope_of(path) == want


@pytest.mark.parametrize("name,want", [
    ("%while.3 = (s32[], f32[8]{0:T(128)}) while((s32[], f32[8]{0}) %t), "
     "condition=%c, body=%b", "while"),
    ("%closed_call.3 = (s32[32]{0}, f32[2]{0}) call(f32[8]{0} %p), "
     "to_apply=%f", "call"),
    ("%conditional = s32[] conditional(pred[] %p, s32[] %a, s32[] %b)",
     "conditional"),
    ("%copy-start.1 = (f32[32]{0:T(128)S(1)}, f32[32]{0:T(128)}, "
     "u32[]{:S(2)}) copy-start(f32[32]{0:T(128)} %x.1)", "copy-start"),
    ("%fusion.12 = f32[32,20,20,32]{3,2,1,0} fusion(f32[32]{0} %p), "
     "kind=kLoop, calls=%f", "fusion"),
    ("jit__lambda(13463537560678651004)", ""),
])
def test_opcode_skips_the_result_shape(name, want):
    assert scopes.opcode(name) == want


WHILE = "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b"
CALL = "%closed_call.3 = (s32[32]{0}) call(f32[8]{0} %p), to_apply=%f"
UPDATE = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
COPY = "%copy.2 = f32[8]{0} copy(f32[8]{0} %p)"
KERNEL = "%custom-call.4 = s32[32,128]{1,0} custom-call(f32[8]{0} %p)"
DONE = "%copy-done.5 = f32[8]{0} copy-done((f32[8]{0}, u32[]) %s)"
FLUSH = "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
STRAY = "%copy.8 = f32[8]{0} copy(f32[8]{0} %q)"


def _hand_trace():
    dev0 = [(0.0, 100.0, WHILE), (10.0, 30.0, UPDATE), (30.0, 40.0, COPY),
            (40.0, 70.0, CALL), (45.0, 65.0, KERNEL), (65.0, 69.0, DONE),
            (110.0, 130.0, FLUSH), (130.0, 140.0, STRAY)]
    dev1 = [(0.0, 50.0, FLUSH)]
    host = [(0.0, 150.0, tr.WINDOW)]
    t = tr.Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host,
                 (0.0, 150.0))
    names = {WHILE: "jit(c)/vmap(learn)/while:",
             UPDATE: "jit(c)/vmap(learn)/while/body/update/mul:",
             CALL: "jit(c)/vmap(learn)/while/body/sample/closed_call:",
             FLUSH: "jit(c)/vmap(flush)/add:"}
    return t, {"/device:TPU:0": names, "/device:TPU:1": {FLUSH: names[FLUSH]}}


def test_scoped_time_falls_back_to_the_enclosing_container():
    t, names = _hand_trace()
    got = scopes.scoped_time(t, names)
    chip = got["chips"]["/device:TPU:0"]
    # the copy takes the while's scope; the kernel and copy-done inside
    # the call named closed_call (opcode call) take the call's scope
    assert chip["learn"]["inclusive_s"] == pytest.approx((20 + 10 + 24) * 1e-9)
    assert chip["learn"]["self_s"] == pytest.approx(10e-9)
    assert chip["learn/update"]["inclusive_s"] == pytest.approx(20e-9)
    assert chip["learn/sample"]["inclusive_s"] == pytest.approx(24e-9)
    assert chip["flush"]["inclusive_s"] == pytest.approx(20e-9)
    # busy 130 ns; the loop's own span and the stray copy are unscoped
    assert chip["unscoped"]["inclusive_s"] == pytest.approx((130 - 74) * 1e-9)
    assert chip["unscoped"]["loops_s"] == pytest.approx((130 - 84) * 1e-9)
    assert chip["unscoped"]["top"] == [["copy", pytest.approx(10e-9)]]
    # containers are never ops of their own
    kinds = {k for row in chip.values() for k, _ in row["top"]}
    assert not kinds & {"while", "closed_call"}
    assert "act" not in chip
    mean = got["mean"]
    assert mean["flush"]["inclusive_s"] == pytest.approx((20 + 50) / 2 * 1e-9)
    assert mean["learn"]["inclusive_s"] == pytest.approx(54 / 2 * 1e-9)
    assert got["busy_s"] == pytest.approx((130 + 50) / 2 * 1e-9)


def test_a_container_without_a_scope_takes_what_its_ops_share():
    """The TPU profiler gives a while loop no tf_op: the loop takes the
    innermost scope its scoped ops share, and passes it to the ops in
    it that carry none."""
    loop = WHILE.replace("%while.1", "%while.2")
    policy = UPDATE.replace("%fusion.1", "%fusion.21")
    env = UPDATE.replace("%fusion.1", "%fusion.22")
    evs = [(0.0, 100.0, loop), (0.0, 20.0, policy), (20.0, 30.0, COPY),
           (30.0, 60.0, env), (70.0, 80.0, STRAY)]
    names = {policy: "jit(c)/vmap(act)/while/body/policy/dot_general:",
             env: "jit(c)/vmap(act)/while/body/env/add:"}
    t = tr.Trace({"/device:TPU:0": evs}, [], (0.0, 100.0))
    chip = scopes.scoped_time(t, {"/device:TPU:0": names})["mean"]
    assert chip["act"]["self_s"] == pytest.approx(20e-9)
    assert chip["act"]["inclusive_s"] == pytest.approx(70e-9)
    assert chip["act/policy"]["inclusive_s"] == pytest.approx(20e-9)
    assert chip["unscoped"]["inclusive_s"] == pytest.approx(30e-9)


def test_op_scopes_read_the_traces_own_metadata():
    got = scopes.op_scopes(str(SMALL))
    assert list(got) == ["/device:TPU:0"]
    ops = got["/device:TPU:0"]
    kernel = [n for n in ops if " custom-call(" in n]
    assert len(kernel) == 1
    assert ops[kernel[0]] == "jit(<lambda>)/pallas_call:"
    t = tr.read(str(SMALL))
    starts = {n for _, _, n in t.devices["/device:TPU:0"]
              if scopes.opcode(n) == "copy-start"}
    assert starts and not starts & set(ops)


def test_existing_readers_read_the_small_trace_as_before():
    """The readers the benchmark had give the values they gave before
    the scope reduction was added."""
    t = tr.read(str(SMALL))
    ctx = {"trace": t, "cell": cells.load_cell("rainbow-nature.p1"),
           "peak": peaks("TPU v5 lite"), "chips": 1}
    assert cells.load_reader("device_idle_share")(ctx) == pytest.approx(
        99.71394295890364, rel=1e-12)
    assert cells.load_reader("segment_tree_roofline")(ctx) == pytest.approx(
        0.47059825189777643, rel=1e-12)
    assert tr.op_totals(t)[0] == ["copy-done", pytest.approx(4.206e-06)]


def test_clock_offset_pairs_runs_by_run_id():
    from jax.profiler import ProfileData
    got = scopes.clock_offset(ProfileData.from_file(str(SMALL)))
    assert got["runs"] == 4
    assert got["least_ms"] == pytest.approx(-1.37378)
    assert got["median_ms"] == pytest.approx(-1.308054)


def test_traced_cycles_count_the_windows_dispatches():
    assert scopes.traced_cycles({"trace": tr.read(str(SMALL))}) == 2
    assert scopes.traced_cycles({"traced_cycles": 3}) == 3


@pytest.mark.parametrize("config,row,updates", [
    ("dqn-nature", 2 * 84 * 84 * 4 + 4 + 4 + 1, 10_000 // 4),
    ("rainbow-nature", 2 * 84 * 84 * 4 + 4 + 4 + 1 + 4 + 4, 8_000 // 4),
])
def test_replay_sample_bytes(config, row, updates):
    cfg = cells.load_cell(f"{config}.p1").config
    assert replay.row_bytes(cfg) == row
    assert replay.updates_per_cycle(cfg) == updates
    assert replay.sample_bytes_per_cycle(cfg) == updates * 32 * row


def _ctx(tmp_path, variant, with_path=True):
    cell = cells.load_cell(CELL, make(tmp_path, CONFIGS[variant]))
    ctx = {"trace": tr.read(str(SCOPED[variant])), "cell": cell,
           "chips": 1}
    if with_path:
        ctx["trace_path"] = str(SCOPED[variant])
    return ctx


@pytest.mark.parametrize("variant", ["dqn", "rainbow"])
def test_new_readers_on_a_recorded_scoped_cycle(tmp_path, variant, capsys):
    """One cycle of the tiny cell, recorded on a TPU v5 lite: each new
    reader gives a finite value (per_tree_ms only where replay is
    prioritized), and the run logs one scope table with its unscoped
    share."""
    ctx = _ctx(tmp_path, variant)
    values = {m: cells.load_reader(m)(ctx) for m in NEW}
    for name, value in values.items():
        if name == "per_tree_ms" and variant == "dqn":
            assert value is None
        else:
            assert value is not None and math.isfinite(value), name
            assert value > 0, name
    assert values["render_share"] < 100
    table = ctx["scopes"]
    assert table["cycles"] == 1
    assert "unscoped" in table["mean"]
    assert set(table["mean"]) - {"unscoped"} <= set(scopes.SCOPES)
    err = capsys.readouterr().err
    assert err.count("[bench] scopes: ") == 1
    assert '"unscoped"' in err and '"share"' in err
    assert err.count("[bench] clock offset") == 1


def test_readers_find_the_profile_without_its_path(tmp_path, monkeypatch):
    """The harness passes readers the trace but not its file: the
    reduction finds the file by its traced window among the harness's
    trace directories."""
    tmp = tmp_path / "tmp"
    for variant in ("rainbow", "dqn"):
        d = tmp / f"bench_trace_{variant}" / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        shutil.copy(SCOPED[variant], d / "host.xplane.pb")
    monkeypatch.setattr(tempfile, "tempdir", str(tmp))
    ctx = _ctx(tmp_path, "rainbow", with_path=False)
    path, _ = scopes.find_profile(ctx)
    assert path.endswith("bench_trace_rainbow/plugins/profile/1/host.xplane.pb")
    assert cells.load_reader("per_tree_ms")(ctx) > 0


def test_readers_are_silent_on_a_trace_without_scopes():
    """On a program without named scopes (the parent's) every op is
    unscoped and the new readers give nothing."""
    ctx = {"trace": tr.read(str(SMALL)), "trace_path": str(SMALL),
           "cell": cells.load_cell("dqn-nature.p1"), "chips": 1}
    assert all(cells.load_reader(m)(ctx) is None for m in NEW)
    assert set(ctx["scopes"]["mean"]) == {"unscoped"}
