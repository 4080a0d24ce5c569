"""The trace reduction: unions, kernel sums and gap attribution, on
hand-made intervals and on a small trace recorded on one TPU v5e."""

import pytest

from tiny_cell import ROOT

from bench import trace as tr

SMALL = ROOT / "bench" / "testdata" / "small_tpu.xplane.pb"


def _trace():
    dev0 = [(10.0, 20.0, "fusion.1"), (15.0, 30.0, "convolution.2"),
            (40.0, 50.0, "custom-call.3 | _seg_kernel"), (90.0, 120.0,
                                                          "fusion.4")]
    dev1 = [(0.0, 100.0, "fusion.1")]
    host = [(0.0, 100.0, tr.WINDOW), (30.0, 45.0, "bench.cycle_dispatch"),
            (45.0, 100.0, "bench.metrics_fetch")]
    return tr.Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host,
                    (0.0, 100.0))


def test_merge_and_clip():
    assert tr.merge([(3, 5), (0, 1), (1, 2), (4, 8)]) == [(0, 2), (3, 8)]
    assert tr.clip([(0, 2), (3, 8)], 1, 4) == [(1, 2), (3, 4)]


def test_busy_is_the_union_inside_the_window():
    busy = tr.busy_s(_trace())
    assert busy["/device:TPU:0"] == pytest.approx((20 + 10 + 10) * 1e-9)
    assert busy["/device:TPU:1"] == pytest.approx(100e-9)
    assert tr.mean_busy_s(_trace()) == pytest.approx(70e-9)


def test_kernel_time_sums_matching_events():
    secs, n = tr.kernel_time(_trace(), r"_seg_kernel")
    assert (secs, n) == (pytest.approx(10e-9), 1)
    # the event that runs past the window's end is not counted
    secs, n = tr.kernel_time(_trace(), r"^fusion")
    assert n == 2 and secs == pytest.approx(110e-9)


def test_idle_gaps_are_named_after_the_host_phase():
    gaps = tr.idle_gaps(_trace())
    assert gaps[0] == ["bench.metrics_fetch", pytest.approx(40e-9)]
    assert ["host.none", pytest.approx(10e-9)] in gaps
    assert ["bench.cycle_dispatch", pytest.approx(10e-9)] in gaps


def test_op_totals_strip_numeric_suffixes():
    ops = dict(tr.op_totals(_trace()))
    assert ops["fusion"] == pytest.approx((10 + 100) * 1e-9 / 2)
    assert tr.op_kind("%convolution.24 = f32[32,20,20,32]{3,2,1,0} "
                      "convolution(...)") == "convolution"
    assert tr.op_kind("%copy-done = f32[2048]{0} copy-done(...)") == \
        "copy-done"


def test_recorded_tpu_trace():
    """Two steps of a jitted XLA program and the segment_tree Mosaic
    kernel, recorded on a TPU v5 lite inside the harness's annotations."""
    t = tr.read(str(SMALL))
    assert list(t.devices) == ["/device:TPU:0"]
    assert t.window_s > 0
    busy = tr.mean_busy_s(t)
    assert 0 < busy < t.window_s
    from bench import cells
    reader = cells.load_reader("segment_tree_roofline")
    pattern = reader.__globals__["PATTERN"]
    secs, n = tr.kernel_time(t, pattern)
    assert n == 2 and 0 < secs < busy
    assert tr.kernel_time(t, cells.load_reader(
        "categorical_projection_roofline").__globals__["PATTERN"])[1] == 0
    names = {label for label, _ in tr.idle_gaps(t)}
    assert names <= {"bench.cycle_dispatch", "bench.metrics_fetch",
                     "host.none"}
    assert "bench.metrics_fetch" in names or "bench.cycle_dispatch" in names
    ops = tr.op_totals(t, top=1000)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert {k for k, _ in ops} >= {"copy-done", "_lambda_"}
