"""Telemetry subsystem guarantees (repro.telemetry):

1. spans nest and order correctly (depth/parent/seq), and both sinks
   round-trip: JSONL re-loads record-for-record, the Chrome trace is
   valid `trace_event` JSON with time-consistent nesting;
2. `NullTracer` has API parity with `Tracer` method-for-method and
   writes nothing anywhere;
3. tracing is bitwise-neutral: a traced 2-cycle dqn run produces the
   identical carry to an untraced one;
4. `jax.monitoring` duration events are captured while (and only
   while) a tracer is active;
5. `trace_report` summarizes (compile-vs-steady split, coverage),
   diffs two traces, and gates a trace against a committed
   BENCH_<n>.json by exact row/span name — failing loudly past
   tolerance and on empty overlap;
6. `PolicyServer` flushes record queue-wait vs compute spans; sweep
   runs land per-run traces under runs/<id>/trace.jsonl.
"""

import inspect
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import AlgoSpec, ExperimentSpec, ScheduleSpec, build_trainer
from repro.configs.dqn_nature import get_variant
from repro.telemetry import (ChromeTraceSink, JsonlSink, MemorySink,
                             NullTracer, Tracer, chrome_path_for,
                             make_tracer, provenance)
from repro.telemetry import report
from repro.launch import trace_report as trace_report_cli


def _tiny_spec(**over):
    over.setdefault("mode", "concurrent")
    return ExperimentSpec(
        variant=get_variant("dqn"), envs=4, frame_size=10, net="tiny",
        schedule=ScheduleSpec(cycles=2, cycle_steps=16, prepopulate=32,
                              eval_every=1, eval_episodes=4),
        algo=AlgoSpec(minibatch_size=8, replay_capacity=128,
                      train_period=4, eps_anneal_steps=1000), **over)


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# 1. spans, nesting, sinks
# ---------------------------------------------------------------------------

def test_span_nesting_depth_parent_seq():
    sink = MemorySink()
    tr = Tracer([sink], capture_compiles=False, with_provenance=False)
    with tr.span("train"):
        with tr.span("cycle"):
            with tr.span("inner"):
                pass
        with tr.span("eval"):
            pass
    tr.close()

    spans = {r["name"]: r for r in sink.records if r["t"] == "span"}
    assert set(spans) == {"train", "cycle", "inner", "eval"}
    assert spans["train"]["depth"] == 1 and spans["train"]["parent"] is None
    assert spans["cycle"]["depth"] == 2 and spans["cycle"]["parent"] == "train"
    assert spans["inner"]["depth"] == 3 and spans["inner"]["parent"] == "cycle"
    assert spans["eval"]["parent"] == "train"
    # seq is completion order: inner closes before cycle, cycle before train
    assert (spans["inner"]["seq"] < spans["cycle"]["seq"]
            < spans["eval"]["seq"] < spans["train"]["seq"])
    # time containment: children fit inside their parents
    for child, parent in (("inner", "cycle"), ("cycle", "train"),
                          ("eval", "train")):
        c, p = spans[child], spans[parent]
        assert c["ts"] >= p["ts"] - 1e-6
        assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-6
    assert sink.closed


def test_counters_accumulate_and_flush_at_close():
    sink = MemorySink()
    tr = Tracer([sink], capture_compiles=False, with_provenance=False)
    tr.count("env_steps", 128)
    tr.count("env_steps", 128)
    tr.count("cycles")
    assert tr.counters == {"env_steps": 256.0, "cycles": 1.0}
    tr.close()
    counters = {r["name"]: r["value"] for r in sink.records
                if r["t"] == "counter"}
    assert counters == {"env_steps": 256.0, "cycles": 1.0}
    tr.close()  # idempotent: no duplicate counter records
    assert sum(r["t"] == "counter" for r in sink.records) == 2


def test_point_and_complete_record_explicit_durations():
    sink = MemorySink()
    tr = Tracer([sink], capture_compiles=False, with_provenance=False)
    tr.point("cycle_dqn_p1", 1500.0, derived="x")
    a = time.perf_counter()
    b = a + 0.01
    tr.complete("queue_wait", a, b, batch=4)
    tr.close()
    spans = {r["name"]: r for r in sink.records if r["t"] == "span"}
    assert spans["cycle_dqn_p1"]["dur"] == pytest.approx(1500.0)
    assert spans["cycle_dqn_p1"]["attrs"]["point"] is True
    assert spans["queue_wait"]["dur"] == pytest.approx(1e4, rel=1e-3)
    assert spans["queue_wait"]["attrs"] == {"batch": 4}


def test_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tr = Tracer([JsonlSink(path)], meta={"env": "catch"},
                capture_compiles=False)
    with tr.span("train"):
        with tr.span("cycle", index=1):
            pass
    tr.count("cycles", 1)
    tr.event("marker", note="hi")
    tr.close()

    trace = report.load_trace(path)
    assert trace["meta"]["attrs"] == {"env": "catch"}
    assert set(trace["meta"]["provenance"]) >= {
        "git_sha", "git_dirty", "platform", "cpu_model", "python_version"}
    names = [s["name"] for s in trace["spans"]]
    assert names == ["cycle", "train"]
    assert trace["spans"][0]["attrs"] == {"index": 1}
    assert trace["counters"] == {"cycles": 1.0}
    assert [e["name"] for e in trace["events"]] == ["marker"]


def test_jsonl_extra_meta_per_sink(tmp_path):
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    tr = Tracer([JsonlSink(pa, extra_meta={"run": "run000"}),
                 JsonlSink(pb, extra_meta={"run": "run001"})],
                meta={"fleet": "fleet000"}, capture_compiles=False,
                with_provenance=False)
    with tr.span("cycle"):
        pass
    tr.close()
    ma = report.load_trace(pa)["meta"]["attrs"]
    mb = report.load_trace(pb)["meta"]["attrs"]
    assert ma == {"fleet": "fleet000", "run": "run000"}
    assert mb == {"fleet": "fleet000", "run": "run001"}
    # the span stream itself is shared
    assert (report.load_trace(pa)["spans"]
            == report.load_trace(pb)["spans"])


def test_chrome_trace_round_trip(tmp_path):
    path = str(tmp_path / "t.chrome.json")
    tr = Tracer([ChromeTraceSink(path)], meta={"env": "catch"},
                capture_compiles=False, with_provenance=False)
    with tr.span("train"):
        with tr.span("cycle", index=1):
            pass
    tr.count("cycles", 2)
    tr.close()

    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    phases = [e for e in events if e.get("ph") == "X"]
    byname = {e["name"]: e for e in phases}
    assert set(byname) == {"train", "cycle"}
    # Perfetto essentials: complete events with ts+dur on one pid/tid,
    # nested child inside parent's interval
    c, p = byname["cycle"], byname["train"]
    assert c["tid"] == p["tid"] and c["pid"] == p["pid"]
    assert c["ts"] >= p["ts"] and c["ts"] + c["dur"] <= p["ts"] + p["dur"]
    assert c["args"] == {"index": 1}
    counters = [e for e in events if e.get("ph") == "C"]
    assert counters and counters[0]["args"] == {"cycles": 2.0}
    assert any(e.get("ph") == "M" and e["name"] == "process_name"
               for e in events)
    assert doc["otherData"]["attrs"] == {"env": "catch"}


def test_make_tracer_paths_and_disabled_mode(tmp_path):
    assert chrome_path_for("runs/x/trace.jsonl") == \
        "runs/x/trace.chrome.json"
    assert chrome_path_for("t.log") == "t.log.chrome.json"

    tr = make_tracer(None)
    assert not tr.enabled
    with tr.span("cycle"):
        tr.count("cycles", 1)
    assert tr.counters == {"cycles": 1.0}   # counters work without sinks
    tr.close()

    path = str(tmp_path / "x" / "trace.jsonl")   # parent dir auto-created
    tr = make_tracer(path, meta={"a": 1})
    assert tr.enabled
    with tr.span("cycle"):
        pass
    tr.close()
    assert report.load_trace(path)["spans"]
    assert os.path.exists(str(tmp_path / "x" / "trace.chrome.json"))


# ---------------------------------------------------------------------------
# 2. NullTracer parity
# ---------------------------------------------------------------------------

def _public_api(cls):
    # parameters only: return annotations legitimately differ
    # (_Span vs _NullSpan, Tracer vs NullTracer)
    return {n: str(inspect.signature(m).parameters.values()) for n, m in
            inspect.getmembers(cls, callable)
            if not n.startswith("_") or n in ("__enter__", "__exit__")}


def test_null_tracer_api_parity():
    real, null = _public_api(Tracer), _public_api(NullTracer)
    assert set(real) == set(null), (
        f"Tracer/NullTracer drift: only-real={set(real) - set(null)}, "
        f"only-null={set(null) - set(real)}")
    for name in real:
        assert real[name] == null[name], \
            f"signature drift on {name}: {real[name]} != {null[name]}"
    # properties too
    for prop in ("counters", "enabled"):
        assert isinstance(inspect.getattr_static(NullTracer, prop),
                          property)


def test_null_tracer_is_inert(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)      # any accidental write would land here
    tr = NullTracer()
    with tr.span("cycle", index=1):
        with tr.span("inner"):
            pass
    tr.count("cycles", 5)
    tr.event("x")
    tr.point("y", 10.0)
    tr.complete("z", 0.0, 1.0)
    x = jnp.arange(3)
    assert tr.fence(x) is x          # identity, no block
    assert tr.counters == {}
    assert not tr.enabled
    tr.close()
    assert os.listdir(tmp_path) == []   # zero writes anywhere


def test_tracer_fence_returns_value():
    tr = Tracer((), capture_compiles=False)
    x = jnp.arange(4)
    y = tr.fence((x, {"a": x}))
    np.testing.assert_array_equal(np.asarray(y[0]), np.arange(4))
    tr.close()


def test_tracer_spans_land_on_the_profilers_host_plane(tmp_path):
    """A span is a profiler annotation too: opened under a profiler
    session, it shows by name on the trace's ``/host:CPU`` plane, on
    the clock the device ops use."""
    import glob

    from jax.profiler import ProfileData

    tr = Tracer((), capture_compiles=False)
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("cycle"):
            with tr.span("checkpoint"):
                jnp.arange(5).block_until_ready()
    tr.close()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    assert len(files) == 1
    plane = ProfileData.from_file(files[0]).find_plane_with_name("/host:CPU")
    events = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
              for line in plane.lines for e in line.events}
    assert {"cycle", "checkpoint"} <= set(events)
    outer, inner = events["cycle"], events["checkpoint"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]


# ---------------------------------------------------------------------------
# 3. compile-event capture (jax.monitoring)
# ---------------------------------------------------------------------------

def test_monitoring_events_captured_only_while_active(tmp_path):
    from jax import monitoring
    sink = MemorySink()
    tr = Tracer([sink], with_provenance=False)
    monitoring.record_event_duration_secs("/test/telemetry/fake", 0.5)
    tr.close()
    monitoring.record_event_duration_secs("/test/telemetry/late", 0.5)
    compiles = [r for r in sink.records if r["t"] == "compile"]
    assert any(c["name"] == "/test/telemetry/fake" and
               c["dur"] == pytest.approx(5e5) for c in compiles)
    assert not any(c["name"] == "/test/telemetry/late" for c in compiles)


def test_real_jit_compile_lands_in_trace(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tr = make_tracer(path)
    with tr.span("cycle"):
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    tr.close()
    trace = report.load_trace(path)
    assert any("compile" in c["name"] for c in trace["compiles"]), \
        [c["name"] for c in trace["compiles"]]
    # attribution: the compile fired inside the cycle span
    assert any(c["attrs"].get("phase") == "cycle"
               for c in trace["compiles"])


# ---------------------------------------------------------------------------
# 4. bitwise neutrality on a real 2-cycle dqn run
# ---------------------------------------------------------------------------

def test_trace_does_not_perturb_determinism(tmp_path):
    spec = _tiny_spec()

    def run(tracer):
        trainer = build_trainer(spec)
        carry = trainer.init_carry()
        for i in range(spec.schedule.cycles):
            with tracer.span("cycle", index=i + 1):
                carry, m = trainer.cycle(carry)
                if tracer.enabled:
                    tracer.fence(m)
            with tracer.span("eval", index=i + 1):
                evals = tracer.fence(trainer.eval(carry,
                                                  trainer.eval_key(i)))
        tracer.close()
        return carry, evals

    carry_null, evals_null = run(NullTracer())
    traced = make_tracer(str(tmp_path / "trace.jsonl"))
    carry_traced, evals_traced = run(traced)

    _assert_trees_equal(carry_null, carry_traced)
    _assert_trees_equal(evals_null, evals_traced)
    # and the trace itself is real: cycle + eval spans, chrome twin
    trace = report.load_trace(str(tmp_path / "trace.jsonl"))
    names = {s["name"] for s in trace["spans"]}
    assert {"cycle", "eval"} <= names
    assert os.path.exists(str(tmp_path / "trace.chrome.json"))


# ---------------------------------------------------------------------------
# 5. report: summarize / coverage / diff / bench gate
# ---------------------------------------------------------------------------

def _synthetic_trace(path, cycle_us, extra=()):
    """A hand-built JSONL trace: len(cycle_us) cycle spans under one
    train root (first span is the 'compile' one), plus extra
    (name, dur) top-level spans."""
    ts = 0.0
    records = [{"t": "meta", "version": 1, "clock": "perf_counter_us",
                "provenance": None, "attrs": {}}]
    seq = 0
    for i, dur in enumerate(cycle_us):
        seq += 1
        records.append({"t": "span", "name": "cycle", "ts": ts,
                        "dur": dur, "depth": 2, "parent": "train",
                        "seq": seq, "attrs": {"index": i + 1}})
        ts += dur
    for name, dur in extra:
        seq += 1
        records.append({"t": "span", "name": name, "ts": ts, "dur": dur,
                        "depth": 2, "parent": "train", "seq": seq,
                        "attrs": {}})
        ts += dur
    seq += 1
    records.append({"t": "span", "name": "train", "ts": 0.0, "dur": ts,
                    "depth": 1, "parent": None, "seq": seq, "attrs": {}})
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return str(path)


def test_summarize_first_vs_steady_and_coverage(tmp_path):
    # first cycle pays compile: 1000us, steady state is ~100us
    path = _synthetic_trace(tmp_path / "a.jsonl",
                            [1000.0, 100.0, 110.0, 90.0, 100.0])
    trace = report.load_trace(path)
    rows = {r["name"]: r for r in report.summarize(trace)}
    assert rows["cycle"]["count"] == 5
    assert rows["cycle"]["first_us"] == pytest.approx(1000.0)
    assert rows["cycle"]["steady_p50_us"] == pytest.approx(100.0)
    assert rows["cycle"]["p95_us"] == pytest.approx(1000.0)
    assert rows["cycle"]["pct_of_parent"] == pytest.approx(100.0)
    assert report.phase_coverage(trace, "train") == pytest.approx(1.0)
    out = report.render_summary(trace)
    assert "cycle" in out and "coverage[train]" in out


def test_diff_two_synthetic_traces(tmp_path):
    a = _synthetic_trace(tmp_path / "a.jsonl", [500.0, 100.0, 100.0],
                         extra=[("only_a", 50.0)])
    b = _synthetic_trace(tmp_path / "b.jsonl", [500.0, 150.0, 150.0])
    rows = {r["name"]: r for r in
            report.diff(report.load_trace(a), report.load_trace(b))}
    assert rows["cycle"]["delta_pct"] == pytest.approx(50.0)  # b slower
    assert rows["only_a"]["b_us"] is None
    assert rows["only_a"]["delta_pct"] is None
    text = report.render_diff(list(rows.values()), "a", "b")
    assert "+50.0%" in text


def _bench_file(path, rows):
    with open(path, "w") as f:
        json.dump({"meta": {}, "rows": rows}, f)
    return str(path)


def test_against_gate_pass_fail_and_empty_overlap(tmp_path):
    trace = report.load_trace(
        _synthetic_trace(tmp_path / "t.jsonl", [900.0, 100.0, 100.0]))
    bench = report.load_bench(_bench_file(
        tmp_path / "bench.json",
        [{"name": "cycle", "us_per_call": 80.0, "derived": ""},
         {"name": "unrelated", "us_per_call": 1.0, "derived": ""}]))
    rows = report.against(trace, bench, tolerance=2.0)
    assert len(rows) == 1     # only matching names compared
    assert rows[0]["ok"] and rows[0]["ratio"] == pytest.approx(1.25)
    rows = report.against(trace, bench, tolerance=1.1)
    assert not rows[0]["ok"]  # 1.25x > 1.1x tolerance: regression
    assert "REGRESSION" in report.render_against(rows, "bench.json", 1.1)

    empty = report.load_bench(_bench_file(
        tmp_path / "none.json",
        [{"name": "nothing_matches", "us_per_call": 1.0, "derived": ""}]))
    with pytest.raises(ValueError, match="no trace span matches"):
        report.against(trace, empty)


def test_trace_report_cli(tmp_path, capsys):
    path = _synthetic_trace(tmp_path / "t.jsonl", [900.0, 100.0, 100.0],
                            extra=[("eval", 30.0)])
    bench_ok = _bench_file(tmp_path / "ok.json",
                           [{"name": "cycle", "us_per_call": 90.0}])
    bench_bad = _bench_file(tmp_path / "bad.json",
                            [{"name": "cycle", "us_per_call": 1.0}])

    assert trace_report_cli.main([path]) == 0
    assert trace_report_cli.main(
        [path, "--require-phases", "cycle,eval",
         "--min-coverage", "0.95", "--root", "train"]) == 0
    assert trace_report_cli.main(
        [path, "--require-phases", "cycle,checkpoint"]) == 1
    assert trace_report_cli.main(
        [path, "--against", bench_ok, "--tolerance", "3"]) == 0
    assert trace_report_cli.main(
        [path, "--against", bench_bad, "--tolerance", "3"]) == 1
    assert trace_report_cli.main([path, "--diff", path]) == 0
    assert trace_report_cli.main([str(tmp_path / "missing.jsonl")]) == 2
    capsys.readouterr()


# ---------------------------------------------------------------------------
# 6. integration: PolicyServer spans + sweep per-run traces
# ---------------------------------------------------------------------------

def test_policy_server_flush_spans():
    from repro.api.serve import PolicyServer, ServeSpec
    from repro.envs.preprocess import ObsPipeline

    pipe = ObsPipeline("vector", (3,), jnp.float32)

    def qf(params, obs):
        return jnp.tile(jnp.array([0.0, 1.0]), (obs.shape[0], 1))

    sink = MemorySink()
    tracer = Tracer([sink], capture_compiles=False, with_provenance=False)
    server = PolicyServer({}, qf, pipe, frame_stack=2, n_actions=2,
                          serve=ServeSpec(policy="greedy", max_batch=4),
                          tracer=tracer)
    for sid in range(6):                     # 6 requests, max_batch 4
        server.submit(sid, np.zeros(3, np.float32), first=True)
    actions = server.flush()
    tracer.close()

    assert len(actions) == 6
    spans = [r for r in sink.records if r["t"] == "span"]
    names = [s["name"] for s in spans]
    assert names.count("serve.compute") == 2   # two microbatches
    assert names.count("serve.queue_wait") == 2
    assert names.count("serve.flush") == 1
    flush = next(s for s in spans if s["name"] == "serve.flush")
    assert flush["attrs"]["requests"] == 6
    compute = [s for s in spans if s["name"] == "serve.compute"]
    assert sorted(c["attrs"]["batch"] for c in compute) == [2, 4]
    assert all(c["parent"] == "serve.flush" for c in compute)
    counters = {r["name"]: r["value"] for r in sink.records
                if r["t"] == "counter"}
    assert counters == {"serve.actions": 6.0}

    # identical server without a tracer: identical actions (neutrality)
    server2 = PolicyServer({}, qf, pipe, frame_stack=2, n_actions=2,
                           serve=ServeSpec(policy="greedy", max_batch=4))
    for sid in range(6):
        server2.submit(sid, np.zeros(3, np.float32), first=True)
    assert server2.flush() == actions


def test_run_sweep_writes_per_run_traces(tmp_path):
    from repro.api import SweepSpec, run_sweep

    base = _tiny_spec(mode="population", seeds=1)
    sweep = SweepSpec(dir=str(tmp_path / "sweep"), base=base,
                      axes={"seed": [0, 1]})
    results = run_sweep(sweep, trace=True)
    assert len(results) == 2 and not any(r["skipped"] for r in results)

    for run_id in [r["run"] for r in results]:
        tpath = tmp_path / "sweep" / "runs" / run_id / "trace.jsonl"
        assert tpath.exists(), f"no trace for {run_id}"
        trace = report.load_trace(str(tpath))
        names = {s["name"] for s in trace["spans"]}
        assert {"cycle", "eval", "train", "init"} <= names
        assert trace["meta"]["attrs"]["run"] == run_id
        assert trace["meta"]["attrs"]["kind"] == "sweep_fleet"
        assert trace["counters"]["cycles"] == base.schedule.cycles
