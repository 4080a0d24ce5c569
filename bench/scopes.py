"""Attribute a traced cycle's device time to the program's named scopes.

    python -m bench.scopes <file.xplane.pb>   # print the scope table

The C-cycle names its phases with ``jax.named_scope``
(``core/concurrent.py``): ``act`` with ``policy``, ``env`` and
``render`` inside it, ``per_tree``, ``learn`` with ``sample`` and
``update`` inside it, and ``flush``. A scope lands in the op_name of
every HLO op traced inside it, and the TPU profiler keeps the op_name as
the ``tf_op`` stat of the op's event metadata
(``bench/xplane.py``), for instance
``jit(cycle)/vmap(act)/while/body/closed_call/render/sin:``.

Each op on a chip's ``XLA Ops`` line takes the innermost scope of its
own ``tf_op``. An op without one (XLA's ``copy-start``/``copy-done``
and layout copies carry none) takes the scope of the innermost loop,
call or conditional whose event encloses it in time on the same chip:
those events span their bodies. They are known by their opcode, never
by their name (a ``call`` is often named ``closed_call``), and are not
counted as time of their own. A container whose own metadata has no
scope (the TPU profiler gives a ``while`` none) takes the innermost
scope that all the scoped ops it encloses share. A scope's time is the
union of its ops' intervals in the window, nested scopes included; what
is left of the chip's busy time is ``unscoped``: ops no scope reaches,
and ``loops_s``, the time in which only a container runs.

The per-layer readers (``act_ms``, ``learn_ms``, ``flush_ms``,
``per_tree_ms``, ``render_share``, ``replay_sample_gbps``) share one
table per run through ``table(ctx)``, which also logs it and the
host-to-device clock offset on standard error.
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
import sys
import tempfile
import traceback
import warnings
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

from bench import trace as tr
from bench import xplane

SCOPES = ("act", "act/policy", "act/env", "act/render", "per_tree",
          "learn", "learn/sample", "learn/update", "flush")
UNSCOPED = "unscoped"
DISPATCH = "bench.cycle_dispatch"     # the harness's annotation per cycle
CONTAINER_OPCODES = ("while", "call", "conditional")
TOP_KINDS = 3

_WRAPPED = re.compile(r"[\w.]+\((.+)\)")
_OPCODE = re.compile(r"([a-z][a-z0-9-]*)\(")


def op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """Per chip (``/device:TPU:n`` plane), each op event's name (its
    HLO text) -> its ``tf_op``, for the ops that carry one."""
    out = {}
    for plane, events in xplane.event_metadata(path, tr.DEVICE_PLANE).items():
        out[plane] = {name: stats["tf_op"] for name, stats in events.items()
                      if stats.get("tf_op")}
    return out


def _unwrap(part: str) -> str:
    """``vmap(act)`` -> ``act``; ``shard_map(vmap(learn))`` -> ``learn``."""
    while (m := _WRAPPED.fullmatch(part)):
        part = m.group(1)
    return part


def scope_of(path: Optional[str]) -> Optional[str]:
    """The innermost scope of ``SCOPES`` on an op_name path, or None.
    Components are matched with their transform wrappers removed, a
    nested scope only inside its parent; a trailing ``:<type>`` (as the
    profiler writes ``tf_op``) is dropped."""
    if not path:
        return None
    parts = path.split("/")
    parts[-1] = parts[-1].split(":", 1)[0]
    scope = None
    for part in map(_unwrap, parts):
        inner = part if scope is None else f"{scope}/{part}"
        if inner in SCOPES:
            scope = inner
    return scope


def opcode(name: str) -> str:
    """The HLO opcode of an op event's name: ``%while.3 = (s32[], ...)
    while(...)`` -> ``while``; the result shape, a tuple included, is
    skipped."""
    _, sep, rest = name.partition(" = ")
    if not sep:
        return ""
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    m = _OPCODE.match(rest.lstrip())
    return m.group(1) if m else ""


def _shared(a: Optional[str], b: str) -> Optional[str]:
    """The innermost scope two scope paths share (None if none)."""
    if a is None:
        return None
    common = []
    for x, y in zip(a.split("/"), b.split("/")):
        if x != y:
            break
        common.append(x)
    return "/".join(common) or None


_UNSET = ""


def _attribute(events: List[tr.Interval], names: Dict[str, str]
               ) -> Dict[Optional[str], List[tr.Interval]]:
    """The ops of one chip by scope (None where no scope reaches them);
    container events are dropped."""
    kinds = {}
    order = []
    for s, e, name in events:
        if name not in kinds:
            kinds[name] = (opcode(name) in CONTAINER_OPCODES,
                           scope_of(names.get(name)))
        container = kinds[name][0]
        # containers open before the ops that start with them
        order.append((s, -e, not container, s, e, name))
    order.sort()
    # each container's scope: its own, else what its scoped ops share
    scope_at: Dict[int, Optional[str]] = {}
    open_: List[List] = []                # [end, index, own, shared]
    for i, (_, _, _, s, e, name) in enumerate(order):
        while open_ and open_[-1][0] <= s:
            end, j, own, shared = open_.pop()
            scope_at[j] = own or shared or None
        container, scope = kinds[name]
        if container:
            open_.append([e, i, scope, _UNSET])
        elif scope is not None:
            for frame in open_:
                frame[3] = scope if frame[3] == _UNSET else _shared(
                    frame[3], scope)
    for end, j, own, shared in open_:
        scope_at[j] = own or shared or None
    stack: List[Tuple[float, Optional[str]]] = []     # (end, scope)
    out: Dict[Optional[str], List[tr.Interval]] = defaultdict(list)
    for i, (_, _, _, s, e, name) in enumerate(order):
        while stack and stack[-1][0] <= s:
            stack.pop()
        container, scope = kinds[name]
        if container:
            scope = scope_at[i]
        if scope is None and stack:
            scope = stack[-1][1]
        if container:
            stack.append((e, scope))
        else:
            out[scope].append((s, e, name))
    return out


def _union_s(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in tr.clip(tr.merge(intervals), lo, hi)) * 1e-9


def _kinds(intervals, lo: float, hi: float) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for s, e, name in intervals:
        if min(e, hi) > max(s, lo):
            out[tr.op_kind(name)] += (min(e, hi) - max(s, lo)) * 1e-9
    return out


def _top(kinds: Dict[str, float], scale: float = 1.0) -> List[List]:
    ranked = sorted(kinds.items(), key=lambda kv: -kv[1])[:TOP_KINDS]
    return [[k, v * scale] for k, v in ranked]


def scoped_time(trace: tr.Trace, scopes: Dict[str, Dict[str, str]]
                ) -> Dict[str, Any]:
    """Device seconds in the window per scope, per chip and as the mean
    over chips: ``inclusive_s`` (nested scopes included), ``self_s``
    (the scope's own ops) and ``top`` (the op kinds, by ``op_kind``,
    that took most of its time), with an ``unscoped`` entry for the
    busy time no scope covers and ``busy_s``. A scope no op carries is
    left out. ``unscoped`` also gives ``loops_s``, its part in which
    only a container event runs."""
    lo, hi = trace.window
    busy = tr.busy_s(trace)
    chips: Dict[str, Dict[str, Dict[str, Any]]] = {}
    kinds_sum: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    for plane, events in trace.devices.items():
        by_scope = _attribute(events, scopes.get(plane, {}))
        table: Dict[str, Dict[str, Any]] = {}
        for scope in SCOPES:
            inside = [iv for sc, ivs in by_scope.items() if sc is not None
                      and (sc == scope or sc.startswith(scope + "/"))
                      for iv in ivs]
            if not inside:
                continue
            kinds = _kinds(inside, lo, hi)
            for k, v in kinds.items():
                kinds_sum[scope][k] += v
            table[scope] = {"inclusive_s": _union_s(inside, lo, hi),
                            "self_s": _union_s(by_scope.get(scope, []),
                                               lo, hi),
                            "top": _top(kinds)}
        scoped = [iv for sc, ivs in by_scope.items() if sc is not None
                  for iv in ivs]
        ops = [iv for ivs in by_scope.values() for iv in ivs]
        rest = max(busy[plane] - _union_s(scoped, lo, hi), 0.0)
        loops = max(busy[plane] - _union_s(ops, lo, hi), 0.0)
        kinds = _kinds(by_scope.get(None, []), lo, hi)
        for k, v in kinds.items():
            kinds_sum[UNSCOPED][k] += v
        table[UNSCOPED] = {"inclusive_s": rest, "self_s": rest,
                           "loops_s": loops, "top": _top(kinds)}
        chips[plane] = table
    n = max(len(chips), 1)
    mean: Dict[str, Dict[str, Any]] = {}
    for scope in SCOPES + (UNSCOPED,):
        rows = [t[scope] for t in chips.values() if scope in t]
        if rows:
            mean[scope] = {
                "inclusive_s": sum(r["inclusive_s"] for r in rows) / n,
                "self_s": sum(r["self_s"] for r in rows) / n,
                "top": _top(kinds_sum[scope], 1.0 / n)}
    if UNSCOPED in mean:
        mean[UNSCOPED]["loops_s"] = sum(
            t[UNSCOPED]["loops_s"] for t in chips.values()) / n
    return {"chips": chips, "mean": mean,
            "busy_s": sum(busy.values()) / n}


def clock_offset(pd) -> Optional[Dict[str, float]]:
    """Device clock against host clock in one profile (a
    ``ProfileData``): for each program run, the start of its ``XLA
    Modules`` event on a chip minus the earliest host event that
    carries the same ``run_id`` (its launch), in ms; the least, the
    median and the number of runs paired. None where nothing pairs."""
    launch: Dict[int, float] = {}
    starts: List[Tuple[int, float]] = []
    events = ((plane.name, line.name, e) for plane in pd.planes
              for line in plane.lines for e in line.events
              if plane.name == tr.HOST_PLANE
              or (tr.DEVICE_PLANE.match(plane.name)
                  and line.name == "XLA Modules"))
    with warnings.catch_warnings():
        # jaxlib's stats type warns once that it has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane, _, e in events:
            run = dict(e.stats).get("run_id")
            if run is None:
                continue
            start = float(e.start_ns)
            if plane != tr.HOST_PLANE:
                starts.append((int(run), start))
            elif start < launch.get(int(run), float("inf")):
                launch[int(run)] = start
    offsets = [(s - launch[r]) * 1e-6 for r, s in starts if r in launch]
    if not offsets:
        return None
    return {"least_ms": min(offsets), "median_ms": statistics.median(offsets),
            "runs": len(offsets)}


def _window_of(pd) -> Optional[Tuple[float, float]]:
    plane = pd.find_plane_with_name(tr.HOST_PLANE)
    for line in plane.lines if plane is not None else ():
        for e in line.events:
            if e.name == tr.WINDOW:
                return float(e.start_ns), float(e.start_ns + e.duration_ns)
    return None


def find_profile(ctx) -> Tuple[Optional[str], Any]:
    """The ``.xplane.pb`` that ``ctx["trace"]`` was read from, and its
    ``ProfileData``: ``ctx["trace_path"]`` where the harness gives it,
    else the newest profile under the harness's trace directories
    (``bench_trace_*`` in the temporary directory) whose traced window
    is the trace's own."""
    from jax.profiler import ProfileData
    t = ctx.get("trace")
    if ctx.get("trace_path"):
        path = ctx["trace_path"]
        return path, ProfileData.from_file(path)
    if t is None:
        return None, None
    want = [(h[0], h[1]) for h in t.host if h[2] == tr.WINDOW][:1]
    found = glob.glob(os.path.join(tempfile.gettempdir(), "bench_trace_*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        pd = ProfileData.from_file(path)
        if [_window_of(pd)] == want:
            return path, pd
    return None, None


def traced_cycles(ctx) -> int:
    """Cycles in the traced window: ``ctx["traced_cycles"]`` where the
    harness gives it, else the window's ``bench.cycle_dispatch``
    annotations."""
    if ctx.get("traced_cycles"):
        return int(ctx["traced_cycles"])
    t = ctx["trace"]
    windows = [(s, e) for s, e, name in t.host if name == tr.WINDOW]
    if not windows:
        return 0
    lo, hi = windows[0]
    return sum(1 for s, e, name in t.host
               if name == DISPATCH and lo <= s and e <= hi)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def table(ctx) -> Optional[Dict[str, Any]]:
    """The run's scope table, made once per ``ctx`` and kept in it
    (``ctx["scopes"]``): ``scoped_time`` with ``cycles``, the traced
    cycles. Logs ``[bench] scopes: {...}`` (seconds per scope, inclusive
    and self, its top op kinds, and the unscoped share of busy time)
    and the clock offset. None where there is no profile to read."""
    if "scopes" in ctx:
        return ctx["scopes"]
    ctx["scopes"] = None
    try:
        path, pd = find_profile(ctx)
        if path is None:
            log("[bench] scopes: no profile found for the trace")
            return None
        got = scoped_time(ctx["trace"], op_scopes(path))
        got["cycles"] = traced_cycles(ctx)
        offset = clock_offset(pd)
    except Exception:       # a per-layer reading must not end the run
        log("[bench] scopes: the reduction failed\n"
            + traceback.format_exc())
        return None
    ctx["scopes"] = got
    busy = got["busy_s"]
    summary: Dict[str, Any] = {"cycles": got["cycles"], "busy_s": busy}
    for scope, row in got["mean"].items():
        summary[scope] = {"incl_s": row["inclusive_s"],
                          "self_s": row["self_s"], "top": row["top"]}
    if UNSCOPED in got["mean"] and busy > 0:
        summary[UNSCOPED].update(
            loops_s=got["mean"][UNSCOPED]["loops_s"],
            share=got["mean"][UNSCOPED]["inclusive_s"] / busy)
    log(f"[bench] scopes: {json.dumps(summary)}")
    log(f"[bench] clock offset (device start - host launch): "
        f"{json.dumps(offset)}")
    return got


def per_cycle_s(ctx, scope: str) -> Optional[float]:
    """Mean device seconds per traced cycle in ``scope`` (inclusive),
    or None where the trace has no op in it."""
    got = table(ctx)
    if not got or scope not in got["mean"] or not got["cycles"]:
        return None
    return got["mean"][scope]["inclusive_s"] / got["cycles"]


if __name__ == "__main__":
    t = tr.read(sys.argv[1])
    print(json.dumps(table({"trace": t, "trace_path": sys.argv[1]}),
                     indent=1))
