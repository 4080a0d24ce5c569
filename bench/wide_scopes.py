"""Device time in a recurrent net's scopes, besides ``SCOPES``.

``bench/scopes.py`` attributes device time to a fixed tuple of scopes
(``SCOPES``). A recurrent net's cycle (``core/concurrent.py``
``RECURRENT_SCOPES``) has three more: ``act/lstm`` (the actor's LSTM
step), ``learn/burn_in`` (the learner's no-gradient unrolls) and
``learn/lstm`` (its recurrences, forward and backward). ``table(ctx)``
attributes the run's profile to ``SCOPES`` and these together, by
``scopes.py``'s own rule (the innermost matching component on an op's
``tf_op`` path, a nested scope only inside its parent; an op with none
takes the scope of its innermost enclosing loop), keeps the recurrent
scopes' times in ``ctx["wide_scopes"]`` and logs them. ``scopes.py``
is left as it is: its table keeps their time under their parents.
Where the profile has no op in them, as a feed-forward cell's has not,
a reader gets None.
"""

from __future__ import annotations

import json
import traceback
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

from bench import scopes
from bench import trace as tr

RECURRENT = ("act/lstm", "learn/burn_in", "learn/lstm")
WIDE = scopes.SCOPES + RECURRENT


def scope_of(path: Optional[str]) -> Optional[str]:
    """``scopes.scope_of`` over ``WIDE``."""
    if not path:
        return None
    parts = path.split("/")
    parts[-1] = parts[-1].split(":", 1)[0]
    scope = None
    for part in map(scopes._unwrap, parts):
        inner = part if scope is None else f"{scope}/{part}"
        if inner in WIDE:
            scope = inner
    return scope


def attribute(events: List[tr.Interval], names: Dict[str, str]
              ) -> Dict[Optional[str], List[tr.Interval]]:
    """``scopes._attribute`` with ``scope_of`` over ``WIDE``: the ops of
    one chip by scope, container events dropped."""
    kinds = {}
    order = []
    for s, e, name in events:
        if name not in kinds:
            kinds[name] = (scopes.opcode(name) in scopes.CONTAINER_OPCODES,
                           scope_of(names.get(name)))
        order.append((s, -e, not kinds[name][0], s, e, name))
    order.sort()
    scope_at: Dict[int, Optional[str]] = {}
    open_: List[List] = []                # [end, index, own, shared]
    for i, (_, _, _, s, e, name) in enumerate(order):
        while open_ and open_[-1][0] <= s:
            end, j, own, shared = open_.pop()
            scope_at[j] = own or shared or None
        container, scope = kinds[name]
        if container:
            open_.append([e, i, scope, scopes._UNSET])
        elif scope is not None:
            for frame in open_:
                frame[3] = scope if frame[3] == scopes._UNSET else \
                    scopes._shared(frame[3], scope)
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


def recurrent_time(trace: tr.Trace, names: Dict[str, Dict[str, str]]
                   ) -> Dict[str, Dict[str, Any]]:
    """Mean over chips of the device seconds in the window in each
    recurrent scope that some op carries: ``inclusive_s``, ``self_s``
    and ``top``, as ``scopes.scoped_time`` gives them."""
    lo, hi = trace.window
    n = max(len(trace.devices), 1)
    out: Dict[str, Dict[str, Any]] = {}
    kinds_sum: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    for plane, events in trace.devices.items():
        by_scope = attribute(events, names.get(plane, {}))
        for scope in RECURRENT:
            inside = [iv for sc, ivs in by_scope.items() if sc is not None
                      and (sc == scope or sc.startswith(scope + "/"))
                      for iv in ivs]
            if not inside:
                continue
            for k, v in scopes._kinds(inside, lo, hi).items():
                kinds_sum[scope][k] += v
            row = out.setdefault(scope, {"inclusive_s": 0.0, "self_s": 0.0})
            row["inclusive_s"] += scopes._union_s(inside, lo, hi) / n
            row["self_s"] += scopes._union_s(by_scope.get(scope, []),
                                             lo, hi) / n
    for scope, row in out.items():
        row["top"] = scopes._top(kinds_sum[scope], 1.0 / n)
    return out


def table(ctx) -> Optional[Dict[str, Any]]:
    """``{"mean": {scope: row}, "cycles": n}`` for the run's profile,
    computed once per ``ctx``; None where there is no profile."""
    if "wide_scopes" in ctx:
        return ctx["wide_scopes"]
    ctx["wide_scopes"] = None
    try:
        path, _ = scopes.find_profile(ctx)
        if path is None:
            return None
        got = {"mean": recurrent_time(ctx["trace"], scopes.op_scopes(path)),
               "cycles": scopes.traced_cycles(ctx)}
    except Exception:       # a per-layer reading must not end the run
        scopes.log("[bench] recurrent scopes: the reduction failed\n"
                   + traceback.format_exc())
        return None
    ctx["wide_scopes"] = got
    if got["mean"]:
        scopes.log(f"[bench] recurrent scopes: "
                   f"{json.dumps(dict(cycles=got['cycles'], **got['mean']))}")
    return got


def per_cycle_s(ctx, scope: str) -> Optional[float]:
    """Mean device seconds per traced cycle in ``scope`` (inclusive),
    or None where the trace has no op in it."""
    got = table(ctx)
    if not got or scope not in got["mean"] or not got["cycles"]:
        return None
    return got["mean"][scope]["inclusive_s"] / got["cycles"]
