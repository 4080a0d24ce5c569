"""flush_ms (ms): device ms per cycle in the ``flush`` scope: staged
priorities, n-step aggregation and the staged transitions into replay.
Nested scopes included; an op without a scope of its own takes its
enclosing loop's (``bench/scopes.py``). Mean over the traced cycles
and the cell's chips."""

from bench import scopes


def read(ctx):
    secs = scopes.per_cycle_s(ctx, "flush")
    return None if secs is None else 1e3 * secs
