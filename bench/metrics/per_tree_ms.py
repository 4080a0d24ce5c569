"""per_tree_ms (ms): device ms per cycle in the ``per_tree`` scope,
the sum-tree rebuild over the replay snapshot at the sync point (PER).
Nested scopes included; an op without a scope of its own takes its
enclosing loop's (``bench/scopes.py``). Mean over the traced cycles
and the cell's chips."""

from bench import scopes


def read(ctx):
    secs = scopes.per_cycle_s(ctx, "per_tree")
    return None if secs is None else 1e3 * secs
