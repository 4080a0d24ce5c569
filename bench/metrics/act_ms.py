"""act_ms (ms): device ms per cycle in the ``act`` scope, the sampler scan
of C/W synchronized rounds (policy forward, env step and render).
Nested scopes included; an op without a scope of its own takes its
enclosing loop's (``bench/scopes.py``). Mean over the traced cycles
and the cell's chips."""

from bench import scopes


def read(ctx):
    secs = scopes.per_cycle_s(ctx, "act")
    return None if secs is None else 1e3 * secs
