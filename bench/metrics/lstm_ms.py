"""lstm_ms (ms): device ms per cycle in the ``learn/lstm`` scope, the
learner's LSTM recurrences after the burn-in, forward and backward, of
the online and the target net (``core/dqn.py`` ``sequence_loss``).
Attributed with the recurrent scopes besides ``bench/scopes.py``'s
(``bench/wide_scopes.py``). Mean over the traced cycles and the cell's
chips; None where the cycle has no such scope."""

from bench import wide_scopes


def read(ctx):
    secs = wide_scopes.per_cycle_s(ctx, "learn/lstm")
    return None if secs is None else 1e3 * secs
