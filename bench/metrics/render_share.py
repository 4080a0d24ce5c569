"""render_share (%): the share of the act scope's device time spent in
its ``render`` scope, the 84x84 observation render of the stepped envs
(``envs/preprocess.py``), read from the same traced cycles
(``bench/scopes.py``)."""

from bench import scopes


def read(ctx):
    act = scopes.per_cycle_s(ctx, "act")
    render = scopes.per_cycle_s(ctx, "act/render")
    if not act or render is None:
        return None
    return 100.0 * render / act
