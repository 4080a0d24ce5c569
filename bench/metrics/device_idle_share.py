"""device_idle_share (%): the share of the traced window in which no
operation ran on the chip, averaged over the cell's chips. Busy time is
the union of the op intervals on each chip's ``XLA Ops`` line."""

from bench import trace as tr


def read(ctx):
    t = ctx.get("trace")
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s(t) / t.window_s)
