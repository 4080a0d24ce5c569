"""cycle_mfu (%): the whole cycle's share of the chips' bf16 peak.

Network FLOPs per env step times the window's env steps per second,
over chips times the peak. The FLOPs per env step are the
configuration's own count, ``flops_per_env_step(config, n_actions)`` of
``bench/counts/<config>.py``, where it brings one, and otherwise
``bench/counts/network.py``'s: one act forward, and minibatch/F learner
samples of forward, backward, target and double-Q forwards of the
Nature-CNN family. Recomputed operations do not count."""

from bench import cells


def read(ctx):
    rate = ctx.get("env_steps_per_s")
    if not rate:
        return None
    cell = ctx["cell"]
    count = cells.load_count(cell.config_name, cell.root)
    flops = count(cell.config, ctx["n_actions"])
    return (100.0 * flops * rate
            / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"]))
