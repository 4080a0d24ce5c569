"""cycle_mfu (%): the whole cycle's share of the chips' bf16 peak.

Network FLOPs per env step (one act forward, and minibatch/F learner
samples of forward, backward, target and double-Q forwards, from
``bench/counts/network.py``) times the window's env steps per second,
over chips times the peak. Recomputed operations do not count."""

from bench.counts import network


def read(ctx):
    rate = ctx.get("env_steps_per_s")
    if not rate:
        return None
    flops = network.flops_per_env_step(ctx["cell"].config, ctx["n_actions"])
    return (100.0 * flops * rate
            / (ctx["chips"] * ctx["peak"]["bf16_flops_per_s"]))
