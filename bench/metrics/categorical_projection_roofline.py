"""categorical_projection_roofline (%): the C51 projection kernel's
least time over its device time in the traced window, with the work of
a (minibatch x atoms) projection per call
(``bench/counts/categorical_projection.py``); bytes bound it."""

from bench import trace as tr
from bench.counts import categorical_projection, least_seconds

# the Mosaic kernel's custom call, known by its float32 (rows, 128) mass tile output
# (a leading replica axis under vmap is allowed)
PATTERN = (r"= f32\[(?:\d+,)*\d+,128\]\S* custom-call\("
           r".*custom_call_target=\"tpu_custom_call\"")


def read(ctx):
    t = ctx.get("trace")
    if t is None:
        return None
    secs, calls = tr.kernel_time(t, PATTERN)
    if calls == 0 or secs <= 0:
        return None
    spec = ctx["cell"].config["spec"]
    flops, nbytes = categorical_projection.work(
        int(spec["algo"]["minibatch_size"]), int(spec["variant"]["num_atoms"]))
    return 100.0 * calls * least_seconds(flops, nbytes, ctx["peak"]) / secs
