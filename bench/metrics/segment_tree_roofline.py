"""segment_tree_roofline (%): the PER sampling kernel's least time over
its device time in the traced window. Least time is the larger of
FLOPs over the bf16 peak and bytes over HBM bandwidth for a descent of
B targets through log2(leaves) levels per call
(``bench/counts/segment_tree.py``); bytes bound it."""

from bench import trace as tr
from bench.counts import least_seconds, segment_tree

# the Mosaic kernel's custom call, known by its int32 (targets, 128) count tile output
# (a leading replica axis under vmap is allowed)
PATTERN = (r"= s32\[(?:\d+,)*\d+,128\]\S* custom-call\("
           r".*custom_call_target=\"tpu_custom_call\"")


def read(ctx):
    t = ctx.get("trace")
    if t is None:
        return None
    secs, calls = tr.kernel_time(t, PATTERN)
    if calls == 0 or secs <= 0:
        return None
    spec = ctx["cell"].config["spec"]
    cap = int(spec["algo"]["replay_capacity"])
    leaves = 1 << (cap - 1).bit_length()
    flops, nbytes = segment_tree.work(int(spec["algo"]["minibatch_size"]),
                                      leaves)
    return 100.0 * calls * least_seconds(flops, nbytes, ctx["peak"]) / secs
