"""replay_sample_gbps (GB/s): the bytes a cycle's replay draws must read
(updates x minibatch x row bytes, ``bench/counts/replay.py``) for the
replicas on one chip, over the device time per cycle in the
``learn/sample`` scope (``bench/scopes.py``), in 1e9 bytes per second.
Where XLA fuses part of the gather into the update, the scope's time
leaves that part out and the rate reads high."""

from bench import scopes
from bench.counts import replay


def read(ctx):
    secs = scopes.per_cycle_s(ctx, "learn/sample")
    if not secs:
        return None
    cell = ctx["cell"]
    per_chip = cell.replicas / cell.chips
    return per_chip * replay.sample_bytes_per_cycle(cell.config) / secs / 1e9
