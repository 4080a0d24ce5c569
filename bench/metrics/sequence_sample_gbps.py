"""sequence_sample_gbps (GB/s): the bytes a cycle's sequence draws must
read (updates x minibatch x sequence bytes,
``bench/counts/sequence_replay.py``) for the replicas on one chip, over
the device time per cycle in the ``learn/sample`` scope
(``bench/scopes.py``), in 1e9 bytes per second. The scope also holds
the draw's unpacking and the rebuilding of its frame stacks. None for a
configuration without sequence replay."""

from bench import scopes
from bench.counts import sequence_replay


def read(ctx):
    cell = ctx["cell"]
    if not int(cell.config["spec"]["variant"].get("sequence_length", 0)):
        return None
    secs = scopes.per_cycle_s(ctx, "learn/sample")
    if not secs:
        return None
    per_chip = cell.replicas / cell.chips
    return (per_chip * sequence_replay.sample_bytes_per_cycle(cell.config)
            / secs / 1e9)
