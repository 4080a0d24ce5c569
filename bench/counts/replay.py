"""Bytes the learner's replay draws must read, from the configuration.

A sampled row is what ``replay_sample`` / ``per_sample`` gather for
one transition: the two uint8 frame stacks (``obs`` and ``next_obs``,
each frame_size x frame_size x frame_stack), the int32 action, the
float32 reward and the bool done; under prioritized replay also the
int32 index and the float32 importance weight of the draw. A cycle
draws updates x minibatch rows, with updates = C / F.
"""

from typing import Any, Dict

UINT8, INT32, FLOAT32, BOOL = 1, 4, 4, 1


def row_bytes(config: Dict[str, Any]) -> int:
    """Bytes of one sampled transition."""
    net, spec = config["network"], config["spec"]
    frames = 2 * int(net["frame_size"]) ** 2 * int(net["frame_stack"]) * UINT8
    row = frames + INT32 + FLOAT32 + BOOL
    if spec["variant"]["prioritized"]:
        row += INT32 + FLOAT32
    return row


def updates_per_cycle(config: Dict[str, Any]) -> int:
    spec = config["spec"]
    return max(int(spec["schedule"]["cycle_steps"])
               // int(spec["algo"]["train_period"]), 1)


def sample_bytes_per_cycle(config: Dict[str, Any]) -> int:
    """Bytes one replica's learner draws from replay in one cycle."""
    return (updates_per_cycle(config)
            * int(config["spec"]["algo"]["minibatch_size"])
            * row_bytes(config))
