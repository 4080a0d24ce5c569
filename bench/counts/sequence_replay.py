"""Bytes the learner's sequence draws must read, from the configuration.

A drawn sequence is what ``sequence_sample`` gathers for one leaf: the
window of ``frame_stack - 1 + burn_in + sequence_length + 1`` steps of
one stream, each an 84x84 uint8 frame (frame_size squared bytes), an
int32 action, a float32 reward and a bool done; the stored LSTM state
(h and c, float32); and the int32 index and float32 importance weight
of the draw. A cycle draws updates x minibatch sequences, with updates
= C / F.
"""

from typing import Any, Dict

UINT8, INT32, FLOAT32, BOOL = 1, 4, 4, 1


def sequence_bytes(config: Dict[str, Any]) -> int:
    net, v = config["network"], config["spec"]["variant"]
    window = (int(net["frame_stack"]) - 1 + int(v["burn_in"])
              + int(v["sequence_length"]) + 1)
    step = int(net["frame_size"]) ** 2 * UINT8 + INT32 + FLOAT32 + BOOL
    return window * step + 2 * int(net["lstm"]) * FLOAT32 + INT32 + FLOAT32


def sample_bytes_per_cycle(config: Dict[str, Any]) -> int:
    """Bytes one replica's learner draws from replay in one cycle."""
    spec = config["spec"]
    updates = max(int(spec["schedule"]["cycle_steps"])
                  // int(spec["algo"]["train_period"]), 1)
    return updates * int(spec["algo"]["minibatch_size"]) * sequence_bytes(
        config)
