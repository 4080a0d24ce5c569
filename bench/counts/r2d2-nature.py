"""FLOPs of the R2D2 network and of its learner per env step.

``r2d2-nature``'s own count (``bench.cells.load_count``). A FLOP is one
multiply or one add of a convolution or a dense layer (2 per
multiply-accumulate); activations, the LSTM's gate nonlinearities and
the optimizer are not counted.

One step-forward is the Nature conv torso, the LSTM's input projection
(torso output, one-hot previous action and previous reward), its
recurrent projection, and the dueling head with its two hidden layers.
Per env step: one act forward, and batch/train_period sequences, each
an online unroll of all its steps (burn-in included), a backward of
its trained steps (twice a forward) and a target unroll of all its
steps.
"""

from __future__ import annotations

from typing import Any, Dict


def step_forward_flops(network: Dict[str, Any], n_actions: int) -> float:
    """FLOPs of one step of the network for one stream."""
    size, ch = int(network["frame_size"]), int(network["frame_stack"])
    macs = 0
    for out_ch, k, s in network["convs"]:
        size = (size - k) // s + 1
        macs += size * size * out_ch * k * k * ch
        ch = out_ch
    H, D = int(network["lstm"]), int(network["dueling_hidden"])
    macs += (size * size * ch + n_actions + 1) * 4 * H      # input
    macs += H * 4 * H                                         # recurrent
    macs += 2 * H * D + D * (1 + n_actions)                   # dueling
    return 2.0 * macs


def forwards_per_sequence(variant: Dict[str, Any]) -> int:
    steps = int(variant["burn_in"]) + int(variant["sequence_length"]) + 1
    return steps + 2 * int(variant["sequence_length"]) + steps


def flops_per_env_step(config: Dict[str, Any], n_actions: int) -> float:
    spec = config["spec"]
    algo = spec["algo"]
    per_update = (int(algo["minibatch_size"])
                  * forwards_per_sequence(spec["variant"]))
    return step_forward_flops(config["network"], n_actions) * (
        1.0 + per_update / int(algo["train_period"]))
