"""FLOPs of the Nature-CNN Q-network and of the learner per env step.

The shared count: a configuration without a ``bench/counts/<config>.py``
of its own is counted here (``bench.cells.load_count``).

A FLOP here is one multiply or one add of a convolution or a dense
layer (2 per multiply-accumulate). Activations, softmaxes, the noise of
noisy layers and the optimizer are not counted: against the matmul and
convolution work they are small, and the roofline of the whole step is
what the convolutions and matmuls could reach.
"""

from __future__ import annotations

from typing import Any, Dict


def forward_flops(network: Dict[str, Any], n_actions: int, atoms: int = 1,
                  dueling: bool = False) -> float:
    """FLOPs of one forward pass of one frame stack."""
    size, ch = int(network["frame_size"]), int(network["frame_stack"])
    macs = 0
    for out_ch, k, s in network["convs"]:
        size = (size - k) // s + 1
        macs += size * size * out_ch * k * k * ch
        ch = out_ch
    hidden = int(network["hidden"])
    macs += size * size * ch * hidden
    heads = (atoms + n_actions * atoms) if dueling else n_actions * atoms
    macs += hidden * heads
    return 2.0 * macs


def forwards_per_env_step(minibatch: int, train_period: int,
                          double: bool) -> float:
    """Forward-pass equivalents per env step: one act forward, and
    minibatch/train_period learner samples, each an online forward, a
    backward (twice a forward), a target forward and, for double Q, an
    online forward of the next state."""
    per_sample = 1 + 2 + 1 + (1 if double else 0)
    return 1.0 + minibatch / train_period * per_sample


def flops_per_env_step(config: Dict[str, Any], n_actions: int) -> float:
    spec = config["spec"]
    v, algo = spec["variant"], spec["algo"]
    atoms = int(v["num_atoms"]) if v["distributional"] else 1
    fwd = forward_flops(config["network"], n_actions, atoms,
                        bool(v["dueling"]))
    return fwd * forwards_per_env_step(int(algo["minibatch_size"]),
                                       int(algo["train_period"]),
                                       bool(v["double"]))
