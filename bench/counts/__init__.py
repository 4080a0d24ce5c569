"""Operation and byte counts, worked out from shapes alone.

``network`` counts a Q-network's forward FLOPs and the learner's
multiple of them per env step; each kernel has a module of its own
(``segment_tree``, ``categorical_projection``) whose ``work`` gives the
least FLOPs and bytes its algorithm needs for one call. Nothing here
reads the program, so a change to the program cannot change a count.
"""

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a chip not in ``peaks.json`` is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of FLOPs over the
    bf16 peak and bytes over the HBM bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
