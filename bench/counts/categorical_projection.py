"""The least work of one C51 projection call (Bellemare et al. 2017).

For each of B rows and K atoms: the shifted atom r + gamma^n (1 - done)
z_j (3 FLOPs), its clip (2), its position b = (Tz - v_min) / dz (2), the
floor and fraction (2), and the two shares of its mass added to the
neighbouring atoms (4): 13 FLOPs. The (B, K) masses are read and the
(B, K) result written in float32, with the B rewards and done flags.
"""

from __future__ import annotations


def work(rows: int, atoms: int):
    """(flops, bytes) of one call."""
    flops = 13.0 * rows * atoms
    nbytes = 4.0 * (2 * rows * atoms + 2 * rows)
    return flops, nbytes
