"""The least work of one prioritized-replay sampling call.

Inverse-CDF sampling of B targets over a sum-tree of L leaves is a
descent of log2(L) levels per target: at each level one node is read
(4 bytes), compared and, on a right turn, subtracted. The targets are
read and the indices written once. Whatever implements it (a tree
descent, or a scan of the whole leaf level as the Mosaic kernel does)
needs at least this, so the count does not depend on the backend.
"""

from __future__ import annotations


def work(targets: int, leaves: int):
    """(flops, bytes) of one call."""
    levels = max(int(leaves) - 1, 1).bit_length()
    flops = 2.0 * targets * levels
    nbytes = 4.0 * targets * levels + 4.0 * targets + 4.0 * targets
    return flops, nbytes
