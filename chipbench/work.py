"""The bytes each GF(p) operation needs, from logical shapes.

A symbol counts one byte whatever dtype carries it, and only unpadded
symbols count: what the operation must read and write, not what an
implementation happens to move.  So the share of the memory roofline that
``gf_roofline_pct`` reports is of the same work under any backend, and
padding, widening to int32 or a second pass shows as a lower share.

Every function returns a byte count for an [n = 2k, k] double-circulant
code; ``s`` is the symbols per block.
"""
from __future__ import annotations


def encode(n: int, s: int) -> int:
    """Eq. (2): read the n data blocks, write the n redundancy blocks."""
    return 2 * n * s


def regenerate(k: int, s: int) -> int:
    """One lost node rebuilt from d = k+1 helpers: read r_{i-1} and k data
    blocks, write the node's (a, r) pair."""
    return (k + 1) * s + 2 * s
