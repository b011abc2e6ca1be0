"""Pytree <-> MSR block placement: serialize training state into the code's
n = 2k data blocks and back (DESIGN.md §2 — MSR-coded checkpointing).

The mapping is deliberately dumb and auditable:
  pytree -> flat list of (path, dtype, shape, raw bytes) -> one byte stream
         -> pad to a multiple of n -> reshape (n, S) uint8 data symbols.

Data symbols are bytes, so the blocks stay ``uint8`` on the host; the
planned executables widen them to GF(p) int32 lanes on the device.

Systematic property: restoring WITHOUT failures reads only the raw data
blocks — `blocks_to_pytree(data_blocks)` never touches field arithmetic.

Physical placement (DESIGN.md §9): `RackLayout` assigns the n storage
nodes to failure domains (racks) so the cluster simulator can model
*correlated* failures — losing a whole rack must not exceed the code's
n - k erasure budget, which `RackLayout.survives_rack_loss` checks.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Any, Sequence

import jax
import numpy as np

from . import gf


@dataclass
class TreeSpec:
    """Static metadata needed to rebuild the pytree from bytes."""
    treedef_repr: str
    leaves: list[dict]       # [{dtype, shape, nbytes}]
    total_bytes: int
    n_blocks: int
    block_symbols: int

    def to_json(self) -> str:
        return json.dumps({
            "treedef_repr": self.treedef_repr,
            "leaves": self.leaves,
            "total_bytes": self.total_bytes,
            "n_blocks": self.n_blocks,
            "block_symbols": self.block_symbols,
        })

    @staticmethod
    def from_json(s: str) -> "TreeSpec":
        d = json.loads(s)
        return TreeSpec(**d)


@dataclass(frozen=True)
class RackLayout:
    """Node -> failure-domain (rack) assignment for correlated failures.

    Parameters
    ----------
    n_nodes : int
        Number of storage nodes (the code's n = 2k).
    racks : tuple of int
        ``racks[i]`` is the rack id of node ``v_{i+1}`` (0-based rack ids).

    Notes
    -----
    Build one with :func:`rack_layout`, which round-robins nodes across
    racks so rack sizes differ by at most one — the placement that
    maximizes the number of racks that may fail together while staying
    inside the code's n - k erasure budget.
    """
    n_nodes: int
    racks: tuple[int, ...]

    def __post_init__(self):
        if len(self.racks) != self.n_nodes:
            raise ValueError(f"need one rack id per node: "
                             f"{len(self.racks)} != {self.n_nodes}")

    @property
    def n_racks(self) -> int:
        return len(set(self.racks))

    def rack_of(self, node: int) -> int:
        """Rack id of node ``v_node`` (1-indexed)."""
        if not 1 <= node <= self.n_nodes:
            raise ValueError(f"node {node} out of range 1..{self.n_nodes}")
        return self.racks[node - 1]

    def nodes_in(self, rack: int) -> tuple[int, ...]:
        """All (1-indexed) nodes assigned to ``rack``."""
        return tuple(i + 1 for i, r in enumerate(self.racks) if r == rack)

    @property
    def max_rack_size(self) -> int:
        return max(len(self.nodes_in(r)) for r in set(self.racks))

    def survives_rack_loss(self, k: int) -> bool:
        """True if losing ANY single rack leaves >= k nodes alive — i.e.
        every rack holds at most n - k nodes, so a correlated rack
        failure stays inside the code's erasure budget."""
        return self.max_rack_size <= self.n_nodes - k


def rack_layout(n_nodes: int, n_racks: int) -> RackLayout:
    """Round-robin the n nodes across ``n_racks`` failure domains.

    Rack sizes differ by at most one; with ``n_racks >= n / (n - k)`` the
    resulting layout survives any single-rack loss (``survives_rack_loss``).
    """
    if n_racks < 1:
        raise ValueError("need at least one rack")
    return RackLayout(n_nodes=n_nodes,
                      racks=tuple(i % n_racks for i in range(n_nodes)))


def rotate_placement(layout: RackLayout, n_shares: int,
                     stripe: int) -> tuple[int, ...]:
    """Physical nodes (1-indexed) holding a stripe's ``n_shares`` shares.

    Share j of stripe t lands on node ``(t + j) mod n_nodes + 1``: stripes
    rotate around the node ring so load (and, after a node failure, the
    per-stripe loss count) spreads evenly, and because ``rack_layout``
    round-robins rack ids, any window of consecutive nodes also spreads
    across racks — roughly ``ceil(n_shares / n_racks)`` shares of one
    stripe per failure domain, up to one more when the window wraps a
    ring whose size is not a multiple of ``n_racks``.  The binding
    invariant is the one the stripe manager CHECKS at construction:
    ``max_shares_per_rack`` stays within the code's n - k erasure budget
    for every rotation phase (DESIGN.md §10).
    """
    if n_shares > layout.n_nodes:
        raise ValueError(f"cannot place {n_shares} distinct shares on "
                         f"{layout.n_nodes} nodes")
    return tuple((stripe + j) % layout.n_nodes + 1 for j in range(n_shares))


def max_shares_per_rack(layout: RackLayout,
                        placement: Sequence[int]) -> int:
    """Largest number of a stripe's shares co-located in one rack — a
    correlated rack loss erases exactly this many shares of the stripe,
    so the store requires it to stay within the code's n - k budget."""
    counts: dict[int, int] = {}
    for node in placement:
        r = layout.rack_of(node)
        counts[r] = counts.get(r, 0) + 1
    return max(counts.values()) if counts else 0


def pytree_to_bytes(tree: Any) -> tuple[bytes, jax.tree_util.PyTreeDef, list[dict]]:
    """The leaves' raw bytes joined in pytree order, with the treedef and
    per-leaf metadata.  Runs as the "serialize" stage; each device leaf
    pulled to the host is a "d2h" span counting its bytes."""
    from repro.exec.staging import staged
    with staged("serialize"):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        metas, chunks = [], []
        for leaf in leaves:
            if isinstance(leaf, jax.Array):
                with staged("d2h", nbytes=leaf.nbytes):
                    arr = np.asarray(leaf)
            else:
                arr = np.asarray(leaf)
            raw = arr.tobytes()
            metas.append({"dtype": str(arr.dtype), "shape": list(arr.shape),
                          "nbytes": len(raw)})
            chunks.append(raw)
        return b"".join(chunks), treedef, metas


def bytes_to_leaves(payload, metas: list[dict]) -> list[np.ndarray]:
    """The leaves of ``payload`` (bytes, or a flat contiguous uint8
    array), each copied out of it once."""
    leaves, off = [], 0
    for m in metas:
        dt = np.dtype(m["dtype"])
        # read in place (a bytes slice would copy every leaf once more)
        arr = np.frombuffer(payload, dtype=dt, count=m["nbytes"] // dt.itemsize,
                            offset=off) if m["nbytes"] else np.empty(0, dt)
        off += m["nbytes"]
        leaves.append(arr.reshape(m["shape"]).copy())
    return leaves


def pytree_to_blocks(tree: Any, n: int, p: int = gf.DEFAULT_P,
                     ) -> tuple[np.ndarray, jax.tree_util.PyTreeDef, TreeSpec]:
    """Serialize a pytree into (n, S) ``uint8`` data blocks a_0..a_{n-1}
    (each byte is a GF(p) data symbol, p > 256)."""
    payload, treedef, metas = pytree_to_bytes(tree)
    # one allocation: the copy, the pad to a multiple of n and the (n, S)
    # layout land in a single write (the state can be GBs)
    blocks = np.empty((n, -(-len(payload) // n)), np.uint8)
    gf.bytes_to_symbols_into(payload, blocks.reshape(-1), p)
    spec = TreeSpec(treedef_repr=str(treedef), leaves=metas,
                    total_bytes=len(payload), n_blocks=n,
                    block_symbols=blocks.shape[1])
    return blocks, treedef, spec


def blocks_to_pytree(blocks: np.ndarray, treedef: jax.tree_util.PyTreeDef,
                     spec: TreeSpec) -> Any:
    """Inverse of pytree_to_blocks.  Pure byte reads for systematic blocks.
    Runs as the "deserialize" stage.  ``uint8`` blocks are the payload
    and are read in place; int32 symbols are range-checked and narrowed
    back to bytes as a "pack" span inside it."""
    from repro.exec.staging import staged
    with staged("deserialize"):
        sym = np.asarray(blocks)
        if sym.dtype == np.uint8:
            payload = np.ascontiguousarray(sym).reshape(-1)[: spec.total_bytes]
        else:
            with staged("pack"):
                payload = gf.symbols_to_bytes(
                    sym.reshape(-1)[: spec.total_bytes])
        leaves = bytes_to_leaves(payload, spec.leaves)
        return jax.tree_util.tree_unflatten(treedef, leaves)


__all__ = ["TreeSpec", "RackLayout", "rack_layout", "rotate_placement",
           "max_shares_per_rack", "pytree_to_bytes", "bytes_to_leaves",
           "pytree_to_blocks", "blocks_to_pytree"]
