"""ICI-ring-native MSR encode (DESIGN.md §2, adaptation 2).

The circulant structure of M means every redundancy block is a combination
of the NEXT k data blocks:  node i (0-indexed) computes

    r_{i+1} = sum_{t=1..k} c_{k+1-t} * a_{(i+t) mod n}

so encode = k rounds of *neighbour shift + scale + accumulate*: each round
every node forwards one block to its LEFT neighbour (j -> j-1), i.e. blocks
flow rightward exactly one hop per round — the TPU ICI torus's native
pattern.  Total traffic: k blocks per link, all neighbour-wise; no gather,
no all-to-all.  Implemented with shard_map + jax.lax.ppermute over a 1-D
`storage` mesh axis.

Repair, by contrast, is point-to-point (d = k+1 direct fetches) and lives at
the host/checkpoint layer (repro.checkpoint) where its byte count is the
paper's gamma (eq. 7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .circulant import CodeSpec


def _ring_encode_local(a_local: jnp.ndarray, *, c: tuple[int, ...], p: int,
                       axis: str, wire_dtype) -> jnp.ndarray:
    """Per-device body: a_local is this node's (1, S) data block.

    §Perf (ring iteration 2): only DATA blocks travel the ring, and
    systematic data blocks are raw bytes by construction — so the permute
    payload is uint8, 4x less wire than int32 symbols.  Accumulation stays
    int32-local.
    """
    k = len(c)
    n = 2 * k
    perm = [(j, (j - 1) % n) for j in range(n)]     # send to LEFT neighbour
    buf = a_local.astype(wire_dtype)
    acc = jnp.zeros(a_local.shape, jnp.int32)
    for t in range(1, k + 1):
        buf = jax.lax.ppermute(buf, axis, perm)      # buf now holds a_{i+t}
        acc = (acc + c[k - t] * buf.astype(jnp.int32)) % p  # coeff c_{k+1-t}
    return acc


def ring_encode(data: jnp.ndarray, spec: CodeSpec, mesh: Mesh,
                axis: str = "storage", byte_wire: bool | None = None) -> jnp.ndarray:
    """data: (n, S) int32, row i on storage-node i -> redundancy (n, S),
    row i = r_{i+1} resident on node i.  Neighbour-only communication.

    byte_wire: permute uint8 payloads (4x less wire — §Perf ring iteration
    2).  Valid when every data symbol < 256: automatic for p <= 256; for
    p = 257 the caller opts in when the blocks are systematic raw BYTES
    (always true for the checkpoint layer's data blocks)."""
    n = spec.n
    if mesh.shape[axis] != n:
        raise ValueError(f"mesh axis {axis}={mesh.shape[axis]} != n={n}")
    if byte_wire is None:
        byte_wire = spec.p <= 256
    wire_dtype = jnp.uint8 if byte_wire else jnp.int32
    fn = shard_map(
        functools.partial(_ring_encode_local, c=tuple(spec.c), p=spec.p,
                          axis=axis, wire_dtype=wire_dtype),
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=P(axis, None),
    )
    return fn(jnp.asarray(data, jnp.int32) % spec.p)


def ring_encode_reference(data: jnp.ndarray, spec: CodeSpec) -> jnp.ndarray:
    """Oracle: the dense-M encode from the core layer."""
    from .msr import DoubleCirculantMSR
    return DoubleCirculantMSR(spec).encode(data)


def ring_link_traffic_blocks(spec: CodeSpec) -> int:
    """Blocks crossing each ring link during encode: k (one per round)."""
    return spec.k
