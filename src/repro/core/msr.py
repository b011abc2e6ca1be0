"""Double Circulant MSR code: encode / reconstruct / regenerate (paper §III).

Block convention: the file is cut into n = 2k data blocks; `data[j]` is block
a_j, a row of S symbols (int32 in [0, p)).  Node v_i (1-indexed) stores the
pair (a_{i-1}, r_i) with

    r_i = sum_{u=1..k} c_u * a_{(i - k - u) mod n}   over GF(p).

Storage per node alpha = 2 * S = B/k symbols (MSR point, q = 2).

The three phases of the paper:
  * encode       — construction phase (eq. (2) via M circulant);
  * reconstruct  — data-reconstruction condition: ANY k nodes -> full file;
  * regenerate   — node regeneration with d = k+1 determined helpers and the
                   *embedded property*: no coefficient discovery, helpers send
                   raw stored blocks, the newcomer solves one scalar inverse.

Repair bandwidth: gamma = d * S = (k+1) * B / (2k)  — eq. (7).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Sequence

import jax.numpy as jnp
import numpy as np

from . import gf
from .circulant import CodeSpec, redundancy_support
from .repair import RepairEngine

MatmulFn = Callable[..., jnp.ndarray]  # (A, B, p) -> (A @ B) mod p


@dataclass
class RepairPlan:
    """The embedded property, reified: everything a newcomer for node v_i
    must do, known statically from (i, spec) — no coefficient search."""
    node: int                  # v_i being regenerated (1-indexed)
    prev_node: int             # serves its redundancy block r_{prev}
    next_nodes: tuple[int, ...]  # k nodes serving their data blocks (in order)
    data_indices: tuple[int, ...]  # 0-based a-indices downloaded (a_{i..i+k-1} mod n)
    blocks_downloaded: int     # d = k + 1

    @property
    def d(self) -> int:
        return self.blocks_downloaded


class DoubleCirculantMSR:
    """The paper's [n = 2k, k] code over GF(p), vectorized over symbols.

    Node v_i (1-indexed) stores the pair (a_{i-1}, r_i); all three phases
    — encode (eq. (2)), any-k reconstruct (§III-B) and d = k+1
    regenerate (§III-C) — run as dispatched GF matmuls through the fused
    repair engine (DESIGN.md §3-§4).

    Parameters
    ----------
    spec : CodeSpec
        Validated code specification (k, p, coefficient vector c
        satisfying condition (6)).
    matmul : callable, optional
        Fully custom ``(a, b, p) -> (a @ b) mod p`` kernel.  Injecting
        one disables the structure-exploiting circulant encode and the
        jit fusion so EVERY field operation flows through it.
    backend : str, optional
        Pin a registered dispatch backend by name (``jnp-int32``,
        ``jnp-f32``, ``pallas``, ``pallas-interpret``); None auto-selects
        from (platform, p, k), overridable with ``REPRO_GF_BACKEND``.
    inverse_cache_size : int
        LRU capacity of the decode-inverse cache (entries are keyed by
        the sorted k-node subset; there are C(2k, k) possible).
    mesh : StreamMesh | int | None
        Shard every planned op over this stream-axis device mesh
        (DESIGN.md §14).  ``None`` inherits the ambient
        ``repro.sharding.mesh.use_mesh(...)`` scope (or no mesh at
        all); a 1-device mesh falls back to the plain dispatch planner.
        Ignored for custom-matmul codes (nothing is lowered).

    Attributes
    ----------
    repair : RepairEngine
        The decode-side engine: fused regeneration, cached any-k
        inverses, one-matmul multi-failure repair.
    backend_name : str
        Resolved backend (``"custom"`` when ``matmul`` was injected).

    Examples
    --------
    >>> spec = CodeSpec.make(2, 257)
    >>> code = DoubleCirculantMSR(spec)
    >>> import numpy as np
    >>> red = code.encode(np.zeros((4, 8), np.int32))
    >>> red.shape
    (4, 8)
    """

    def __init__(self, spec: CodeSpec, matmul: MatmulFn | None = None,
                 backend: str | None = None,
                 inverse_cache_size: int = 128, mesh=None):
        self.spec = spec
        self.k, self.n, self.p = spec.k, spec.n, spec.p
        self.c = np.asarray(spec.c, dtype=np.int32)
        self._custom_matmul = matmul is not None
        if matmul is None:
            from repro.kernels import dispatch
            from repro.sharding import mesh as mesh_mod
            be = dispatch.get(backend) if backend else dispatch.select(
                self.p, self.k)
            self.backend_name = be.name
            self._matmul = be.msr_matmul()
            self._circulant = be.circulant_encode
            engine_mm = be.matmul            # module-level singleton: the
                                             # engine's jit cache is shared
            self.mesh = (mesh_mod.as_stream_mesh(mesh) if mesh is not None
                         else mesh_mod.current_mesh())
            # shared per (backend, p, mesh): every code on this backend +
            # mesh hits one AOT executable cache (DESIGN.md §11, §14)
            self.planner = be.planner(self.p, mesh=self.mesh)
        else:
            self.backend_name = "custom"
            self._matmul = matmul
            self._circulant = None
            engine_mm = matmul
            self.mesh = None
            self.planner = None              # custom kernels are not lowered
        self._m = spec.matrix_m()            # (n, n) M[j, i] = coef of a_j in r_{i+1}
        self._mt = np.ascontiguousarray(self._m.T)  # (n, n): r = M^T @ a
        # fused decode-side engine (DESIGN.md §4): repair matrix precomputed
        # here, reconstruction inverses LRU-cached across calls
        self.repair = RepairEngine(spec, engine_mm,
                                   jittable=not self._custom_matmul,
                                   inverse_cache_size=inverse_cache_size,
                                   planner=self.planner)

    # ---------------------------------------------------------------- encode
    def encode(self, data: jnp.ndarray) -> jnp.ndarray:
        """data: (n, S) data blocks -> (n, S) redundancy blocks.

        r[i] = (M^T @ a)[i]; M^T row i has exactly k nonzeros (the circulant
        support), so the dispatched circulant kernel does k MACs/symbol where
        the dense matmul does n — the paper's 2x "computer efficiency" win.
        A custom-matmul code falls back to the dense form.
        """
        data = jnp.asarray(data, jnp.int32)
        if data.shape[0] != self.n:
            raise ValueError(f"expected {self.n} data blocks, got {data.shape[0]}")
        if self._circulant is not None:
            return self._circulant(data, tuple(int(x) for x in self.spec.c),
                                   self.p)
        return self._matmul(jnp.asarray(self._mt), data, self.p)

    def encode_planned(self, data) -> "PlanResult":
        """Planned encode (DESIGN.md §11): the circulant kernel at a
        bucketed stream extent through the shared AOT executable cache.

        Asynchronous — returns a `repro.exec.plan.PlanResult`; call
        ``.host()`` to block and get the exact (n, S) numpy redundancy
        matrix.  Bit-exact vs :meth:`encode` (padding is column-local),
        with zero trace/compile work at steady state.  A ``uint8``
        ``data`` stays one byte a symbol up to the executable, which
        widens it on the device.  Custom-matmul codes fall back to the
        eager :meth:`encode`.
        """
        from repro.exec.plan import PlanResult
        data = np.asarray(data)
        if data.shape[0] != self.n:
            raise ValueError(f"expected {self.n} data blocks, "
                             f"got {data.shape[0]}")
        if self.planner is not None:
            return self.planner.circulant_encode(
                data, tuple(int(x) for x in self.spec.c))
        return PlanResult(self.encode(data), data.shape[-1])

    def node_storage(self, data: jnp.ndarray) -> list[tuple[jnp.ndarray, jnp.ndarray]]:
        """[(a_{i-1}, r_i)] for node v_i, i = 1..n."""
        red = self.encode(data)
        return [(data[i - 1], red[i - 1]) for i in range(1, self.n + 1)]

    # ----------------------------------------------------------- reconstruct
    def reconstruct(self, node_ids: Sequence[int], data_blocks: jnp.ndarray,
                    red_blocks: jnp.ndarray) -> jnp.ndarray:
        """Any-k reconstruction (paper §III-B).

        node_ids: k distinct 1-indexed nodes the DC connected to.
        data_blocks/red_blocks: (k, S) — the (a_{i-1}, r_i) each node served.
        Returns the full (n, S) data block matrix.

        Downloads 2k blocks of S symbols = B symbols total: gamma = B.

        The system inverse is LRU-cached by the sorted node subset
        (``self.repair.decode_cache``): repeated reconstructions — restore
        loops, scrubs — cost one ``gf.gauss_inverse`` per subset, not per
        call, and any ordering of the same k nodes shares the entry.
        """
        return self.repair.reconstruct(node_ids, data_blocks, red_blocks)

    def reconstruct_with_repair(self, node_ids: Sequence[int], data_blocks,
                                red_blocks, failed: Sequence[int],
                                ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Multi-failure repair: full data AND every failed node's
        redundancy block from ONE decode matmul (DESIGN.md §4).
        ``node_ids`` must be sorted."""
        return self.repair.reconstruct_with_repair(node_ids, data_blocks,
                                                   red_blocks, failed)

    def systematic_read(self, data: jnp.ndarray) -> jnp.ndarray:
        """Systematic reconstruction (paper §III-B): connect to all n nodes,
        download only the first (data) block from each — n blocks of S symbols
        = B total, all uncoded.  Zero field operations."""
        return jnp.asarray(data, jnp.int32)

    # ------------------------------------------------------------ regenerate
    def repair_plan(self, i: int) -> RepairPlan:
        """Determined helper set for node v_i — the embedded property."""
        if not 1 <= i <= self.n:
            raise ValueError(f"node {i} out of range 1..{self.n}")
        prev_node = (i - 2) % self.n + 1
        next_nodes = tuple((i - 1 + t) % self.n + 1 for t in range(1, self.k + 1))
        data_indices = tuple((i - 1 + t) % self.n for t in range(1, self.k + 1))
        return RepairPlan(node=i, prev_node=prev_node, next_nodes=next_nodes,
                          data_indices=data_indices, blocks_downloaded=self.k + 1)

    def regenerate(self, i: int, r_prev: jnp.ndarray,
                   next_data: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Systematic (exact) regeneration of node v_i (paper §III-C).

        r_prev: (S,) — r_{i-1} downloaded from the previous node.
        next_data: (k, S) — a_{(i-1+t) mod n}, t = 1..k, downloaded from the
          next k nodes in plan order.
        Returns (a_{i-1}, r_i) — bit-exactly the lost node's pair.

        Download = (k+1) * S symbols = (k+1) B / (2k): eq. (7), the MSR
        minimum for d = k+1.

        Fused path (DESIGN.md §4): the scalar solve, the correction and the
        re-encode fold into ONE (2, k+1) repair-matrix matmul over the
        stacked helpers — ``regenerate_reference`` keeps the unfused
        three-round schedule as the bit-exactness oracle.
        """
        return self.repair.regenerate(i, r_prev, next_data)

    def regenerate_batch(self, nodes: Sequence[int], r_prevs, next_data, *,
                         tile_symbols: int | None = None) -> jnp.ndarray:
        """Batched fused regeneration (vmapped over failed nodes, stream
        axis tiled): (F, S) r_prevs + (F, k, S) helpers -> (F, 2, S)
        [a_lost; r_new] stacks.  See RepairEngine.regenerate_batch."""
        return self.repair.regenerate_batch(nodes, r_prevs, next_data,
                                            tile_symbols=tile_symbols)

    def regenerate_reference(self, i: int, r_prev: jnp.ndarray,
                             next_data: jnp.ndarray,
                             ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """The unfused pre-engine newcomer schedule: two small matmuls plus
        host-side elementwise correction.  Kept as the reference the fused
        single-matmul path is verified (and benchmarked) against."""
        k, n, p = self.k, self.n, self.p
        r_prev = jnp.asarray(r_prev, jnp.int32)
        next_data = jnp.asarray(next_data, jnp.int32)
        if next_data.shape[0] != k:
            raise ValueError(f"expected {k} helper data blocks, got {next_data.shape[0]}")

        # r_{i-1} = c_k a_{i-1} + sum_{u=1..k-1} c_u a_{(i-1+k-u) mod n}
        # the u-th term's block is next_data[k-u-1]  (t = k-u).
        c = self.c.astype(np.int64)
        if k > 1:
            coefs = jnp.asarray(c[:-1], jnp.int32)            # c_1..c_{k-1}
            # t = k-u for u=1..k-1  ->  rows k-2, k-3, ..., 0 of next_data
            rows = next_data[jnp.arange(k - 2, -1, -1)]       # (k-1, S)
            partial = self._matmul(coefs[None, :], rows, p)[0]
        else:
            partial = jnp.zeros_like(r_prev)
        ck_inv = int(pow(int(c[-1]), p - 2, p))
        a_lost = ((r_prev - partial) * ck_inv) % p

        # r_i = sum_{u=1..k} c_u a_{(i-k-u) mod n}; term u uses t = k+1-u,
        # i.e. next_data[k-u]  (t-1 = k-u).
        coefs_all = jnp.asarray(c, jnp.int32)
        rows_all = next_data[jnp.arange(k - 1, -1, -1)]       # u=1..k -> t-1 = k-1..0
        r_new = self._matmul(coefs_all[None, :], rows_all, p)[0]
        return a_lost, r_new

    # ------------------------------------------------------------- accounting
    def gamma_regenerate_symbols(self, block_symbols: int) -> int:
        """Repair bandwidth in symbols: d * S = (k+1) * B / (2k)."""
        return (self.k + 1) * block_symbols

    def gamma_reconstruct_symbols(self, block_symbols: int) -> int:
        """Classical-EC-style repair (full reconstruction): 2k * S = B."""
        return 2 * self.k * block_symbols

    def alpha_symbols(self, block_symbols: int) -> int:
        """Per-node storage: 2 * S = B / k (MSR point)."""
        return 2 * block_symbols

    # sanity helper used by property tests
    def verify_support(self) -> bool:
        for i in range(1, self.n + 1):
            sup = redundancy_support(i, self.n)
            col = self._m[:, i - 1]
            nz = [j for j in range(self.n) if col[j] != 0]
            if sorted(sup) != sorted(nz):
                return False
        return True


# ---------------------------------------------------------------- file-level
@dataclass
class EncodedFile:
    """A file encoded across n nodes (host-side container for tests/examples)."""
    spec: CodeSpec
    data: np.ndarray          # (n, S) data blocks
    red: np.ndarray           # (n, S) redundancy blocks
    orig_len: int             # original byte length (before padding)

    def node(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        return self.data[i - 1], self.red[i - 1]


def encode_file(payload: bytes, spec: CodeSpec,
                code: DoubleCirculantMSR | None = None) -> EncodedFile:
    code = code or DoubleCirculantMSR(spec)
    sym = gf.bytes_to_symbols(payload, spec.p)
    n = spec.n
    pad = (-len(sym)) % n
    sym = np.pad(sym, (0, pad))
    blocks = sym.reshape(n, -1)
    red = np.asarray(code.encode(jnp.asarray(blocks)))
    return EncodedFile(spec=spec, data=blocks.astype(np.int32), red=red,
                       orig_len=len(payload))


def reconstruct_file(enc: EncodedFile, node_ids: Sequence[int],
                     code: DoubleCirculantMSR | None = None) -> bytes:
    code = code or DoubleCirculantMSR(enc.spec)
    d = jnp.asarray(enc.data[[i - 1 for i in node_ids]])
    r = jnp.asarray(enc.red[[i - 1 for i in node_ids]])
    blocks = np.asarray(code.reconstruct(node_ids, d, r))
    return gf.symbols_to_bytes(blocks.reshape(-1)[: enc.orig_len])


__all__ = ["DoubleCirculantMSR", "RepairPlan", "EncodedFile",
           "encode_file", "reconstruct_file"]
