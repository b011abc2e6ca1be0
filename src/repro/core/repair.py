"""Fused batched repair engine (DESIGN.md §4).

The decode-side counterpart of the encode dispatch layer: everything a
repairing / reconstructing reader does is reduced to **one GF matmul per
request** through the dispatched backend, with all tiny host-side linear
algebra precomputed (repair matrices) or cached (reconstruction inverses).

Regeneration (paper §III-C).  The reference path solves the newcomer's
scalar equation in three device rounds: a (1, k-1) matmul for the partial
sum, an elementwise ``(r_prev - partial) * c_k^{-1} mod p`` correction, and
a second (1, k) matmul for the re-encoded redundancy.  But the whole
newcomer computation is *linear* in the d = k+1 downloaded helper blocks,
so it folds into a single (2, k+1) **repair matrix** R applied to the
stacked helper matrix H = [r_{i-1}; a_{i+1}; ...; a_{i+k}]:

    [a_lost; r_new] = R @ H  mod p,          R =
      row 0 (decode):    [c_k^{-1},  -c_k^{-1} c_{k-1}, ..., -c_k^{-1} c_1, 0]
      row 1 (re-encode): [0,          c_k,  c_{k-1},     ...,          c_1]

Because the construction is circulant, R is the SAME for every node v_i —
helper blocks are always indexed relative to i (the embedded property made
compute-static: no per-node matrices, no coefficient discovery, one fused
matmul reusing the backend's lazy mod-folding envelope).

Reconstruction (paper §III-B).  The 2k x 2k system matrix depends only on
WHICH k nodes are read, not the read order, so inverses are cached in an
LRU keyed by the sorted node subset — there are only C(2k, k) of them and
restore loops / scrubs hit the same subsets over and over.  Multi-failure
repair stacks the re-encode rows of the failed nodes under the inverse so
full data AND every lost redundancy block come out of one decode matmul.
"""
from __future__ import annotations

import functools
import threading
import weakref
from collections import OrderedDict
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.exec.plan import PlanResult, make_regen_fn, planning_enabled

from . import gf
from .circulant import CodeSpec

MatmulFn = Callable[..., jnp.ndarray]  # (A, B, p) -> (A @ B) mod p


def build_repair_matrix(spec: CodeSpec) -> np.ndarray:
    """The (2, k+1) fused repair matrix R (one per code, see module doc).

    Column 0 multiplies r_{i-1}; column 1+j multiplies the j-th helper data
    block a_{(i+j) mod n} (plan order, j = 0..k-1).  Row 0 recovers the
    lost data block a_{i-1}, row 1 re-encodes the lost redundancy r_i.
    ``repair_matrix(i)`` below returns this same R for every i: the
    circulant structure makes the repair matrix node-invariant.
    """
    k, p = spec.k, spec.p
    c = np.asarray(spec.c, dtype=np.int64) % p
    ck_inv = pow(int(c[-1]), p - 2, p)
    r = np.zeros((2, k + 1), dtype=np.int64)
    # r_{i-1} = c_k a_{i-1} + sum_{u=1..k-1} c_u a_{(i-1+k-u) mod n}; the
    # u-th term is helper column 1 + (k-u-1), so
    #   a_{i-1} = c_k^{-1} r_{i-1} - sum_u c_k^{-1} c_u a_{(i-1+k-u)}.
    r[0, 0] = ck_inv
    for j in range(k - 1):                      # j = k-u-1  <->  u = k-1-j
        r[0, 1 + j] = (-ck_inv * c[k - 2 - j]) % p
    # r_i = sum_{u=1..k} c_u a_{(i-1+k+1-u) mod n}: helper column 1 + (k-u).
    for j in range(k):                          # j = k-u    <->  u = k-j
        r[1, 1 + j] = c[k - 1 - j]
    return (r % p).astype(np.int32)


# Module-level jitted kernels with the backend matmul as a *static* argument:
# backend matmuls are module-level singletons, so the jit cache is shared
# across every engine instance (no per-code recompilation).
#
# The kernel body itself (matmul + row-0 axpy epilogue, chosen because
# XLA's CPU int32 einsum degrades badly at tiny odd contraction depths
# and an in-jit stack of the (k+1, S) helper matrix costs a full extra
# memory pass; exactness argument alongside it) is defined ONCE in
# `exec.plan.make_regen_fn` — the planned AOT executables trace the same
# function, so the two execution modes cannot desync.

@functools.partial(jax.jit, static_argnames=("mm", "p"))
def _fused_regenerate(mm, rmat, r_prev, next_data, p: int):
    return make_regen_fn(mm, p)(rmat, r_prev, next_data)


@functools.partial(jax.jit, static_argnames=("mm", "p"))
def _fused_regenerate_vmapped(mm, rmat, r_prevs, next_data, p: int):
    one = make_regen_fn(mm, p)
    return jax.vmap(lambda rp, nd: one(rmat, rp, nd))(
        r_prevs, next_data)                              # (F, 2, S)


class DecodeCacheInfo(NamedTuple):
    hits: int
    misses: int
    size: int
    maxsize: int


# Every live DecodeInverseCache, for the per-family stats surface
# (DESIGN.md §15.4): the process-wide planner registry already exposes
# plan_stats(); decode_cache_stats() is its decode-side counterpart.
_CACHE_LOCK = threading.Lock()
_LIVE_CACHES: "weakref.WeakSet[DecodeInverseCache]" = weakref.WeakSet()


def decode_cache_stats() -> dict[str, DecodeCacheInfo]:
    """Aggregate decode-inverse cache counters per code-family identity
    — one :class:`DecodeCacheInfo` per distinct family key across every
    live cache (two families with overlapping (k, p) report separately;
    that is the point of family-keyed entries)."""
    agg: dict[str, list[int]] = {}
    with _CACHE_LOCK:
        caches = list(_LIVE_CACHES)
    for c in caches:
        row = agg.setdefault(c.family, [0, 0, 0, 0])
        info = c.cache_info()
        row[0] += info.hits
        row[1] += info.misses
        row[2] += info.size
        row[3] += info.maxsize
    return {fam: DecodeCacheInfo(*row) for fam, row in sorted(agg.items())}


class DecodeInverseCache:
    """LRU of reconstruction inverses keyed by (code family, sorted
    k-node subset).

    The any-k system matrix [I^s | M^s]^T is determined by the *set* of
    nodes read; there are only C(2k, k) subsets (12870 at k = 8) and real
    restore/scrub traffic reuses a handful, so the O(n^3) host-side
    ``gf.gauss_inverse`` runs once per subset instead of once per call.

    Entry keys carry the owning code's **family identity** — not just
    the subset — so two code families with overlapping (k, p) can never
    alias an inverse (DESIGN.md §15.4), and :func:`decode_cache_stats`
    can report hit rates per family.

    Parameters
    ----------
    spec : CodeSpec, optional
        The double-circulant code whose system matrices are inverted.
        Omitted by non-circulant families, which pass ``matrix_fn``.
    maxsize : int
        LRU capacity; least-recently-used subsets are evicted beyond it.
    family : str, optional
        Family identity string baked into every entry key; defaults to
        the double-circulant identity derived from ``spec``.
    matrix_fn : callable, optional
        ``subset -> (square ndarray)`` system-matrix builder for
        generator-matrix families (e.g. product-matrix MSR); mutually
        exclusive with ``spec``.
    k, p : int, optional
        Subset size / field modulus when ``matrix_fn`` is used.

    Attributes
    ----------
    hits, misses : int
        Lifetime counters (see :meth:`cache_info`).

    See Also
    --------
    RepairEngine.reconstruct : canonicalizes caller orderings so every
        permutation of the same k nodes shares one entry.
    """

    def __init__(self, spec: Optional[CodeSpec] = None, maxsize: int = 128,
                 *, family: Optional[str] = None,
                 matrix_fn: Optional[Callable] = None,
                 k: Optional[int] = None, p: Optional[int] = None):
        self.spec = spec
        if spec is not None:
            if matrix_fn is not None:
                raise ValueError("pass spec or matrix_fn, not both")
            self.k, self.n, self.p = spec.k, spec.n, spec.p
            self._m = spec.matrix_m()           # (n, n)
            self._matrix_fn = None
            family = family or (f"double-circulant[n{spec.n},k{spec.k},"
                                f"p{spec.p}]")
        else:
            if matrix_fn is None or k is None or p is None:
                raise ValueError("matrix_fn caches need matrix_fn, k and p")
            self.k, self.p = int(k), int(p)
            self.n = None
            self._matrix_fn = matrix_fn
            family = family or "generator-matrix"
        self.family = str(family)
        self.maxsize = max(1, maxsize)
        self._entries: OrderedDict[tuple, np.ndarray] = OrderedDict()
        self.hits = 0
        self.misses = 0
        with _CACHE_LOCK:
            _LIVE_CACHES.add(self)

    def system_matrix(self, subset: tuple[int, ...]) -> np.ndarray:
        """The square decode system for the (sorted) subset: the
        circulant [I columns | M columns]^T (2k, n), or the family's
        ``matrix_fn`` rows for generator-matrix codes."""
        if self._matrix_fn is not None:
            return np.asarray(self._matrix_fn(subset), np.int64) % self.p
        cols = [i - 1 for i in subset]
        return np.concatenate(
            [np.eye(self.n, dtype=np.int64)[:, cols], self._m[:, cols]],
            axis=1,
        ).T % self.p

    def inverse(self, subset: Sequence[int]) -> np.ndarray:
        """Cached inverse of the subset's system matrix — (n, n) for the
        circulant family, (k*q, k*q) for generator-matrix families."""
        key = tuple(subset)
        if sorted(set(key)) != list(key) or len(key) != self.k:
            raise ValueError(f"need a sorted set of k={self.k} distinct "
                             f"nodes, got {key}")
        entry_key = (self.family,) + key       # family identity in the key
        hit = self._entries.get(entry_key)
        if hit is not None:
            self.hits += 1
            self._entries.move_to_end(entry_key)
            return hit
        self.misses += 1
        inv = gf.gauss_inverse(self.system_matrix(key), self.p)
        self._entries[entry_key] = inv
        if len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
        return inv

    def cache_info(self) -> DecodeCacheInfo:
        return DecodeCacheInfo(self.hits, self.misses, len(self._entries),
                               self.maxsize)

    def clear(self) -> None:
        self._entries.clear()
        self.hits = 0
        self.misses = 0


class RepairEngine:
    """Fused decode-side compute for one code: all repair/reconstruct
    requests reduce to a single dispatched GF matmul (DESIGN.md §4).

    Parameters
    ----------
    spec : CodeSpec
        The code being repaired.
    matmul : callable
        Backend ``(a, b, p) -> (a @ b) mod p`` primitive; module-level
        dispatch singletons share one jit cache across engines.
    jittable : bool
        False for custom injected matmuls: keeps every field op routed
        through the injected function and skips the jit fusion — the
        helper stack is built eagerly and the single matmul still
        applies.
    inverse_cache_size : int
        Capacity of :attr:`decode_cache`.
    planner : repro.exec.plan.PlanCache, optional
        Shape-bucketed AOT plan cache (DESIGN.md §11).  When set, the
        ``*_planned`` methods run through pre-compiled bucketed
        executables — zero recompiles at steady state — and fall back
        to the per-shape jit paths when absent or globally disabled.

    Attributes
    ----------
    decode_cache : DecodeInverseCache
        Any-k reconstruction inverses, LRU-keyed by sorted node subset.

    Notes
    -----
    The (2, k+1) repair matrix (:func:`build_repair_matrix`) is
    node-invariant by the circulant structure, so one engine serves
    every node's regeneration with zero per-node precompute.
    """

    def __init__(self, spec: CodeSpec, matmul: MatmulFn, *,
                 jittable: bool = True, inverse_cache_size: int = 128,
                 planner=None):
        self.spec = spec
        self.k, self.n, self.p = spec.k, spec.n, spec.p
        self._mm = matmul
        self._jittable = jittable
        self._mt = np.ascontiguousarray(spec.matrix_m().T)   # (n, n)
        self._rmat_np = build_repair_matrix(spec)
        self._rmat = jnp.asarray(self._rmat_np)
        self.decode_cache = DecodeInverseCache(spec, maxsize=inverse_cache_size)
        self.planner = planner

    def _planned(self) -> bool:
        return self.planner is not None and planning_enabled()

    # ------------------------------------------------------------ regenerate
    def repair_matrix(self, i: int | None = None) -> np.ndarray:
        """R for node v_i — identical for every i (circulant invariance)."""
        if i is not None and not 1 <= i <= self.n:
            raise ValueError(f"node {i} out of range 1..{self.n}")
        return self._rmat_np

    def apply(self, mat, blocks) -> jnp.ndarray:
        """(mat @ blocks) mod p through the dispatched backend."""
        return self._mm(jnp.asarray(mat, jnp.int32),
                        jnp.asarray(blocks, jnp.int32), self.p)

    def apply_planned(self, mat, blocks) -> PlanResult:
        """Planned (mat @ blocks) mod p (DESIGN.md §11): dispatched
        through the shape-bucketed AOT executable cache — async; call
        ``.host()`` on the result to block and get exact numpy.  Falls
        back to :meth:`apply` (per-shape jit) without a planner."""
        if self._planned():
            return self.planner.matmul(mat, blocks)
        blocks = np.asarray(blocks)
        return PlanResult(self.apply(mat, blocks), blocks.shape[-1])

    def regenerate_stacked(self, i: int, r_prev, next_data) -> jnp.ndarray:
        """Fused newcomer compute: one (2, k+1) repair-matrix application
        in a single jitted dispatch (matmul + axpy-epilogue, see the
        kernel comment above; custom matmuls get the literal stacked
        (2, k+1) @ (k+1, S) product).

        Returns the (2, S) stack [a_{i-1}; r_i] — bit-exactly the lost
        node's pair (row 0 = data block, row 1 = redundancy block).
        """
        r_prev = jnp.asarray(r_prev, jnp.int32)
        next_data = jnp.asarray(next_data, jnp.int32)
        if next_data.shape[0] != self.k:
            raise ValueError(f"expected {self.k} helper data blocks, "
                             f"got {next_data.shape[0]}")
        if self._jittable:
            return _fused_regenerate(self._mm, self._rmat, r_prev,
                                     next_data, self.p)
        helpers = jnp.concatenate([r_prev[None, :], next_data], axis=0)
        return self._mm(self._rmat, helpers, self.p)

    def regenerate(self, i: int, r_prev, next_data) -> tuple[jnp.ndarray, jnp.ndarray]:
        out = self.regenerate_stacked(i, r_prev, next_data)
        return out[0], out[1]

    def regenerate_planned(self, i: int, r_prev, next_data) -> PlanResult:
        """Planned fused newcomer compute: the (2, k+1) repair-matrix
        application through one bucketed AOT executable per (k, bucket).
        Same contract as :meth:`regenerate_stacked`, asynchronous; a
        ``uint8`` ``next_data`` is widened inside the executable."""
        next_data = np.asarray(next_data)
        if next_data.shape[0] != self.k:
            raise ValueError(f"expected {self.k} helper data blocks, "
                             f"got {next_data.shape[0]}")
        if self._planned():
            return self.planner.regenerate(self._rmat_np, r_prev, next_data)
        r_prev = np.asarray(r_prev, np.int32)
        return PlanResult(self.regenerate_stacked(i, r_prev, next_data),
                          r_prev.shape[-1])

    def regenerate_batch_planned(self, nodes: Sequence[int], r_prevs,
                                 next_data) -> PlanResult:
        """Planned batched fused regeneration: BOTH the stream axis and
        the failed-node axis F are bucketed (a 3-stripe and a 5-stripe
        drain share one executable); ``.host()`` returns the exact
        (F, 2, S) stack.  Falls back to :meth:`regenerate_batch`."""
        r_prevs = np.asarray(r_prevs, np.int32)
        next_data = np.asarray(next_data)
        f = len(nodes)
        if r_prevs.shape[0] != f or next_data.shape[:2] != (f, self.k):
            raise ValueError(f"helper shapes {r_prevs.shape}/{next_data.shape}"
                             f" do not match {f} nodes, k={self.k}")
        if self._planned():
            return self.planner.regenerate_batch(self._rmat_np, r_prevs,
                                                 next_data)
        return PlanResult(self.regenerate_batch(nodes, r_prevs, next_data),
                          r_prevs.shape[-1], batch=f)

    def regenerate_batch(self, nodes: Sequence[int], r_prevs, next_data, *,
                         tile_symbols: int | None = None) -> jnp.ndarray:
        """Batched fused regeneration, vmapped over failed nodes.

        r_prevs: (F, S) — r_{i-1} per failed node, plan order.
        next_data: (F, k, S) — the k helper data blocks per failed node.
        Returns (F, 2, S): [a_lost; r_new] per node.

        The stream axis is processed in ``tile_symbols`` tiles (bounds the
        device working set; XLA pipelines the per-tile dispatches).  The
        node axis is vmapped through the backend matmul; only custom
        (non-jittable) matmuls dispatch per node.
        """
        r_prevs = jnp.asarray(r_prevs, jnp.int32)
        next_data = jnp.asarray(next_data, jnp.int32)
        f = len(nodes)
        if r_prevs.shape[0] != f or next_data.shape[:2] != (f, self.k):
            raise ValueError(f"helper shapes {r_prevs.shape}/{next_data.shape}"
                             f" do not match {f} nodes, k={self.k}")
        s = r_prevs.shape[-1]
        tile = s if tile_symbols is None else max(1, tile_symbols)
        parts = []
        for s0 in range(0, s, tile):
            parts.append(self._regen_tile_batch(
                nodes, r_prevs[:, s0:s0 + tile],
                next_data[:, :, s0:s0 + tile]))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)

    def _regen_tile_batch(self, nodes, r_prevs, next_data) -> jnp.ndarray:
        if self._jittable:
            return _fused_regenerate_vmapped(self._mm, self._rmat,
                                             r_prevs, next_data, self.p)
        return jnp.stack([self.regenerate_stacked(i, r_prevs[f], next_data[f])
                          for f, i in enumerate(nodes)])

    # ----------------------------------------------------------- reconstruct
    def decode_matrix(self, subset: Sequence[int]) -> np.ndarray:
        """Cached (n, n) any-k decode matrix for a sorted node subset."""
        return self.decode_cache.inverse(tuple(subset))

    def decode_repair_matrix(self, subset: Sequence[int],
                             failed: Sequence[int]) -> np.ndarray:
        """(n + F, n) combined decode + re-encode matrix.

        Rows 0..n-1 recover the full data matrix; row n + j re-encodes the
        redundancy block of ``failed[j]`` (r_f = M^T[f-1] @ data), so a
        multi-failure repair produces ALL lost pairs from one matmul with
        the downloads.  The tiny (F, n) @ (n, n) host product rides on the
        cached inverse.
        """
        inv = self.decode_cache.inverse(tuple(subset))
        rows = np.asarray([self._mt[f - 1] for f in failed], dtype=np.int64)
        red_rows = (rows @ inv.astype(np.int64)) % self.p
        return np.concatenate([inv.astype(np.int64), red_rows],
                              axis=0).astype(np.int32)

    def split_decode_output(self, out):
        """Split a ``decode_repair_matrix`` product into
        (data (n, S), failed_red (F, S)) — the single source of truth for
        the combined matrix's row layout (callers that tile the product
        themselves must not hand-roll this split)."""
        return out[: self.n], out[self.n:]

    def reconstruct(self, node_ids: Sequence[int], data_blocks,
                    red_blocks) -> jnp.ndarray:
        """Any-k reconstruction via the cached inverse (paper §III-B).

        ``node_ids`` may arrive in any order: rows are permuted to the
        sorted subset so every ordering of the same k nodes shares one
        cache entry (and one ``gf.gauss_inverse``).
        """
        ids = [int(x) for x in node_ids]
        if len(set(ids)) != self.k:
            raise ValueError(f"need k={self.k} distinct nodes, got {ids}")
        order = sorted(range(self.k), key=lambda j: ids[j])
        subset = tuple(ids[j] for j in order)
        data_blocks = jnp.asarray(data_blocks, jnp.int32)
        red_blocks = jnp.asarray(red_blocks, jnp.int32)
        if order != list(range(self.k)):
            sel = jnp.asarray(order)
            data_blocks, red_blocks = data_blocks[sel], red_blocks[sel]
        downloads = jnp.concatenate([data_blocks, red_blocks], axis=0)
        return self.apply(self.decode_matrix(subset), downloads)

    def reconstruct_with_repair(self, node_ids: Sequence[int], data_blocks,
                                red_blocks, failed: Sequence[int],
                                ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """One-matmul multi-failure repair: full data AND the failed nodes'
        redundancy blocks from a single decode matmul.

        Returns (data (n, S), failed_red (F, S)) with failed_red rows in
        ``failed`` order.  ``node_ids`` must be sorted (restore reads the
        surviving nodes in id order).
        """
        subset = tuple(int(x) for x in node_ids)
        downloads = jnp.concatenate([jnp.asarray(data_blocks, jnp.int32),
                                     jnp.asarray(red_blocks, jnp.int32)],
                                    axis=0)
        mat = self.decode_repair_matrix(subset, failed)
        return self.split_decode_output(self.apply(mat, downloads))


__all__ = ["RepairEngine", "DecodeInverseCache", "DecodeCacheInfo",
           "build_repair_matrix", "decode_cache_stats"]
