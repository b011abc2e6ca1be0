"""Shape-bucketed execution-plan cache (DESIGN.md §11.1-§11.2).

Every hot-path GF operation has one large *stream* axis (symbols) whose
extent varies per object/leaf/stripe, and a handful of tiny static axes
(the code dimensions n, k, a batch count F).  `jax.jit` keyed on raw
shapes retraces and recompiles once per distinct stream extent — a
mixed-size workload with thousands of object sizes pays thousands of
XLA compiles for what is the same program at different paddings.

The :class:`PlanCache` removes that cost structurally:

* the stream axis is padded **up** to a small geometric ladder of shape
  buckets (:func:`bucket_symbols`) — log-many buckets cover any size
  range, and padding is bit-exact because every planned op is
  column-local over the stream axis (zero columns in, zero columns out,
  sliced off host-side before anyone looks);
* variable *batch* axes (the F failed-node axis of ``regenerate_batch``)
  are bucketed the same way, so a drain of 3 stripes and a drain of 5
  share one executable;
* each ``(op, static dims, bucket)`` key is lowered ONCE to an
  ahead-of-time compiled executable (``jax.jit(...).lower(...)
  .compile()``) with the stream operand **donated** on device backends
  whenever an output can actually alias it (encode's (n, S) -> (n, S),
  the square any-k decode) — the padded staging buffer is dead after
  the call, so XLA reuses it instead of allocating;
* a ``uint8`` stream operand (data symbols, one byte each) is shipped
  as bytes and widened to int32 inside the executable; its dtype joins
  the plan key, int32 keys stay as they were, and it is never donated
  (an int32 output cannot alias a byte buffer);
* :func:`plan_stats` exposes lifetime hits / misses / compiles across
  every live planner, which is how the recompile-regression test and
  ``benchmarks/bench_pipeline.py`` assert the steady-state guarantee:
  after warm-up, a mixed-size put/get/restore workload performs ZERO
  new compiles.

Planners are shared process-wide per ``(backend, p, ladder, donation,
mesh)`` via :func:`get_planner` so every code instance on the same
backend hits one executable cache.  :func:`planning_disabled` restores
the raw jit-per-shape dispatch (the pre-plan behavior) for A/B
measurement.

Mesh-sharded plans (DESIGN.md §14): pass ``mesh=`` (a
``repro.sharding.mesh.StreamMesh``, an int shard count, or None) and
every executable is lowered as ``jit(shard_map(op))`` over the stream
axis under the declarative rule registry.  The bucket ladder then runs
*per shard*: the stream extent is split ceil(s / m) per device, THAT is
bucketed, and the global operand pads to ``m * shard_bucket`` — so each
shape bucket compiles once per-shard shape, stream lengths not
divisible by the mesh just pad (still bit-exact: column-local ops),
and a 1-device mesh normalizes to the plain unsharded planner (same
object, same executables — no spurious recompiles when the device
count collapses to one).
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .staging import StagingPool, staged

# Ladder defaults: buckets 4096, 8192, 16384, ... — stream extents below
# the floor all share the smallest executable, and a ratio-2 ladder
# bounds padded compute at 2x while keeping the executable count
# logarithmic in the size range.  Ratio 2 also makes every power-of-two
# tile (the checkpointer's stream tiles, the store's full put windows)
# an EXACT bucket hit, so the tiled hot loops never pad at all — only
# odd tails and whole small objects pay the padding tax.
BUCKET_MIN = 1 << 12
BUCKET_RATIO = 2.0

# Batch axes (regenerate_batch's F) are tiny; a finer floor avoids
# padding a single-failure repair up to a 4096-wide batch.
BATCH_BUCKET_MIN = 4

_ENABLED = True
_LOCK = threading.Lock()
_REGISTRY: dict[tuple, "PlanCache"] = {}


def bucket_symbols(s: int, *, bucket_min: int = BUCKET_MIN,
                   ratio: float = BUCKET_RATIO) -> int:
    """Smallest ladder bucket >= ``s``: bucket_min * ratio^j, j >= 0.

    >>> bucket_symbols(1000)
    4096
    >>> bucket_symbols(4097)
    8192
    """
    if s <= 0:
        raise ValueError(f"stream extent must be positive, got {s}")
    if ratio <= 1.0:
        raise ValueError(f"ladder ratio must be > 1, got {ratio}")
    if s <= bucket_min:
        return bucket_min
    # ceil in log space, then walk down float error
    j = max(0, math.ceil(math.log(s / bucket_min) / math.log(ratio)))
    b = int(math.ceil(bucket_min * ratio ** j))
    while b < s:                                   # float round-down guard
        j += 1
        b = int(math.ceil(bucket_min * ratio ** j))
    while j > 0 and int(math.ceil(bucket_min * ratio ** (j - 1))) >= s:
        j -= 1
        b = int(math.ceil(bucket_min * ratio ** j))
    return b


def set_planning(enabled: bool) -> None:
    """Process-wide switch: False restores raw jit-per-shape dispatch."""
    global _ENABLED
    _ENABLED = bool(enabled)


def planning_enabled() -> bool:
    return _ENABLED


@contextlib.contextmanager
def planning_disabled():
    """Temporarily bypass every plan cache (the benchmark's "before")."""
    prev = _ENABLED
    set_planning(False)
    try:
        yield
    finally:
        set_planning(prev)


def make_regen_fn(mm: Callable, p: int) -> Callable:
    """THE fused newcomer kernel — the single definition both execution
    modes trace (planned AOT executables here, the per-shape jit paths
    in `core/repair.py`), so the two can never desync.

    Algebraically R @ [r_prev; next_data]; the r_prev column is peeled
    out of the dispatched matmul into a row-0 scale-accumulate epilogue
    (R[1, 0] is 0, so only the decode row touches r_prev).  Exactness:
    the matmul output is < p and the epilogue term is <= (p-1)^2, so the
    sum stays inside the int32 envelope (kernels/envelope.py guarantees
    (p-1) + (p-1)^2 < 2^31) before the single fold.
    """
    def fn(rmat, r_prev, next_data):
        part = mm(rmat[:, 1:], next_data, p)
        return part.at[0].set((part[0] + rmat[0, 0] * r_prev) % p)

    return fn


class PlanStats(NamedTuple):
    """Executable-cache accounting: ``misses`` trigger ``compiles``
    (they differ only if a lowering raises), ``hits`` run an existing
    executable with zero trace/compile work."""
    hits: int
    misses: int
    compiles: int


class PlanResult:
    """A planned op's asynchronous result: the (possibly padded) device
    value plus the true stream extent.

    Dispatch is async — holding a PlanResult does NOT block on the
    device.  :meth:`host` blocks, materializes, and slices the padding
    off with a host-side numpy view (deliberately NOT a device slice:
    a ``lax.slice`` per distinct extent would reintroduce the very
    per-shape compiles the plan cache exists to remove).
    """

    __slots__ = ("raw", "symbols", "batch", "_release")

    def __init__(self, raw, symbols: int, batch: Optional[int] = None,
                 release: Optional[Callable] = None):
        self.raw = raw
        self.symbols = int(symbols)
        self.batch = None if batch is None else int(batch)
        self._release = release

    def host(self) -> np.ndarray:
        """Block and return the exact (unpadded) result as numpy —
        stream padding sliced off the last axis, batch padding (when the
        op bucketed a leading batch axis) off the first.

        Materializing is also the staging release point: any pooled pad
        buffers the dispatch read are recycled here, AFTER the blocking
        conversion proves the compute consumed them (DESIGN.md §16.2).
        A PlanResult dropped without ``host()`` simply strands its
        buffers — the pool never reissues an unreleased buffer, so that
        is safe, just not free.  The pull is the "d2h" stage, counting
        the raw (padded) result's bytes."""
        raw = self.raw
        with staged("d2h", nbytes=raw.nbytes
                    if isinstance(raw, jax.Array) else None):
            out = np.asarray(raw)
        if self._release is not None:
            rel, self._release = self._release, None
            rel()
        if out.shape[-1] != self.symbols:
            out = out[..., : self.symbols]
        if self.batch is not None and out.shape[0] != self.batch:
            out = out[: self.batch]
        return out

    def __array__(self, dtype=None):
        out = self.host()
        return out if dtype is None else out.astype(dtype)


def _stream(arr) -> np.ndarray:
    """A stream operand as the planner ships it: ``uint8`` (data symbols,
    one byte each) stays ``uint8`` and is widened inside the executable;
    anything else is int32 symbols, as before."""
    arr = np.asarray(arr)
    return arr if arr.dtype == np.uint8 else arr.astype(np.int32, copy=False)


def _pad_last(arr: np.ndarray, bucket: int,
              pool: Optional["StagingPool"] = None,
              bufs: Optional[list] = None) -> np.ndarray:
    """Zero-pad the stream (last) axis up to ``bucket``, in the operand's
    dtype (:func:`_stream`).

    JAX reads host operands asynchronously (after dispatch returns), so
    a scratch buffer may not be reused while an in-flight compute still
    reads it.  With ``pool`` set, the pad stages into a pooled buffer
    appended to ``bufs`` — the caller attaches the buffers to the
    PlanResult, whose ``host()`` (the dispatch-completion proof)
    releases them back to the pool (DESIGN.md §16.2).  Without a pool
    the historical always-fresh buffer keeps the same safety the hard
    way.
    """
    arr = _stream(arr)
    s = arr.shape[-1]
    if s == bucket:
        return arr
    with staged("pad"):
        if pool is None:
            out = np.zeros(arr.shape[:-1] + (bucket,), arr.dtype)
            out[..., :s] = arr
        else:
            out = pool.acquire(arr.shape[:-1] + (bucket,), arr.dtype)
            out[..., :s] = arr
            out[..., s:] = 0        # reused buffer: tail must be re-zeroed
            bufs.append(out)
    return out


def _pad_both(arr: np.ndarray, f_bucket: int, s_bucket: int,
              pool: Optional["StagingPool"] = None,
              bufs: Optional[list] = None) -> np.ndarray:
    """Pad axis 0 to ``f_bucket`` and the last axis to ``s_bucket`` in
    one copy (the batched-regenerate operands); pooled like
    :func:`_pad_last` when ``pool`` is set."""
    arr = _stream(arr)
    f, s = arr.shape[0], arr.shape[-1]
    if f == f_bucket and s == s_bucket:
        return arr
    with staged("pad"):
        shape = (f_bucket,) + arr.shape[1:-1] + (s_bucket,)
        if pool is None:
            out = np.zeros(shape, arr.dtype)
            out[:f, ..., :s] = arr
        else:
            out = pool.acquire(shape, arr.dtype)
            out[...] = 0
            out[:f, ..., :s] = arr
            bufs.append(out)
    return out


class PlanCache:
    """AOT-compiled, shape-bucketed executables for one (backend, p).

    Parameters
    ----------
    backend : repro.kernels.dispatch.GFBackend
        The exact GF implementation the plans lower through; its matmul
        / circulant_encode primitives are traced INSIDE each plan, so a
        plan is exactly the dispatched op at a fixed padded shape.
    p : int
        Field modulus (static in every executable).
    bucket_min, bucket_ratio :
        The stream-axis ladder (:func:`bucket_symbols`).
    donate : bool, optional
        Donate the stream operand to XLA where an output can alias it.
        Default: True on device backends (gpu/tpu — operands live in
        device buffers the planner's host copy populated), False on CPU,
        where XLA may read the HOST numpy buffer in place: donating an
        exact-bucket-fit caller array there could let the output
        overwrite caller memory.  Donation is disabled on sharded plans
        (the padded staging buffer is host-side and gets scattered to
        per-device shards; there is no whole-buffer alias to reuse).
    mesh : StreamMesh | int | None, optional
        Shard every plan over this stream-axis mesh (DESIGN.md §14).
        A 1-device mesh is normalized to None — the plain dispatch
        fallback.

    Notes
    -----
    All planned ops are column-local over the stream axis, which is the
    bit-exactness argument for bucketing: a zero symbol column maps to a
    zero output column through matmul, circulant encode and the fused
    regenerate epilogue alike, and :meth:`PlanResult.host` slices those
    columns off before any caller sees them.
    """

    def __init__(self, backend, p: int, *, bucket_min: int = BUCKET_MIN,
                 bucket_ratio: float = BUCKET_RATIO,
                 donate: Optional[bool] = None, mesh=None):
        from repro.sharding.mesh import as_stream_mesh
        self.backend = backend
        self.backend_name = getattr(backend, "name", "custom")
        self.p = int(p)
        self.bucket_min = int(bucket_min)
        self.bucket_ratio = float(bucket_ratio)
        mesh = as_stream_mesh(mesh)
        if mesh is not None and mesh.is_trivial:
            mesh = None                 # single-device: plain dispatch
        self.mesh = mesh
        if donate is None:
            donate = jax.default_backend() not in ("cpu",)
        if mesh is not None:
            donate = False              # see class docstring
        self.donate = bool(donate)
        # pooled zero-copy pad staging (DESIGN.md §16): pad buffers are
        # acquired here and released by PlanResult.host() once the
        # dispatch that read them has provably completed
        self.staging = StagingPool()
        self._plans: dict[tuple, Callable] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.compiles = 0

    # ------------------------------------------------------------- plumbing
    def bucket(self, s: int) -> int:
        return bucket_symbols(s, bucket_min=self.bucket_min,
                              ratio=self.bucket_ratio)

    def batch_bucket(self, f: int) -> int:
        return bucket_symbols(f, bucket_min=BATCH_BUCKET_MIN,
                              ratio=self.bucket_ratio)

    def stream_pad(self, s: int) -> tuple[int, int]:
        """(plan-key bucket, padded stream extent) for a true extent s.

        Unsharded: both are the ladder bucket.  Sharded: the ladder runs
        per shard — bucket ceil(s / m), pad the global operand to
        m * shard_bucket so every device sees the same bucketed shard
        shape (one compile per-shard shape; lengths not divisible by the
        mesh just pad, still bit-exact because the ops are column-local).
        """
        if self.mesh is None:
            b = self.bucket(s)
            return b, b
        sb = self.bucket(self.mesh.shard_extent(s))
        return sb, sb * self.mesh.size

    def _compile(self, op: str, fn: Callable, shapes, donate=(),
                 dtypes=None):
        """Lower + AOT-compile ``fn`` at ``shapes`` (int32 operands unless
        ``dtypes`` names each one's): plain jit when unsharded,
        ``jit(shard_map(fn))`` under the op's registered sharding rule
        when meshed (inputs/outputs pinned to the rule's NamedShardings,
        so host numpy operands are scattered straight to their
        per-device shards at call time).

        ``fn`` widens every operand to int32 first, so a ``uint8`` data
        operand crosses the link at one byte a symbol and is widened on
        the device; for int32 operands the cast traces to nothing.  Only
        int32 operands are donated: an int32 output cannot alias a byte
        buffer."""
        if dtypes is None:
            dtypes = (np.int32,) * len(shapes)
        donate = tuple(i for i in donate if dtypes[i] == np.int32)
        body = fn

        def fn(*xs):
            return body(*(x.astype(jnp.int32) for x in xs))

        if self.mesh is None:
            jf = jax.jit(fn, donate_argnums=donate)
        else:
            from repro.sharding.mesh import get_rule, shard_body
            rule = get_rule(op)
            jf = jax.jit(shard_body(fn, op, self.mesh),
                         in_shardings=self.mesh.shardings(rule.in_specs),
                         out_shardings=self.mesh.sharding(rule.out_specs))
        return jf.lower(*(jax.ShapeDtypeStruct(s, d)
                          for s, d in zip(shapes, dtypes))).compile()

    def _exe(self, key: tuple, build: Callable[[], Callable]) -> Callable:
        with self._lock:
            exe = self._plans.get(key)
            if exe is not None:
                self.hits += 1
                return exe
            self.misses += 1
            exe = build()
            self.compiles += 1
            self._plans[key] = exe
            return exe

    @staticmethod
    def _run(exe: Callable, *operands: np.ndarray):
        """Call ``exe`` on host operands: the "h2d" stage, counting the
        bytes handed to the device, padding included."""
        with staged("h2d", nbytes=sum(x.nbytes for x in operands)):
            return exe(*operands)

    def _releaser(self, bufs: list) -> Optional[Callable]:
        """A PlanResult release hook recycling ``bufs`` (pooled pad
        staging) — None when nothing was staged."""
        if not bufs:
            return None
        pool = self.staging

        def rel():
            for b in bufs:
                pool.release(b)

        return rel

    @staticmethod
    def _tagged(key: tuple, tag: Optional[str]) -> tuple:
        """Mix a family tag into a plan key, so families with
        overlapping shapes never share an executable slot.  ``None``
        (every pre-existing caller) leaves the key byte-identical — no
        recompiles ride along with the tagging feature."""
        return key if tag is None else key + (tag,)

    @staticmethod
    def _typed(key: tuple, dtype) -> tuple:
        """Mix a non-int32 stream dtype (``uint8`` data symbols) into a
        plan key; int32 operands keep their key and executable."""
        return key if dtype == np.int32 else key + (np.dtype(dtype).name,)

    def plan_stats(self) -> PlanStats:
        return PlanStats(self.hits, self.misses, self.compiles)

    def reset_stats(self) -> None:
        self.hits = self.misses = self.compiles = 0

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
        self.reset_stats()

    def __len__(self) -> int:
        return len(self._plans)

    # ------------------------------------------------------------------ ops
    def matmul(self, mat, blocks, *, tag: Optional[str] = None) -> PlanResult:
        """(mat @ blocks) mod p — the decode-side workhorse.

        ``mat`` is a small runtime operand (cached decode inverses, the
        combined decode+re-encode matrix, row subsets for degraded
        reads); its shape is part of the plan key, its VALUES are not.
        Only ``blocks`` (the stream operand) is padded and donated.
        ``tag`` is the dispatching code family's identity, mixed into
        the plan key (DESIGN.md §15.4).
        """
        mat = np.asarray(mat, np.int32)
        blocks = _stream(blocks)
        s = blocks.shape[-1]
        if not _ENABLED:
            return PlanResult(self.backend.matmul(mat, blocks, self.p), s)
        b, pad = self.stream_pad(s)
        key = self._typed(self._tagged(
            ("matmul", mat.shape, blocks.shape[:-1], b), tag), blocks.dtype)
        # donation is only usable when an output can alias the donated
        # buffer, i.e. the product has the stream operand's exact shape
        # (square decode matrices: the (n, n) any-k inverse) — donating
        # anything else just trips XLA's unusable-donation warning
        donate = (1,) if self.donate and mat.shape[0] == blocks.shape[0] \
            else ()

        def build():
            fn = lambda a, x: self.backend.matmul(a, x, self.p)
            return self._compile("matmul", fn,
                                 (mat.shape, blocks.shape[:-1] + (pad,)),
                                 donate, (np.int32, blocks.dtype))

        bufs: list = []
        padded = _pad_last(blocks, pad, self.staging, bufs)
        return PlanResult(self._run(self._exe(key, build), mat, padded), s,
                          release=self._releaser(bufs))

    def circulant_encode(self, data, c, *, tag: Optional[str] = None,
                         ) -> PlanResult:
        """The paper's eq. (2) encode at a bucketed stream extent.

        The coefficient tuple ``c`` is static in the underlying kernels,
        so it is part of the plan key — one executable per code, not per
        call.
        """
        data = _stream(data)
        c = tuple(int(x) for x in c)
        s = data.shape[-1]
        if not _ENABLED:
            return PlanResult(self.backend.circulant_encode(data, c, self.p),
                              s)
        b, pad = self.stream_pad(s)
        key = self._typed(self._tagged(("circ", data.shape[0], c, b), tag),
                          data.dtype)

        def build():
            fn = lambda d: self.backend.circulant_encode(d, c, self.p)
            return self._compile("circulant_encode", fn,
                                 ((data.shape[0], pad),),
                                 (0,) if self.donate else (), (data.dtype,))

        bufs: list = []
        padded = _pad_last(data, pad, self.staging, bufs)
        return PlanResult(self._run(self._exe(key, build), padded), s,
                          release=self._releaser(bufs))

    def regenerate(self, rmat, r_prev, next_data) -> PlanResult:
        """The fused (2, k+1) repair-matrix application (DESIGN.md §4):
        backend matmul over the k helper blocks + the row-0 axpy
        epilogue on r_prev, one executable per (k, bucket)."""
        rmat = np.asarray(rmat, np.int32)
        r_prev = np.asarray(r_prev, np.int32)
        next_data = _stream(next_data)
        s = r_prev.shape[-1]
        if not _ENABLED:
            return PlanResult(
                self._regen_fn()(rmat, r_prev, next_data), s)
        b, pad = self.stream_pad(s)
        k = next_data.shape[0]
        key = self._typed(("regen", k, b), next_data.dtype)

        def build():
            # the (2, S) pair can alias next_data only at k == 2
            donate = (2,) if self.donate and k == 2 else ()
            return self._compile("regenerate", self._regen_fn(),
                                 (rmat.shape, (pad,), (k, pad)), donate,
                                 (np.int32, np.int32, next_data.dtype))

        bufs: list = []
        return PlanResult(self._run(
            self._exe(key, build), rmat,
            _pad_last(r_prev, pad, self.staging, bufs),
            _pad_last(next_data, pad, self.staging, bufs)), s,
            release=self._releaser(bufs))

    def regenerate_batch(self, rmat, r_prevs, next_data) -> PlanResult:
        """Vmapped fused regeneration with BOTH variable axes bucketed:
        the stream axis on the symbol ladder, the failed-node axis F on
        the batch ladder (zero-padded tasks regenerate zeros).

        Returns a PlanResult whose raw value is (F_bucket, 2, S_bucket);
        ``host()`` trims both paddings back to (F, 2, S).
        """
        rmat = np.asarray(rmat, np.int32)
        r_prevs = np.asarray(r_prevs, np.int32)
        next_data = _stream(next_data)
        s = r_prevs.shape[-1]
        f, k = next_data.shape[0], next_data.shape[1]
        if not _ENABLED:
            one = self._regen_fn()
            return PlanResult(jax.vmap(lambda rp, nd: one(rmat, rp, nd))(
                r_prevs, next_data), s, batch=f)
        b, pad = self.stream_pad(s)
        fb = self.batch_bucket(f)
        key = self._typed(("regen_batch", fb, k, b), next_data.dtype)

        def build():
            one = self._regen_fn()

            def fn(rm, rps, nds):
                return jax.vmap(lambda rp, nd: one(rm, rp, nd))(rps, nds)

            # the (F, 2, S) output can alias next_data only at k == 2
            donate = (2,) if self.donate and k == 2 else ()
            return self._compile("regenerate_batch", fn,
                                 (rmat.shape, (fb, pad), (fb, k, pad)),
                                 donate, (np.int32, np.int32, next_data.dtype))

        bufs: list = []
        return PlanResult(self._run(
            self._exe(key, build), rmat,
            _pad_both(r_prevs, fb, pad, self.staging, bufs),
            _pad_both(next_data, fb, pad, self.staging, bufs)), s, batch=f,
            release=self._releaser(bufs))

    def matmul_batch(self, mats, blocks, *,
                     tag: Optional[str] = None) -> PlanResult:
        """Per-element batched (q, d) @ (d, S) mod p — the coalesced
        regeneration dispatch for families WITHOUT a node-invariant
        repair matrix (product-matrix MSR: the newcomer matrix differs
        per (node, helpers), so ``regenerate_batch``'s shared-matrix
        vmap does not apply).

        mats: (F, q, d) int — one newcomer matrix per batch element.
        blocks: (F, d, S) — the stacked helper sends per element.
        Returns (F, q, S) via ``host()``; both the batch axis and the
        stream axis are bucketed (zero-padded elements multiply zeros).
        """
        mats = np.asarray(mats, np.int32)
        blocks = np.asarray(blocks, np.int32)
        if mats.ndim != 3 or blocks.ndim != 3 or \
                mats.shape[0] != blocks.shape[0] or \
                mats.shape[2] != blocks.shape[1]:
            raise ValueError(f"matmul_batch needs (F, q, d) mats and "
                             f"(F, d, S) blocks, got {mats.shape} / "
                             f"{blocks.shape}")
        f, s = blocks.shape[0], blocks.shape[-1]
        if not _ENABLED:
            out = ((mats.astype(np.int64) @ blocks.astype(np.int64))
                   % self.p).astype(np.int32)
            return PlanResult(out, s, batch=f)
        b, pad = self.stream_pad(s)
        fb = self.batch_bucket(f)
        key = self._tagged(("matmul_batch", mats.shape[1:], fb, b), tag)

        def build():
            def fn(ms, xs):
                return jax.vmap(
                    lambda m, x: self.backend.matmul(m, x, self.p))(ms, xs)

            return self._compile(
                "matmul_batch", fn,
                ((fb,) + mats.shape[1:], (fb, blocks.shape[1], pad)))

        if mats.shape[0] != fb:     # tiny (F, q, d) stack: plain pad
            pm = np.zeros((fb,) + mats.shape[1:], np.int32)
            pm[:f] = mats
            mats = pm
        bufs: list = []
        return PlanResult(self._run(
            self._exe(key, build), mats,
            _pad_both(blocks, fb, pad, self.staging, bufs)),
            s, batch=f, release=self._releaser(bufs))

    def _regen_fn(self):
        return make_regen_fn(self.backend.matmul, self.p)


# --------------------------------------------------------------- registry
def get_planner(backend, p: int, *, bucket_min: int = BUCKET_MIN,
                bucket_ratio: float = BUCKET_RATIO,
                donate: Optional[bool] = None, mesh=None) -> PlanCache:
    """The shared PlanCache for (backend, p, ladder, donation, mesh) —
    every code/engine on the same backend and mesh shares one executable
    cache.  A 1-device mesh normalizes to the UNSHARDED planner (the
    very same object), so collapsing the device count to one changes
    neither results nor compile counts."""
    from repro.sharding.mesh import as_stream_mesh
    mesh = as_stream_mesh(mesh)
    if mesh is not None and mesh.is_trivial:
        mesh = None
    if donate is None:
        donate = jax.default_backend() not in ("cpu",)
    if mesh is not None:
        donate = False                  # matches PlanCache normalization
    key = (getattr(backend, "name", id(backend)), int(p), int(bucket_min),
           float(bucket_ratio), bool(donate),
           None if mesh is None else mesh.key())
    with _LOCK:
        pc = _REGISTRY.get(key)
        if pc is None:
            pc = PlanCache(backend, p, bucket_min=bucket_min,
                           bucket_ratio=bucket_ratio, donate=donate,
                           mesh=mesh)
            _REGISTRY[key] = pc
        return pc


def plan_stats() -> PlanStats:
    """Aggregate hits/misses/compiles over every live planner — the
    number tests and ``bench_pipeline`` watch for steady-state zeros."""
    h = m = c = 0
    with _LOCK:
        planners = list(_REGISTRY.values())
    for pc in planners:
        st = pc.plan_stats()
        h += st.hits
        m += st.misses
        c += st.compiles
    return PlanStats(h, m, c)


def reset_plan_stats() -> None:
    with _LOCK:
        planners = list(_REGISTRY.values())
    for pc in planners:
        pc.reset_stats()


def clear_planners() -> None:
    """Drop every cached executable AND registry entry (tests only)."""
    with _LOCK:
        for pc in _REGISTRY.values():
            pc.clear()
        _REGISTRY.clear()


__all__ = [
    "BUCKET_MIN", "BUCKET_RATIO", "BATCH_BUCKET_MIN",
    "bucket_symbols", "make_regen_fn",
    "PlanCache", "PlanResult", "PlanStats",
    "get_planner", "plan_stats",
    "reset_plan_stats", "clear_planners",
    "set_planning", "planning_enabled", "planning_disabled",
]
