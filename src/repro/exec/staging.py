"""Pooled zero-copy host staging + per-stage wall-time accounting
(DESIGN.md §16).

Every overlapped hot path (store put/get, checkpoint save/restore/
scrub, scheduler repair) used to pay one fresh host allocation per
window: the flatten transpose, the bucket-ladder zero-pad, and the
pack257 byte split each materialized a new ndarray per dispatch.  At
depth-2 pipelining those allocations (plus their first-touch page
faults) sat squarely on the critical thread and ate the overlap win —
BENCH_pipeline showed the depth-2 put *slower* than serial.

:class:`StagingPool` replaces them with a reusable ring of preallocated,
bucket-ladder-sized host buffers:

* ``acquire(shape, dtype)`` returns a view into a pooled buffer whose
  backing allocation is rounded up the same geometric ladder the plan
  cache buckets on — so the window sizes a steady-state stream touches
  map to a handful of distinct pool slots that are reused forever.
* Buffers are **page-touched at allocation** (``prefault=True``): after
  the first use every reuse hits resident pages with a stable address,
  which is what XLA's host-to-device transfer path wants from a staging
  buffer (on device backends the planner additionally donates the
  staged operand — see ``PlanCache.donate``).
* **Aliasing rule**: a buffer handed out by ``acquire`` is never handed
  out again until ``release`` is called on it.  The release points are
  exactly the dispatch-completion points — ``PlanResult.host()`` for
  planner-internal pad staging, and the pipeline consume stage (which
  has just blocked in ``host()``) for caller-owned flatten staging — so
  a reused buffer can never be scribbled while an in-flight compute
  still reads it.  Because the pool grows on demand, its depth is
  always >= the pipeline depth: ``stats().in_use`` is the live count
  tests assert against.
* Dropping a buffer without releasing it is safe (it is simply retired
  from the pool, never reissued), so error paths need no bookkeeping.

The module also owns the process-wide **stage clock**, the program's
one tracing primitive.  ``with staged(name, nbytes, **meta):`` opens a
profiler span ``repro.<name>`` (a ``jax.profiler.TraceAnnotation``
carrying ``meta``, so the span sits on the device trace's clock), and
on exit adds the block's wall seconds, and ``nbytes`` if given, to
stage ``name``.  `stage_times` / `stage_bytes` read the cumulative
sums.  With no trace active a span costs one annotation (about a
microsecond) and one locked add.  Stages: "pack" (bytes <-> symbols,
pack257, stripe transposes), "pad" (planner bucket padding), "h2d" /
"d2h" (bytes handed to and pulled from the device), "serialize",
"format" (npy / npz / CRC bytes), "write" / "fsync" / "read" (node
files), "ckpt.*" (checkpointer operations) and "pipe.*" (the
pipeline's read / dispatch / consume callbacks).
``Pipeline.stage_stats()`` merges "pack" and "pad" with its own
read/dispatch/consume timers into the ``t_stage_read / t_pack / t_pad /
t_dispatch / t_consume`` accounting BENCH_pipeline reports.
"""
from __future__ import annotations

import threading
from collections import defaultdict
from time import perf_counter
from typing import NamedTuple, Optional

import numpy as np
from jax.profiler import TraceAnnotation

# Pool buckets ride their own power-of-two ladder from this floor; it
# deliberately matches the plan cache's BUCKET_MIN so a planner pad of a
# bucketed stream extent is an exact-size pool hit.
POOL_BUCKET_MIN = 1 << 12

# Stage names surfaced by Pipeline.stage_stats() (DESIGN.md §16.3).
STAGE_NAMES = ("t_stage_read", "t_pack", "t_pad", "t_dispatch",
               "t_consume")

# ------------------------------------------------------------ stage clock
_TLOCK = threading.Lock()
_TIMES: dict = defaultdict(float)
_BYTES: dict = defaultdict(int)


def record_stage(name: str, seconds: float, nbytes: int = 0) -> None:
    """Add ``seconds`` of wall time and ``nbytes`` bytes to stage
    ``name`` (thread-safe; called from pool workers and the dispatch
    thread)."""
    with _TLOCK:
        _TIMES[name] += seconds
        if nbytes:
            _BYTES[name] += nbytes


def stage_times() -> dict:
    """Cumulative process-wide seconds per stage."""
    with _TLOCK:
        return dict(_TIMES)


def stage_bytes() -> dict:
    """Cumulative process-wide bytes per stage, for the stages that
    count bytes."""
    with _TLOCK:
        return dict(_BYTES)


class staged:
    """``with staged(name, nbytes=None, **meta) as span:`` -- a profiler
    span ``repro.<name>`` with ``meta`` as its metadata, whose wall
    seconds (``span.seconds`` after exit) and ``span.nbytes`` (settable
    inside the block, for sizes known only at its end) are added to
    stage ``name``."""

    __slots__ = ("name", "nbytes", "seconds", "_span", "_t0")

    def __init__(self, name: str, nbytes: Optional[int] = None, **meta):
        self.name = name
        self.nbytes = nbytes
        self.seconds = 0.0
        self._span = TraceAnnotation(f"repro.{name}", **meta)

    def __enter__(self) -> "staged":
        self._span.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = perf_counter() - self._t0
        self._span.__exit__(*exc)
        record_stage(self.name, self.seconds, self.nbytes or 0)


# ------------------------------------------------------------------- pool
class StagingStats(NamedTuple):
    """Pool accounting: ``hits`` reused a pooled buffer, ``misses``
    allocated a fresh one, ``in_use`` are acquired-but-unreleased
    buffers (the pipeline-depth invariant tests watch), ``pooled_bytes``
    is the resident free-list footprint."""
    hits: int
    misses: int
    released: int
    in_use: int
    pooled_bytes: int


def _bucket_elems(elems: int) -> int:
    """Smallest power-of-two ladder size >= elems (floor
    POOL_BUCKET_MIN) — the pool's allocation granularity."""
    b = POOL_BUCKET_MIN
    while b < elems:
        b <<= 1
    return b


class StagingPool:
    """A reusable ring of bucket-ladder-sized host staging buffers.

    Parameters
    ----------
    max_pooled : int
        Cap on retained free buffers per (dtype, bucket) slot; releases
        beyond it simply drop the buffer (steady-state streams need at
        most pipeline-depth + in-flight buffers per slot).
    prefault : bool
        Touch every page at allocation so reuses never fault and the
        buffer keeps a stable resident address across dispatches (the
        pinned-host staging property device transfer engines want).

    Notes
    -----
    ``acquire`` may return a reshaped *view* of the pooled base buffer;
    ``release`` accepts the view (it walks ``.base``).  Releasing an
    array the pool never issued is a safe no-op, and double-release is
    idempotent.
    """

    def __init__(self, max_pooled: int = 8, prefault: bool = True):
        self.max_pooled = int(max_pooled)
        self.prefault = bool(prefault)
        self._lock = threading.Lock()
        self._free: dict = defaultdict(list)   # (dtype.str, bucket) -> bufs
        self._in_use: dict = {}                # id(base) -> (key, base)
        self.hits = 0
        self.misses = 0
        self.released = 0

    def acquire(self, shape, dtype=np.int32) -> np.ndarray:
        """A ``shape``-shaped view into a pooled host buffer.  Contents
        are UNDEFINED (callers overwrite every element or zero the tail
        themselves — that is the zero-copy point)."""
        shape = tuple(int(x) for x in shape)
        dt = np.dtype(dtype)
        elems = 1
        for x in shape:
            elems *= x
        key = (dt.str, _bucket_elems(max(elems, 1)))
        with self._lock:
            free = self._free.get(key)
            if free:
                base = free.pop()
                self.hits += 1
            else:
                base = None
                self.misses += 1
        if base is None:
            base = np.empty(key[1], dt)
            if self.prefault:
                base.fill(0)            # touch every page once
        with self._lock:
            self._in_use[id(base)] = (key, base)
        return base[:elems].reshape(shape)

    @staticmethod
    def _base_of(arr: np.ndarray) -> np.ndarray:
        while arr.base is not None and isinstance(arr.base, np.ndarray):
            arr = arr.base
        return arr

    def release(self, arr) -> None:
        """Return ``arr``'s backing buffer to the pool.  Only call once
        the consuming dispatch has completed (``PlanResult.host()`` has
        returned) — that is the aliasing rule (DESIGN.md §16.2)."""
        if not isinstance(arr, np.ndarray):
            return
        base = self._base_of(arr)
        with self._lock:
            entry = self._in_use.pop(id(base), None)
            if entry is None:
                return                  # foreign array / double release
            key, buf = entry
            self.released += 1
            if len(self._free[key]) < self.max_pooled:
                self._free[key].append(buf)

    def stats(self) -> StagingStats:
        with self._lock:
            pooled = sum(b.nbytes for bufs in self._free.values()
                         for b in bufs)
            return StagingStats(self.hits, self.misses, self.released,
                                len(self._in_use), pooled)

    def clear(self) -> None:
        """Drop every retained buffer (tests / memory pressure)."""
        with self._lock:
            self._free.clear()
            self._in_use.clear()
            self.hits = self.misses = self.released = 0


__all__ = ["StagingPool", "StagingStats", "POOL_BUCKET_MIN", "STAGE_NAMES",
           "record_stage", "stage_times", "stage_bytes", "staged"]
