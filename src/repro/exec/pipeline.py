"""Unified overlapped I/O⇄compute pipeline (DESIGN.md §11.3).

One stage engine for every hot path that used to hand-roll its own
streaming loop (checkpoint save/restore/repair/scrub) or run serially
(store put/get, scheduler drain batches):

    read (thread pool)  →  compute (async device dispatch)  →  consume

The engine is *depth-bounded*: compute for item t+1..t+depth-1 is
dispatched before item t's result is consumed, so at most ``depth``
device results are in flight (depth 2 = classic double buffering;
depth 1 = serial, the benchmark's no-overlap baseline).  Reads prefetch
``depth`` items ahead through the pool, and consume callbacks may
:meth:`Pipeline.submit` host writes onto the same pool — joined, with
errors surfaced, at :meth:`barrier`/exit.

JAX dispatch is asynchronous, so ``compute`` returning a device value
(or a `repro.exec.plan.PlanResult`) costs near-zero wall time; the
blocking materialization happens inside ``consume`` (``.host()`` /
``np.asarray``) — by which point the NEXT item's compute is already
running on the device threads while the pool moves bytes.

Two lifecycles:

* context-managed (checkpointer paths): ``with Pipeline(...) as p:`` —
  exit joins every submitted future and surfaces the first error;
* persistent (the object store keeps one pipeline for its lifetime):
  each :meth:`map`/:meth:`stream_tiles` call barriers its own work, the
  pool thread(s) are reused across calls, :meth:`close` shuts down.
"""
from __future__ import annotations

import threading
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Iterable, Optional, Sequence

from . import staging

DEFAULT_DEPTH = 2


class Pipeline:
    """Depth-bounded read → compute → consume engine with a shared
    host-I/O pool.

    Parameters
    ----------
    io_workers : int
        Pool threads for reads and submitted writes.
    depth : int
        Max device results in flight (1 = serial; 2 = double-buffered).

    Notes
    -----
    A pipeline instance is not re-entrant: one ``map``/``stream_tiles``
    runs at a time (the store and checkpointer each own theirs).
    """

    def __init__(self, *, io_workers: int = 4, depth: int = DEFAULT_DEPTH):
        self.io_workers = max(1, int(io_workers))
        self.depth = max(1, int(depth))
        self._ex: Optional[ThreadPoolExecutor] = None
        self._futs: list[Future] = []
        self._stage_lock = threading.Lock()
        self._stage: dict = {}
        self.reset_stage_stats()

    # ------------------------------------------------------ stage accounting
    def reset_stage_stats(self) -> None:
        """Zero this pipeline's stage timers and rebase the process-wide
        pack/pad clocks (DESIGN.md §16.3)."""
        with self._stage_lock:
            self._stage = {"t_stage_read": 0.0, "t_dispatch": 0.0,
                           "t_consume": 0.0}
            self._stage_base = staging.stage_times()

    def _acct(self, name: str, dt: float) -> None:
        with self._stage_lock:
            self._stage[name] += dt

    def stage_stats(self) -> dict:
        """Cumulative wall seconds per pipeline stage since the last
        :meth:`reset_stage_stats`.

        ``t_stage_read`` / ``t_dispatch`` / ``t_consume`` are timed
        around this pipeline's read/compute/consume callbacks (read time
        is pool-thread time, so at depth >= 2 it largely overlaps the
        other two).  ``t_pack`` (flatten / pack257 staging writes) and
        ``t_pad`` (planner bucket padding) are deltas of the
        process-wide stage clock in `repro.exec.staging` — the staging
        work those callbacks triggered, wherever it ran.
        """
        g = staging.stage_times()
        with self._stage_lock:
            out = dict(self._stage)
            base = self._stage_base
        out["t_pack"] = g.get("pack", 0.0) - base.get("pack", 0.0)
        out["t_pad"] = g.get("pad", 0.0) - base.get("pad", 0.0)
        return out

    # ------------------------------------------------------------ lifecycle
    def _pool(self) -> ThreadPoolExecutor:
        if self._ex is None:
            self._ex = ThreadPoolExecutor(max_workers=self.io_workers)
        return self._ex

    def __enter__(self) -> "Pipeline":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.close()
        else:                      # don't mask the in-flight exception,
            self.close(wait=True, surface=False)   # but never leak threads
        return None

    def close(self, *, wait: bool = True, surface: bool = True) -> None:
        """Join tracked futures (surfacing the first error) and shut the
        pool down; the pipeline may be reused afterwards (a fresh pool
        is created lazily)."""
        try:
            if surface:
                self.barrier()
        finally:
            if self._ex is not None:
                self._ex.shutdown(wait=wait)
                self._ex = None
                self._futs = []

    # ----------------------------------------------------------- host pool
    def submit(self, fn: Callable, *args, **kwargs) -> Future:
        """Schedule a host I/O task (file write, share placement, read)
        on the pool; tracked until the next :meth:`barrier`."""
        fut = self._pool().submit(fn, *args, **kwargs)
        self._futs.append(fut)
        return fut

    def barrier(self) -> None:
        """Wait for every tracked future; re-raise the first failure."""
        futs, self._futs = self._futs, []
        for f in futs:
            f.result()

    # -------------------------------------------------------------- stages
    def stream_tiles(self, s_total: int, tile: int,
                     compute: Callable, consume: Callable) -> None:
        """Depth-bounded tile loop over one stream axis (the engine the
        checkpointer's save/restore/scrub share).

        ``compute(sl)`` dispatches stream slice ``sl`` to the device and
        returns without blocking; ``consume(sl, result)`` lands the
        result host-side.  With depth d, tile t is consumed only after
        tiles t+1..t+d-1 have been dispatched.
        """
        tile = max(1, int(tile))
        self.map([slice(s0, min(s0 + tile, s_total))
                  for s0 in range(0, s_total, tile)], compute, consume)

    def map(self, items: Iterable, compute: Callable, consume: Callable, *,
            read: Optional[Callable] = None) -> None:
        """Run ``items`` through read → compute → consume, depth-bounded.

        Parameters
        ----------
        items : iterable
            Work descriptors, processed (and consumed) in order.
        compute : callable
            ``compute(item)`` — or ``compute(item, read_result)`` when
            ``read`` is given.  Should dispatch asynchronously (device
            work / PlanResult); its return value is handed to consume.
        consume : callable
            ``consume(item, compute_result)`` — the blocking stage; may
            :meth:`submit` further host writes.
        read : callable, optional
            ``read(item)`` runs on the pool, prefetched ``depth`` items
            ahead of compute.
        """
        items = list(items)
        if not items:
            return

        timed_read = None
        if read is not None:
            def timed_read(it):
                with staging.staged("pipe.read") as span:
                    data = read(it)
                self._acct("t_stage_read", span.seconds)
                return data

        # depth 1 is the true serial baseline: no prefetch, reads run
        # inline — stage overlap exists only at depth >= 2
        ahead = self.depth if self.depth > 1 else 0
        read_futs: dict[int, Future] = {}
        if read is not None:
            for j in range(min(ahead, len(items))):
                read_futs[j] = self._pool().submit(timed_read, items[j])

        def _consume(it0, out0):
            with staging.staged("pipe.consume") as span:
                consume(it0, out0)
            self._acct("t_consume", span.seconds)

        pending: deque = deque()
        try:
            for i, item in enumerate(items):
                if read is not None:
                    if i in read_futs:
                        data = read_futs.pop(i).result()
                    else:
                        data = timed_read(items[i])
                    nxt = i + ahead
                    if ahead and nxt < len(items):
                        read_futs[nxt] = self._pool().submit(
                            timed_read, items[nxt])
                with staging.staged("pipe.dispatch") as span:
                    out = compute(item) if read is None \
                        else compute(item, data)
                self._acct("t_dispatch", span.seconds)
                pending.append((item, out))
                while len(pending) >= self.depth:
                    it0, out0 = pending.popleft()
                    _consume(it0, out0)
            while pending:
                it0, out0 = pending.popleft()
                _consume(it0, out0)
        finally:
            for f in read_futs.values():     # error path: drain prefetches
                f.cancel()
        self.barrier()


__all__ = ["Pipeline", "DEFAULT_DEPTH"]
