"""The one general traffic generator: it reads a mix file and runs it.

A mix (``chipbench/traffic/<mix>.json``) names a ``loop`` and its
parameters.  One loop exists so far, ``checkpoint``: a training job's
checkpoint shard, held on the device, saved or restored back to back
through ``MSRCheckpointer`` into node files on the local disk.

A loop builds its data from the run's seed, warms up in ``setup``, runs
``window`` for a number of seconds, and afterwards ``check``s what the
window produced against the plain reference in ``reference.py``.  A new
mix of an existing loop is a data file and nothing else.

Every statistic covers all of the window's work: an operation begun
inside the window runs to its end, and a rate divides the bytes of all of
them by the time from the window's start to the end of the last.
"""
from __future__ import annotations

import dataclasses
import math
import pathlib
import shutil
import tempfile
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from typing import Callable

import numpy as np

from chipbench import reference, work


@dataclasses.dataclass
class Op:
    """One operation of the window; times in seconds from its start."""
    kind: str
    due: float
    start: float
    end: float              # math.inf when it failed or was refused
    nbytes: int

    @property
    def ok(self) -> bool:
        return math.isfinite(self.end)


@dataclasses.dataclass
class Check:
    """The numbers compared, each with its limit: correct when every
    value is at most its limit."""
    values: dict = dataclasses.field(default_factory=dict)

    def add(self, name: str, value: float, limit: float = 0) -> None:
        self.values[name] = (float(value), float(limit))

    def bump(self, name: str, value: float, limit: float = 0) -> None:
        old = self.values.get(name, (0.0, limit))[0]
        self.values[name] = (old + float(value), float(limit))

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.values.values())


def _device_key(rng: np.random.Generator):
    import jax
    return jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1)))


class Loop:
    """Base of the loops.  ``span(name)`` brackets the benchmark's own
    calls into the program, and ``phase(name)`` the parts of set-up."""

    def __init__(self, config: dict, mix: dict, seed: int,
                 span: Callable, phase: Callable):
        self.config, self.mix, self.seed = config, mix, int(seed)
        self.rng = np.random.default_rng(self.seed)
        self.span, self.phase = span, phase
        self.ops: list[Op] = []
        self.gf_bytes = 0.0         # needed GF bytes of the window's work
        self.facts: dict = {}       # counts for the run's earlier lines
        self.raised = 0             # operations of the window that raised
        code = config["code"]
        self.n, self.k, self.p = code["n"], code["k"], code["p"]

    def spec(self):
        from repro.core.circulant import CodeSpec
        return CodeSpec.make(self.k, self.p)

    def setup(self, seconds: float) -> None:
        """Build the data and warm up for a window of ``seconds``."""
        raise NotImplementedError

    def window(self, seconds: float) -> float:
        """Run the window; returns its start on the ``perf_counter``
        clock.  Operation times in ``self.ops`` are relative to it."""
        raise NotImplementedError

    def free(self) -> None:
        """Drop the program's state before the reference runs."""

    def check(self) -> Check:
        raise NotImplementedError

    def close(self) -> None:
        pass

    def attempt(self, kind: str, due: float, t0: float, nbytes: int,
                fn: Callable) -> bool:
        """Run one operation of the window and record it.  One that
        raises is recorded as failed, with its traceback on standard
        error, and counted by ``check``: the state it left is what the
        check then reads."""
        start = time.perf_counter() - t0
        try:
            fn()
        except Exception:
            traceback.print_exc()
            self.raised += 1
            self.ops.append(Op(kind, due, start, math.inf, nbytes))
            return False
        self.ops.append(Op(kind, due, start, time.perf_counter() - t0, nbytes))
        return True

    def new_check(self) -> Check:
        chk = Check()
        chk.add("raised_ops", self.raised)
        return chk

    @property
    def work_bytes(self) -> int:
        return sum(op.nbytes for op in self.ops if op.ok)


# ------------------------------------------------------------ checkpoint
def _nest(tree: dict, path: str, value) -> None:
    *parents, leaf = path.split("/")
    for name in parents:
        tree = tree.setdefault(name, {})
    tree[leaf] = value


def make_state(leaves: list[dict], key):
    """The checkpoint shard, made on the device in one jitted call: each
    leaf filled as its ``fill`` says, in its own dtype."""
    import jax
    import jax.numpy as jnp

    def bench_make_state(key):
        tree: dict = {}
        for leaf, k in zip(leaves, jax.random.split(key, len(leaves))):
            shape, dtype = tuple(leaf["shape"]), jnp.dtype(leaf["dtype"])
            how, scale = leaf["fill"]
            if how == "const":
                val = jnp.full(shape, scale, dtype)
            else:
                val = jax.random.normal(k, shape, jnp.float32) * scale
                val = (val * val if how == "normal_sq" else val).astype(dtype)
            _nest(tree, leaf["path"], val)
        return tree

    return jax.jit(bench_make_state)(key)


def advance_fn():
    """Stand-in for a training step between saves, as one jitted call:
    every float scaled by 1 - 2**-10, every integer raised by one."""
    import jax
    import jax.numpy as jnp

    def step(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x * jnp.asarray(1 - 2.0 ** -10, x.dtype)
        return x + 1

    def bench_advance(tree):
        return jax.tree_util.tree_map(step, tree)

    return jax.jit(bench_advance)


def state_bytes(state) -> np.ndarray:
    """The state's bytes, leaves in pytree order, as one uint8 array."""
    import jax
    return np.concatenate([np.asarray(x).reshape(-1).view(np.uint8)
                           for x in jax.tree_util.tree_leaves(state)])


def _disk(path: pathlib.Path) -> dict:
    """The filesystem that holds ``path``: its type and its free bytes."""
    best, fstype = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            _dev, mnt, typ, *_ = line.split()
            inside = str(path) == mnt or str(path).startswith(
                mnt.rstrip("/") + "/")
            if inside and len(mnt) > len(best):
                best, fstype = mnt, typ
    return {"mount": best, "fstype": fstype,
            "free_bytes": shutil.disk_usage(path).free}


class CheckpointLoop(Loop):
    """Saves (``op: save``) or restores after node loss (``op: restore``)
    of a device-resident shard, back to back.

    The node files go to a new directory under ``$TMPDIR`` through the
    local blob backend, flushed as the configuration's ``writer`` says;
    ``close`` removes it.  A restore first deletes the files of the mix's
    next failed node set, then restores with those nodes failed, which
    rebuilds and rewrites them; ``expect_path`` names the restore path
    the mix exercises."""

    def setup(self, seconds: float) -> None:
        import jax
        from repro.checkpoint.msr_checkpoint import MSRCheckpointer
        from repro.io.blob import LocalBlob

        with self.phase("state"):
            self.state = make_state(self.config["leaves"],
                                    _device_key(self.rng))
            jax.block_until_ready(self.state)
        self.nbytes = int(sum(x.nbytes
                              for x in jax.tree_util.tree_leaves(self.state)))
        writer = self.config["writer"]
        self.root = pathlib.Path(tempfile.mkdtemp(prefix="chipbench_ckpt_"))
        self.ckpt = MSRCheckpointer(self.root, self.spec(),
                                    io_backend=LocalBlob(fsync=writer["fsync"]),
                                    keep_last=writer["keep_last"])
        self.s = -(-self.nbytes // self.n)
        self.saved: dict[int, object] = {}      # step -> device state
        self.advance = advance_fn()
        with self.phase("warmup"):
            with self.span("save"):
                self.ckpt.save(0, self.state)
            self.saved[0] = self.state
            if self.mix["op"] == "save":
                jax.block_until_ready(self.advance(self.state))
            else:
                self._restore(0, self.mix["failed_nodes"][-1])

    def _files(self, step: int, i: int) -> tuple:
        """Node i's data and redundancy files at ``step``, as the
        checkpoint's on-disk layout names them."""
        d = self.root / f"step_{step:06d}"
        return d / f"node_{i:02d}.a.npy", d / f"node_{i:02d}.r.npz"

    def _kill(self, step: int, nodes) -> None:
        for i in nodes:
            for path in self._files(step, i):
                path.unlink()

    def _restore(self, step: int, nodes):
        self._kill(step, nodes)
        with self.span("restore"):
            return self.ckpt.restore(self.state, step, failed_nodes=nodes)

    def _save(self, step: int) -> None:
        import jax
        with self.span("advance"):
            self.state = self.advance(self.state)
            jax.block_until_ready(self.state)
        with self.span("save"):
            self.ckpt.save(step, self.state)
        self.saved[step] = self.state
        for old in sorted(self.saved)[:-self.ckpt.keep_last]:
            del self.saved[old]
        self.gf_bytes += work.encode(self.n, self.s)

    def _restore_next(self, i: int) -> None:
        nodes = self.mix["failed_nodes"][(i - 1) % len(self.mix["failed_nodes"])]
        tree, report = self._restore(0, nodes)
        self.restored.append((tree, report.path))
        if report.path == "regenerate":
            self.gf_bytes += work.regenerate(self.k, self.s)

    def window(self, seconds: float) -> float:
        one = self._save if self.mix["op"] == "save" else self._restore_next
        self.restored: list = []
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            i += 1
            start = time.perf_counter() - t0
            if not self.attempt(self.mix["op"], start, t0, self.nbytes,
                                lambda: one(i)):
                break
        self.facts.update(ops=len(self.ops),
                          op_s=[op.end - op.start for op in self.ops],
                          state_bytes=self.nbytes,
                          block_symbols=self.s,
                          steps_kept=self.ckpt.steps(),
                          ckpt_dir_disk=_disk(self.root))
        return t0

    def free(self) -> None:
        self.ckpt.close()

    def close(self) -> None:
        if hasattr(self, "root"):
            shutil.rmtree(self.root, ignore_errors=True)

    # ..................................................................
    def _node_arrays(self, step: int):
        """Per node: (data bytes, low bytes, positions of 256), read back
        from the committed files; None for a file that is not there."""
        out = []
        for i in range(1, self.n + 1):
            a_path, r_path = self._files(step, i)
            try:
                a = np.load(a_path)
                with np.load(r_path) as z:
                    low, hi = z["low"], z["hi"]
            except FileNotFoundError:
                out.append(None)
                continue
            out.append((a.reshape(-1), low.reshape(-1),
                        np.asarray(hi, np.int64).reshape(-1)))
        return out

    def _check_files(self, chk: Check, step: int, payload: np.ndarray,
                     chunk: int = 1 << 21) -> None:
        """The step's node files against the reference: every data file
        against the state's bytes, every redundancy file against the
        reference encode, and the any-k decode on three column windows."""
        c = self.spec().c
        blocks = reference.bytes_to_blocks(payload, self.n)
        nodes = self._node_arrays(step)
        chk.bump("missing_node_files", sum(x is None for x in nodes))
        if any(x is None for x in nodes):
            return
        s = blocks.shape[1]

        def chunk_bad(lo: int) -> tuple[int, int]:
            hi_ = min(s, lo + chunk)
            a = blocks[:, lo:hi_]
            r_ref = reference.encode(c, a, self.p)
            bad_a = bad_r = 0
            for i, (fa, low, pos) in enumerate(nodes):
                if fa.size != s or low.size != s:
                    bad_a += hi_ - lo
                    continue
                bad_a += int(np.count_nonzero(fa[lo:hi_] != a[i]))
                r = low[lo:hi_].astype(np.int32)
                r[pos[(pos >= lo) & (pos < hi_)] - lo] = 256
                bad_r += int(np.count_nonzero(r != r_ref[i]))
            return bad_a, bad_r

        # NumPy releases the GIL in these array passes: chunks in parallel
        with ThreadPoolExecutor(max_workers=8) as pool:
            counts = list(pool.map(chunk_bad, range(0, s, chunk)))
        bad_a = sum(a for a, _ in counts)
        bad_r = sum(r for _, r in counts)
        chk.bump("data_mismatch_bytes", bad_a)
        chk.bump("redundancy_mismatch_symbols", bad_r)
        w = min(4096, s)
        bad_d = 0
        for lo in sorted({0, (s - w) // 2, s - w}):
            a = np.stack([x[0][lo:lo + w] for x in nodes]).astype(np.int64)
            r = np.stack([reference.unpack_red(
                x[1][lo:lo + w], x[2][(x[2] >= lo) & (x[2] < lo + w)] - lo)
                for x in nodes])
            bad_d += reference.stripe_mismatches(c, a, r, self.p, self.rng)
        chk.bump("anyk_decode_mismatch_symbols", bad_d)

    def check(self) -> Check:
        chk = self.new_check()
        chk.add("missing_node_files", 0)
        if self.mix["op"] == "save":
            done = [i for i, op in enumerate(self.ops, 1) if op.ok]
            kept = done[-self.ckpt.keep_last:]
            chk.add("unkept_steps", len(set(kept) - set(self.ckpt.steps())))
            for step in kept:
                if step in self.ckpt.steps() and step in self.saved:
                    self._check_files(chk, step, state_bytes(self.saved[step]))
            return chk
        want = state_bytes(self.state)
        bad = wrong_path = 0
        for tree, path in self.restored:
            got = state_bytes(tree)
            bad += want.size if got.size != want.size \
                else int(np.count_nonzero(got != want))
            wrong_path += path != self.mix["expect_path"]
        chk.add("restored_mismatch_bytes", bad)
        chk.add("wrong_restore_path", wrong_path)
        self._check_files(chk, 0, want)
        return chk


LOOPS = {"checkpoint": CheckpointLoop}


@contextmanager
def _nothing(_name: str):
    yield


def make_loop(config: dict, mix: dict, seed: int,
              span: Callable = _nothing,
              phase: Callable = _nothing) -> Loop:
    try:
        cls = LOOPS[mix["loop"]]
    except KeyError:
        raise ValueError(f"unknown loop {mix.get('loop')!r}; "
                         f"known: {sorted(LOOPS)}") from None
    return cls(config, mix, seed, span, phase)


__all__ = ["Op", "Check", "Loop", "make_loop", "make_state", "state_bytes",
           "LOOPS"]
