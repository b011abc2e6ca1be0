"""The one general traffic generator: it reads a mix file and runs it.

A mix (``chipbench/traffic/<mix>.json``) names a ``loop`` and its
parameters.  A loop is a file of its own, ``chipbench/loops/<loop>.py``,
whose ``LOOP`` class runs every mix that names it: ``make_loop`` finds
it by that name, so a new deployment with traffic of its own is new
files and no edit.  ``checkpoint`` is a training job's checkpoint shard,
held on the device, saved or restored back to back through
``MSRCheckpointer`` into node files on the local disk.

A loop builds its data from the run's seed, warms up in ``setup``, runs
``window`` for a number of seconds, and afterwards ``check``s what the
window produced against the plain reference in ``reference.py``.  A new
mix of an existing loop is a data file and nothing else.

A configuration's ``host_chips`` (default 1) is the number of chips of
one host that hold its state: each leaf's ``shape`` is then the host's
share, laid out along its ``shard_axis`` over those chips.

Every statistic covers all of the window's work: an operation begun
inside the window runs to its end, and a rate divides the bytes of all of
them by the time from the window's start to the end of the last.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
import pathlib
import time
import traceback
from contextlib import contextmanager
from typing import Callable

import numpy as np

from chipbench.harness import HERE, BenchError


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
        self.host_chips = int(config.get("host_chips", 1))
        code = config["code"]
        self.n, self.k, self.p = code["n"], code["k"], code["p"]

    def device_key(self):
        """A device PRNG key, drawn from the run's seed."""
        import jax
        return jax.random.PRNGKey(int(self.rng.integers(0, 2**31 - 1)))

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


# ------------------------------------------------------------------ state
def _nest(tree: dict, path: str, value) -> None:
    *parents, leaf = path.split("/")
    for name in parents:
        tree = tree.setdefault(name, {})
    tree[leaf] = value


def _leaf_shardings(leaves: list[dict], host_chips: int):
    """The state's layout over a 1-D mesh of the first ``host_chips``
    devices: a leaf with a ``shard_axis`` split along it, the others
    replicated."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:host_chips]), ("host",))
    tree: dict = {}
    for leaf in leaves:
        spec = [None] * len(leaf["shape"])
        if leaf["shard_axis"] is not None:
            spec[leaf["shard_axis"]] = "host"
        _nest(tree, leaf["path"], NamedSharding(mesh, PartitionSpec(*spec)))
    return tree


def make_state(leaves: list[dict], key, host_chips: int = 1):
    """The checkpoint shard, made on the device in one jitted call: each
    leaf filled as its ``fill`` says, in its own dtype.  With
    ``host_chips`` > 1 the jit lays the leaves out over those chips
    (``_leaf_shardings``)."""
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

    if host_chips == 1:
        return jax.jit(bench_make_state)(key)
    return jax.jit(bench_make_state,
                   out_shardings=_leaf_shardings(leaves, host_chips))(key)


def advance_fn():
    """Stand-in for a training step between saves, as one jitted call:
    every float scaled by 1 - 2**-10, every integer raised by one.  Each
    leaf keeps the layout it came with."""
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


@contextmanager
def _nothing(_name: str):
    yield


def loop_path(name: str, base: pathlib.Path = HERE) -> pathlib.Path:
    """The file of the loop named ``name``: ``loops/<name>.py``."""
    path = base / "loops" / f"{name}.py"
    if "/" in name or not path.is_file():
        raise BenchError(f"no loop {name!r}: {path} is missing")
    return path


def make_loop(config: dict, mix: dict, seed: int,
              span: Callable = _nothing,
              phase: Callable = _nothing,
              base: pathlib.Path = HERE) -> Loop:
    """The ``LOOP`` of the mix's loop file, built for this run."""
    path = loop_path(str(mix.get("loop")), base)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_loop_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.LOOP(config, mix, seed, span, phase)


__all__ = ["Op", "Check", "Loop", "make_loop", "make_state", "advance_fn",
           "state_bytes", "loop_path"]
