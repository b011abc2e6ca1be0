"""Stream-axis device mesh + declarative sharding rules (DESIGN.md §14).

Every hot-path GF op — circulant encode, the decode-side matmul, fused
regenerate, batched regenerate — has one large *stream* axis (symbol
columns) and the paper's double-circulant structure makes every op
column-local over it: shard the stream, replicate the tiny static
operands (coefficient vectors, repair/decode matrices), and each device
computes its slice with ZERO cross-device GF arithmetic.  The mesh
layer states that once, declaratively:

* :class:`StreamMesh` — a validated 1-D ``jax.sharding.Mesh`` over the
  ``"stream"`` axis (typed :class:`MeshConfigError` on bad sizes or
  device-count mismatches);
* :class:`ShardingRule` + :func:`register_rule` / :func:`get_rule` — a
  registry mapping op name -> per-operand ``PartitionSpec``s, in the
  declarative spirit of scalax's ``MeshShardingHelper``: the exec
  planner looks the rule up by op name instead of hand-writing specs at
  every call site;
* :func:`shard_body` — wraps a dispatch-layer kernel in
  ``jax.shard_map`` under the rule's specs (``check_vma=False``: the
  bodies are pure per-shard maps, there is no replication to verify);
* :func:`use_mesh` / :func:`current_mesh` — ambient-mesh context so
  stores / checkpointers / codes built inside a ``use_mesh(...)`` block
  inherit the mesh without threading a kwarg through every layer.

CPU multi-device testing recipe (DESIGN.md §14.4): set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* the
first jax import and any ``StreamMesh(m)`` with ``m <= N`` works on a
plain CPU host — the parity harness in ``tests/test_sharding.py`` and
``benchmarks/bench_shard.py`` both run that way.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Callable, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

STREAM_AXIS = "stream"


class MeshConfigError(ValueError):
    """Invalid mesh construction: non-integer / non-positive axis size,
    or more shards requested than devices exist."""


class StreamMesh:
    """A validated 1-D device mesh over the ``"stream"`` axis.

    Parameters
    ----------
    n_shards : int, optional
        Mesh size (devices along the stream axis).  ``None`` uses every
        available device.
    devices : sequence of jax devices, optional
        Device pool to draw from (default ``jax.devices()``); the mesh
        takes the first ``n_shards`` of them.

    Raises
    ------
    MeshConfigError
        If ``n_shards`` is not a positive integer or exceeds the number
        of available devices.
    """

    def __init__(self, n_shards: int | None = None, *, devices=None):
        pool = list(jax.devices() if devices is None else devices)
        if n_shards is None:
            n_shards = len(pool)
        if isinstance(n_shards, bool) or not isinstance(n_shards, int):
            raise MeshConfigError(
                f"mesh axis '{STREAM_AXIS}' size must be an int, got "
                f"{n_shards!r} ({type(n_shards).__name__})")
        if n_shards < 1:
            raise MeshConfigError(
                f"mesh axis '{STREAM_AXIS}' size must be >= 1, got "
                f"{n_shards}")
        if n_shards > len(pool):
            raise MeshConfigError(
                f"mesh axis '{STREAM_AXIS}' wants {n_shards} devices but "
                f"only {len(pool)} are available; on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n_shards} "
                f"BEFORE the first jax import")
        self.size = n_shards
        self.devices = tuple(pool[:n_shards])
        self.mesh = Mesh(np.array(self.devices), (STREAM_AXIS,))

    # ------------------------------------------------------------- identity
    @property
    def is_trivial(self) -> bool:
        """1-device meshes carry no sharding — callers fall back to the
        plain dispatch path (satellite: REPRO_GF_BACKEND x device-count
        interaction stays recompile-free)."""
        return self.size == 1

    def key(self) -> tuple:
        """Registry identity: two StreamMesh objects over the same
        devices share planners (and therefore AOT executables)."""
        return (STREAM_AXIS, tuple(d.id for d in self.devices))

    # ------------------------------------------------------------ shardings
    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def shardings(self, specs) -> tuple:
        return tuple(self.sharding(s) for s in specs)

    def shard_extent(self, s: int) -> int:
        """Per-shard stream extent before bucketing: ceil(s / size)."""
        return -(-int(s) // self.size)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"StreamMesh(size={self.size})"


MeshLike = Union[StreamMesh, int, None]


def as_stream_mesh(mesh: MeshLike) -> StreamMesh | None:
    """Coerce user input: None passes through, an int builds a
    StreamMesh of that size, anything else must already be one."""
    if mesh is None or isinstance(mesh, StreamMesh):
        return mesh
    if isinstance(mesh, bool):
        raise MeshConfigError(f"mesh must be a StreamMesh, int or None, "
                              f"got {mesh!r}")
    if isinstance(mesh, int):
        return StreamMesh(mesh)
    raise MeshConfigError(f"mesh must be a StreamMesh, int or None, got "
                          f"{type(mesh).__name__}")


# ------------------------------------------------------------ rule registry
@dataclasses.dataclass(frozen=True)
class ShardingRule:
    """Declarative per-op layout: how each operand and the output split
    over the stream axis.  ``in_specs[i]`` matches positional operand i
    of the planned op; replicated operands use ``P()``."""
    op: str
    in_specs: tuple
    out_specs: P
    doc: str = ""


_RULES: dict[str, ShardingRule] = {}


def register_rule(rule: ShardingRule, *, override: bool = False) -> None:
    if rule.op in _RULES and not override:
        raise ValueError(f"sharding rule for op {rule.op!r} already "
                         f"registered (pass override=True to replace)")
    _RULES[rule.op] = rule


def get_rule(op: str) -> ShardingRule:
    try:
        return _RULES[op]
    except KeyError:
        raise KeyError(f"no sharding rule registered for op {op!r}; "
                       f"known ops: {sorted(_RULES)}") from None


def known_rules() -> tuple[str, ...]:
    return tuple(sorted(_RULES))


# The four planned GF ops (exec/plan.py).  All are column-local over the
# stream (last) axis, so the rules are pure data-parallel splits:
# zero collectives appear in the lowered HLO (asserted by the parity
# harness via steady-state compile counts + bit-exactness).
register_rule(ShardingRule(
    "matmul",
    in_specs=(P(), P(None, STREAM_AXIS)),
    out_specs=P(None, STREAM_AXIS),
    doc="decode-side (mat @ blocks) mod p: small mat replicated, the "
        "(rows, S) block operand and product split over S"))
register_rule(ShardingRule(
    "circulant_encode",
    in_specs=(P(None, STREAM_AXIS),),
    out_specs=P(None, STREAM_AXIS),
    doc="eq. (2) encode: (n, S) data split over S; coefficients are "
        "static in the kernel"))
register_rule(ShardingRule(
    "regenerate",
    in_specs=(P(), P(STREAM_AXIS), P(None, STREAM_AXIS)),
    out_specs=P(None, STREAM_AXIS),
    doc="fused newcomer kernel: (2, k+1) repair matrix replicated, "
        "r_prev (S,) and helper data (k, S) split over S"))
register_rule(ShardingRule(
    "regenerate_batch",
    in_specs=(P(), P(None, STREAM_AXIS), P(None, None, STREAM_AXIS)),
    out_specs=P(None, None, STREAM_AXIS),
    doc="vmapped fused regeneration: batch (F) axis replicated per "
        "device, stream split over S"))
register_rule(ShardingRule(
    "matmul_batch",
    in_specs=(P(), P(None, None, STREAM_AXIS)),
    out_specs=P(None, None, STREAM_AXIS),
    doc="per-element batched matmul (product-matrix batched regen, "
        "DESIGN.md §16.5): the (F, q, d) matrix stack is replicated, "
        "the (F, d, S) sends and (F, q, S) product split over S"))


def shard_body(fn: Callable, op: str, mesh: StreamMesh) -> Callable:
    """Wrap a dispatch-layer kernel body in ``shard_map`` under the
    registered rule for ``op``.  ``check_vma=False``: the bodies are
    per-shard maps with no collectives, so there is no replication
    invariant to verify (and skipping the check keeps tracing cheap)."""
    rule = get_rule(op)
    return _shard_map(fn, mesh=mesh.mesh, in_specs=rule.in_specs,
                      out_specs=rule.out_specs, check_vma=False)


# ------------------------------------------------------------ ambient mesh
_ACTIVE: contextvars.ContextVar[StreamMesh | None] = \
    contextvars.ContextVar("stream_mesh", default=None)


@contextlib.contextmanager
def use_mesh(mesh: MeshLike):
    """Ambient-mesh scope: codes / stores / checkpointers constructed
    inside inherit ``mesh`` (coerced via :func:`as_stream_mesh`)
    without explicit kwargs.  ``use_mesh(None)`` explicitly disables an
    outer ambient mesh for the scope."""
    token = _ACTIVE.set(as_stream_mesh(mesh))
    try:
        yield
    finally:
        _ACTIVE.reset(token)


def current_mesh() -> StreamMesh | None:
    return _ACTIVE.get()


__all__ = [
    "STREAM_AXIS", "MeshConfigError", "StreamMesh", "as_stream_mesh",
    "ShardingRule", "register_rule", "get_rule", "known_rules",
    "shard_body", "use_mesh", "current_mesh",
]
