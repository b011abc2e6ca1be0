"""Gradient compression: int8 error-feedback quantization with a ring
reduce-scatter/all-gather over the data axis (shard_map).

Wire cost per gradient sync drops 4x (f32 -> int8 + one f32 scale per
tensor); the quantization error is carried in an error-feedback accumulator
so the *expected* update is unbiased (1-bit Adam / EF-SGD lineage).

Usage (train loop, optional):
    comp = Int8ErrorFeedback(params)
    grads, comp_state = comp.compress_sync(grads, comp_state, mesh, axis="data")
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def init_error_state(params: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def quantize(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-tensor int8: returns (q, scale)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def ef_compress(g: jnp.ndarray, err: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Error-feedback step: quantize (g + err), carry the residual."""
    target = g.astype(jnp.float32) + err
    q, scale = quantize(target)
    new_err = target - dequantize(q, scale)
    return q, scale, new_err


def compress_tree(grads: Any, err_state: Any) -> tuple[Any, Any, Any]:
    flat_g, tdef = jax.tree_util.tree_flatten(grads)
    flat_e = tdef.flatten_up_to(err_state)
    qs, scales, errs = [], [], []
    for g, e in zip(flat_g, flat_e):
        q, s, ne = ef_compress(g, e)
        qs.append(q)
        scales.append(s)
        errs.append(ne)
    return (tdef.unflatten(qs), tdef.unflatten(scales), tdef.unflatten(errs))


def decompress_tree(qs: Any, scales: Any) -> Any:
    return jax.tree_util.tree_map(dequantize, qs, scales)


# -------------------------------------------------- int8 ring mean (shard_map)
def int8_ring_mean(x: jnp.ndarray, mesh: Mesh, axis: str) -> jnp.ndarray:
    """Mean of per-device gradients with int8 on the wire.

    x: (n, ...) — row i is device i's local gradient (sharded over `axis`).
    Ring reduce-scatter in int8 (each hop re-quantizes its partial sum — the
    standard ring-compression compromise) + int8 all-gather of the finished
    chunks.  Wire bytes: 2 * |x| * 1B vs 2 * |x| * 4B uncompressed.
    Returns (n, ...) with every row = the mean.

    Ring algebra: acc_i^(0) = x_i[chunk i]; each hop sends acc rightward and
    adds the receiver's own chunk (idx - t - 1); after n-1 hops device i holds
    the FULL sum of chunk (i+1) mod n, so gathered chunk c sits at device
    (c - 1) mod n.
    """
    n = mesh.shape[axis]
    if x.shape[0] != n:
        raise ValueError(f"leading dim {x.shape[0]} != axis {axis}={n}")

    def body(xl):
        xi = jnp.reshape(xl[0], (-1,))
        pad = (-xi.size) % n
        xi = jnp.pad(xi, (0, pad))
        chunks = xi.reshape(n, -1)
        idx = jax.lax.axis_index(axis)
        perm = [(j, (j + 1) % n) for j in range(n)]

        def hop(t, acc):
            q, s = quantize(acc)
            q = jax.lax.ppermute(q, axis, perm)
            s = jax.lax.ppermute(s, axis, perm)
            return dequantize(q, s) + chunks[jnp.mod(idx - t - 1, n)]

        acc = chunks[idx]
        if n > 1:
            acc = jax.lax.fori_loop(0, n - 1, hop, acc)
        own = acc / n                           # full mean of chunk (idx+1)%n
        q, s = quantize(own)
        qg = jax.lax.all_gather(q, axis)        # (n, chunk)
        sg = jax.lax.all_gather(s, axis)        # (n,)
        full = dequantize(qg, sg[:, None])
        order = jnp.mod(jnp.arange(n) - 1, n)   # chunk c at device (c-1)%n
        flat = jnp.reshape(full[order], (-1,))
        return jnp.reshape(flat[: xl[0].size], xl.shape)

    fn = shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    return fn(x)
