"""Pallas TPU kernel: GF(p) matrix multiply  (A @ B) mod p.

The encode/reconstruct hot path of the MSR layer: A is the tiny (<= 512 wide)
code matrix (M^T, a solve inverse, or a coefficient row), B is the symbol
stream — gigabytes of checkpoint state cut into (k, S) blocks.

TPU-native trick (DESIGN.md §2): for p = 257, symbols 0..256 are exact in
bf16 and a <=128-term dot stays < 2^24, exact in the MXU's fp32 accumulator.
The kernel therefore:
  * tiles BOTH the output-row axis and the stream axis through VMEM
    ((BM, k) x (k, BS) per grid step), so n = 512 reconstructs stay inside
    the ~16 MB VMEM budget instead of holding a (512, BS) fp32 tile set,
  * contracts on the MXU via jnp.dot(..., preferred_element_type=f32),
  * accumulates fp32 chunk partials (< 2^24 each) LAZILY in int32: the VPU
    folds `mod p` only every 127 chunks — up to ~127x fewer folds than the
    eager per-chunk schedule (DESIGN.md §3.2),
emitting exact int32 symbols.  The fp32 chunk depth adapts as
(2^24-1)/(p-1)^2 (255 for p = 257, clamped to the MXU-friendly 128); p with
(p-1)^2 > 2^24-1 (p > 4097) is REJECTED — a single product already rounds
in fp32, so no MXU schedule is exact and dispatch routes such p to the
integer-lane backends instead.

Validated on CPU via interpret=True against ref.gf_matmul_ref; dispatched as
the `pallas` / `pallas-interpret` backends (repro.kernels.dispatch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .envelope import LAZY_F32_CHUNKS as LAZY_CHUNKS
from .envelope import MXU_FOLD_CAP as FOLD
from .envelope import f32_exact_terms


def _fold_depth(p: int) -> int:
    """Largest chunk depth whose worst-case partial dot stays < 2^24.

    Raises for p outside the fp32 envelope: when (p-1)^2 > 2^24-1 even a
    single product rounds, so this kernel cannot be exact at all."""
    d = f32_exact_terms(p)
    if d < 1:
        raise ValueError(f"(p-1)^2 > 2^24-1: no exact fp32 MXU schedule for "
                         f"p={p}; use the jnp-int32 dispatch backend")
    return min(FOLD, d)


def _gf_matmul_kernel(a_ref, b_ref, o_ref, *, p: int):
    """One grid step: o[BM, BS] = (a[BM, k] @ b[k, BS]) mod p, exact."""
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    k = a.shape[1]
    depth = _fold_depth(p)
    acc = jnp.zeros((a.shape[0], b.shape[1]), jnp.int32)
    pending = 0
    # static unroll over fold chunks: k is small (code dimension n <= 512)
    for s in range(0, k, depth):
        prod = jnp.dot(a[:, s:s + depth], b[s:s + depth, :],
                       preferred_element_type=jnp.float32)
        acc = acc + prod.astype(jnp.int32)    # lazy: partial < 2^24, no fold
        pending += 1
        if pending == LAZY_CHUNKS:            # int32 headroom exhausted
            acc = acc % p
            pending = 0
    o_ref[...] = acc % p


@functools.partial(jax.jit,
                   static_argnames=("p", "block_m", "block_s", "interpret"))
def gf_matmul(a: jnp.ndarray, b: jnp.ndarray, p: int = 257, *,
              block_m: int = 128, block_s: int = 512,
              interpret: bool = True) -> jnp.ndarray:
    """(a @ b) mod p via Pallas.  a: (m, k) int32, b: (k, s) int32.

    2-D grid: output rows tiled by block_m, the symbol stream axis by
    block_s (zero padding is mod-p neutral under matmul).
    """
    a = jnp.asarray(a, jnp.int32) % p
    b = jnp.asarray(b, jnp.int32) % p
    m, k = a.shape
    k2, s = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    block_m = min(block_m, m) or 1
    pad_m = (-m) % block_m
    if pad_m:
        a = jnp.pad(a, ((0, pad_m), (0, 0)))
    pad_s = (-s) % block_s
    if pad_s:
        b = jnp.pad(b, ((0, 0), (0, pad_s)))
    m_pad, s_pad = m + pad_m, s + pad_s
    grid = (m_pad // block_m, s_pad // block_s)
    out = pl.pallas_call(
        functools.partial(_gf_matmul_kernel, p=p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),  # code-matrix rows
            pl.BlockSpec((k, block_s), lambda i, j: (0, j)),  # stream tile
        ],
        out_specs=pl.BlockSpec((block_m, block_s), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, s_pad), jnp.int32),
        interpret=interpret,
        name="gf_matmul",
    )(a, b)
    return out[:m, :s]
