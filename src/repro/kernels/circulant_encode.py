"""Pallas TPU kernel: double circulant MSR encode (paper eq. (2)).

Computes the n redundancy blocks  r[i] = sum_{u=1..k} c_u * a[(i-k-u) mod n]
without materializing the n x n matrix M: the circulant structure is realized
as k static *rolls* of the resident data tile — each roll lowers to two
contiguous VMEM slices (no gathers), and the coefficients are baked into the
kernel as compile-time constants (the paper's *embedded property*: the code
is precalculated, so the kernel is specialized per CodeSpec).

Arithmetic-intensity note: dense (M^T @ a) does n MACs per output symbol;
this kernel does k = n/2 — half the work and half the VMEM traffic for the
same result, which is exactly the structural win the paper's construction
buys over a generic MDS encode.

Exactness (lazy folding, DESIGN.md §3.2): every accumulated term is
c_u * a_j <= (p-1)^2, so int32 holds ~(2^31-1)/(p-1)^2 terms — 32767 for
p = 257 (envelope.int32_lazy_terms) — before a `mod p` fold is due.  The old schedule folded every
128 terms (the fp32 dot envelope), which this elementwise accumulation
never needed; for realistic k the kernel now folds exactly once.
Validated on CPU via interpret=True against ref.circulant_encode_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .envelope import int32_lazy_terms, require_int32_envelope


def _circulant_encode_kernel(a_ref, o_ref, *, c: tuple[int, ...], p: int):
    k = len(c)
    n = 2 * k
    a = a_ref[...]                                    # (n, BS) int32
    chunk = int32_lazy_terms(p)
    acc = jnp.zeros_like(a)
    pending = 0
    for u in range(1, k + 1):
        # output row j holds r_{j+1} (1-indexed nodes):
        # roll(a, k+u-1)[j] = a[(j+1 - k - u) mod n]  — static shift: two slices
        shift = (k + u - 1) % n
        rolled = jnp.concatenate([a[n - shift:], a[:n - shift]], axis=0) if shift else a
        acc = acc + c[u - 1] * rolled
        pending += 1
        if pending == chunk:                           # int32 headroom spent
            acc = acc % p
            pending = 0
    o_ref[...] = acc % p


@functools.partial(jax.jit, static_argnames=("c", "p", "block_s", "interpret"))
def circulant_encode(data: jnp.ndarray, c: tuple[int, ...], p: int = 257, *,
                     block_s: int = 512, interpret: bool = True) -> jnp.ndarray:
    """data: (n, s) int32 data blocks -> (n, s) redundancy blocks.

    c must be a static tuple (it parameterizes the compiled kernel).
    """
    require_int32_envelope(p)
    c = tuple(int(x) % p for x in c)
    if any(x == 0 for x in c):
        raise ValueError("coefficients must be nonzero (paper §III-A)")
    data = jnp.asarray(data, jnp.int32) % p
    n, s = data.shape
    if n != 2 * len(c):
        raise ValueError(f"n={n} != 2k={2 * len(c)}")
    pad = (-s) % block_s
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad)))
    s_pad = s + pad
    grid = (s_pad // block_s,)
    out = pl.pallas_call(
        functools.partial(_circulant_encode_kernel, c=c, p=p),
        grid=grid,
        in_specs=[pl.BlockSpec((n, block_s), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, block_s), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, s_pad), jnp.int32),
        interpret=interpret,
        name="gf_circulant_encode",
    )(data)
    return out[:, :s]
