"""Prime-field GF(p) arithmetic, vectorized for JAX.

The paper (Gastón & Pujol 2010) works over an arbitrary finite field F_m.
We default to p = 257: the smallest prime > 2**8, so every data *byte* is a
field element.  Key TPU-native property (see DESIGN.md §2):

  * integers 0..256 are exactly representable in bf16 (8-bit significand),
  * products <= 256**2 = 2**16 are exact in the MXU's fp32 accumulator,
  * a k-term dot product with k <= 128 stays < 2**24, i.e. exact in fp32.

Hence GF(257) matmuls lower to a single native bf16xbf16->fp32 MXU pass plus
a cheap `mod p` fold — no lookup tables, no integer matmul units.  On CPU
(this container) the same code paths run in fp32/int32 and remain exact.

Everything here is pure JAX (jit/vmap/shard_map friendly).  Host-side helpers
(`inv_table`, `gauss_inverse`) use numpy for tiny O(n^3) matrices.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import numpy as np

DEFAULT_P = 257


# Max number of accumulation terms an int32 lane can hold before a `mod p`
# fold is due: 32767 terms for p = 257 (the lazy mod-folding envelope,
# DESIGN.md §3.2).  The bound lives in repro.kernels.envelope — the single
# source of truth — imported lazily so core carries no module-level edge
# into kernels.  The old fp32-dot bound (128 terms) lives in
# repro.kernels.gf_matmul where the MXU path actually needs it.
def _i32_chunk(p: int) -> int:
    from repro.kernels.envelope import int32_lazy_terms, require_int32_envelope
    require_int32_envelope(p)
    return int32_lazy_terms(p)


def _check_prime(p: int) -> None:
    if p < 2 or any(p % q == 0 for q in range(2, int(p**0.5) + 1)):
        raise ValueError(f"p={p} is not prime")


# ---------------------------------------------------------------------------
# Elementwise ops (int32 lanes; exact)
# ---------------------------------------------------------------------------

def add(x, y, p: int = DEFAULT_P):
    return (jnp.asarray(x, jnp.int32) + jnp.asarray(y, jnp.int32)) % p


def sub(x, y, p: int = DEFAULT_P):
    return (jnp.asarray(x, jnp.int32) - jnp.asarray(y, jnp.int32)) % p


def mul(x, y, p: int = DEFAULT_P):
    return (jnp.asarray(x, jnp.int32) * jnp.asarray(y, jnp.int32)) % p


def neg(x, p: int = DEFAULT_P):
    return (-jnp.asarray(x, jnp.int32)) % p


def pow_(x, e: int, p: int = DEFAULT_P):
    """x**e mod p by square-and-multiply (e is a static python int >= 0)."""
    x = jnp.asarray(x, jnp.int32) % p
    acc = jnp.ones_like(x)
    while e:
        if e & 1:
            acc = (acc * x) % p
        x = (x * x) % p
        e >>= 1
    return acc


def inv(x, p: int = DEFAULT_P):
    """Multiplicative inverse by Fermat's little theorem: x**(p-2) mod p."""
    return pow_(x, p - 2, p)


# ---------------------------------------------------------------------------
# Matmul over GF(p)
# ---------------------------------------------------------------------------

def matmul(a, b, p: int = DEFAULT_P, *, precision=None):
    """(a @ b) mod p, exact — portable int32 lanes with lazy mod-folding.

    a: (..., m, k) int32 symbols in [0, p)
    b: (..., k, n) int32 symbols in [0, p)

    Chunks the contraction by int32 headroom (~(2^31-1)/(p-1)^2 terms, 32767
    for p = 257) instead of the fp32 bound (128 terms): for any realistic k
    that is a single einsum and ONE `mod p` fold.  The MXU fp32 path lives
    in repro.kernels (dispatch backend `jnp-f32` / `pallas`).
    """
    del precision  # kept for API compat; the int32 path has no fp rounding
    a = jnp.asarray(a, jnp.int32) % p
    b = jnp.asarray(b, jnp.int32) % p
    k = a.shape[-1]
    chunk = _i32_chunk(p)
    if k <= chunk:
        return jnp.einsum("...mk,...kn->...mn", a, b) % p
    # fold the running sum every chunk: for p near the int32 ceiling the
    # chunk count itself can be large, so unfolded < p partials could wrap
    out = None
    for s in range(0, k, chunk):
        part = jnp.einsum("...mk,...kn->...mn",
                          a[..., s : s + chunk], b[..., s : s + chunk, :]) % p
        out = part if out is None else (out + part) % p
    return out


def matvec(m, v, p: int = DEFAULT_P):
    return matmul(m, v[..., None], p)[..., 0]


# ---------------------------------------------------------------------------
# Host-side dense linear algebra (tiny matrices: code dimension n <= 512)
# ---------------------------------------------------------------------------

def gauss_inverse(mat: np.ndarray, p: int = DEFAULT_P) -> np.ndarray:
    """Inverse of a square matrix over GF(p) by Gauss-Jordan (numpy, host).

    Raises ValueError if the matrix is singular over GF(p).
    """
    mat = np.asarray(mat, dtype=np.int64) % p
    n = mat.shape[0]
    if mat.shape != (n, n):
        raise ValueError(f"square matrix required, got {mat.shape}")
    aug = np.concatenate([mat, np.eye(n, dtype=np.int64)], axis=1)
    for col in range(n):
        piv = None
        for r in range(col, n):
            if aug[r, col] % p != 0:
                piv = r
                break
        if piv is None:
            raise ValueError("matrix is singular over GF(%d)" % p)
        if piv != col:
            aug[[col, piv]] = aug[[piv, col]]
        pinv = pow(int(aug[col, col]), p - 2, p)
        aug[col] = (aug[col] * pinv) % p
        for r in range(n):
            if r != col and aug[r, col] % p:
                aug[r] = (aug[r] - aug[r, col] * aug[col]) % p
    return (aug[:, n:] % p).astype(np.int32)


def gauss_det(mat: np.ndarray, p: int = DEFAULT_P) -> int:
    """Determinant over GF(p) (numpy, host)."""
    mat = np.asarray(mat, dtype=np.int64).copy() % p
    n = mat.shape[0]
    det = 1
    for col in range(n):
        piv = None
        for r in range(col, n):
            if mat[r, col] % p != 0:
                piv = r
                break
        if piv is None:
            return 0
        if piv != col:
            mat[[col, piv]] = mat[[piv, col]]
            det = (-det) % p
        det = (det * int(mat[col, col])) % p
        pinv = pow(int(mat[col, col]), p - 2, p)
        mat[col] = (mat[col] * pinv) % p
        for r in range(col + 1, n):
            if mat[r, col] % p:
                mat[r] = (mat[r] - mat[r, col] * mat[col]) % p
    return int(det % p)


def nullspace(mat: np.ndarray, p: int = DEFAULT_P) -> np.ndarray:
    """Basis of the right null space of ``mat`` over GF(p) (numpy, host).

    Returns an (n_cols, nullity) matrix N with ``mat @ N == 0 (mod p)``
    whose columns are the canonical RREF basis vectors (free column j
    gets a 1, pivot rows carry the negated reduced entries).  Used by the
    product-matrix code family to shorten the parent (n', k', d') code:
    the admissible messages are exactly the null space of the deleted
    nodes' share map (DESIGN.md §15.2).
    """
    a = np.asarray(mat, dtype=np.int64) % p
    if a.ndim != 2:
        raise ValueError(f"matrix required, got shape {a.shape}")
    rows, cols = a.shape
    a = a.copy()
    pivots: list[int] = []
    r = 0
    for c in range(cols):
        if r == rows:
            break
        piv = None
        for i in range(r, rows):
            if a[i, c] % p:
                piv = i
                break
        if piv is None:
            continue
        if piv != r:
            a[[r, piv]] = a[[piv, r]]
        a[r] = (a[r] * pow(int(a[r, c]), p - 2, p)) % p
        for i in range(rows):
            if i != r and a[i, c] % p:
                a[i] = (a[i] - a[i, c] * a[r]) % p
        pivots.append(c)
        r += 1
    free = [c for c in range(cols) if c not in pivots]
    basis = np.zeros((cols, len(free)), dtype=np.int64)
    for j, fc in enumerate(free):
        basis[fc, j] = 1
        for i, pc in enumerate(pivots):
            basis[pc, j] = (-a[i, fc]) % p
    return (basis % p).astype(np.int32)


def solve(mat: np.ndarray, rhs: np.ndarray, p: int = DEFAULT_P) -> np.ndarray:
    """Solve mat @ x = rhs over GF(p).  rhs may be a matrix of columns.

    Host-side numpy for the tiny system matrix; the big-block application is
    done with `matmul` on device by the callers.
    """
    inv_m = gauss_inverse(mat, p)
    return (inv_m.astype(np.int64) @ (np.asarray(rhs, np.int64) % p)) % p


# ---------------------------------------------------------------------------
# Byte <-> symbol packing
# ---------------------------------------------------------------------------

def bytes_to_symbols(data: bytes | np.ndarray, p: int = DEFAULT_P) -> np.ndarray:
    """Lossless embedding of a byte stream into GF(p) symbols (p > 256)."""
    if p <= 256:
        raise ValueError("byte embedding requires p > 256")
    arr = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else np.asarray(data, np.uint8)
    return arr.astype(np.int32)


def bytes_to_symbols_into(data: bytes | np.ndarray, out: np.ndarray,
                          p: int = DEFAULT_P) -> np.ndarray:
    """One-pass byte embedding into a preallocated symbol buffer
    (zero-copy staging, DESIGN.md §16.1): the copy (and, into int32,
    the cast) and the stripe zero-padding land in a single strided write
    over ``out`` instead of the legacy astype -> pad -> astype copy
    chain.  ``out`` must be a flat int32 or uint8 array (data symbols
    are bytes, so uint8 holds them as they are) at least ``len(data)``
    long; the tail past the payload is zeroed.  Counts toward the
    "pack" stage clock.
    """
    if p <= 256:
        raise ValueError("byte embedding requires p > 256")
    arr = np.frombuffer(data, dtype=np.uint8) \
        if isinstance(data, (bytes, bytearray)) else np.asarray(data, np.uint8)
    if out.dtype not in (np.int32, np.uint8) or out.ndim != 1 \
            or out.size < arr.size:
        raise ValueError(f"need flat int32 or uint8 out of >= {arr.size} "
                         f"symbols, got {out.dtype} {out.shape}")
    # lazy import: the stage clock lives in repro.exec.staging and core
    # carries no module-level edge into exec (as with the envelope)
    from repro.exec.staging import staged
    with staged("pack"):
        out[:arr.size] = arr
        out[arr.size:] = 0
    return out


def symbols_to_bytes(sym: np.ndarray) -> bytes:
    sym = np.asarray(sym)
    if sym.max(initial=0) > 255 or sym.min(initial=0) < 0:
        raise ValueError("symbols out of byte range; not a systematic data block")
    return sym.astype(np.uint8).tobytes()


def pack257(sym: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Pack GF(257) symbols (values 0..256) into (low_bytes uint8, idx256).

    The value 256 occurs with probability ~1/257 in redundancy blocks; we
    store its positions explicitly, so storage is S * (1 + 4/257) bytes
    instead of 2-4 bytes/symbol — the redundancy blocks stay byte-priced.
    """
    sym = np.asarray(sym)
    if sym.min(initial=0) < 0 or sym.max(initial=0) > 256:
        raise ValueError("symbols out of GF(257) range")
    hi = np.nonzero(sym.reshape(-1) == 256)[0].astype(np.int64)
    low = (sym.reshape(-1) % 256).astype(np.uint8)
    return low, hi


def unpack257(low: np.ndarray, hi: np.ndarray, shape=None) -> np.ndarray:
    out = low.astype(np.int32)
    out[hi] = 256
    return out.reshape(shape) if shape is not None else out


def pack257_rows(sym: np.ndarray, *, out: np.ndarray | None = None,
                 ) -> tuple[np.ndarray, list[np.ndarray]]:
    """Vectorized per-row pack257 for a (n, S) block matrix.

    One pass over the whole matrix (no per-node Python loop): returns the
    uint8 low bytes (n, S) and a list of n per-row index-of-256 arrays.

    ``out`` (uint8, same shape) receives the low bytes in place — the
    zero-copy staging path (DESIGN.md §16): callers pass a pooled
    buffer so a checkpoint save stages no fresh (n, S) allocation.  The
    int32 -> uint8 truncating store IS the ``& 0xFF`` (values are
    0..256, so only 256 wraps — to 0, as before).
    """
    sym = np.asarray(sym)
    if sym.ndim != 2:
        raise ValueError(f"expected (n, S) block matrix, got {sym.shape}")
    if sym.min(initial=0) < 0 or sym.max(initial=0) > 256:
        raise ValueError("symbols out of GF(257) range")
    from repro.exec.staging import staged
    with staged("pack"):
        if out is None:
            low = (sym & 0xFF).astype(np.uint8)   # 256 -> 0, others unchanged
        else:
            if out.shape != sym.shape or out.dtype != np.uint8:
                raise ValueError(f"out must be uint8 {sym.shape}, got "
                                 f"{out.dtype} {out.shape}")
            np.copyto(out, sym, casting="unsafe")
            low = out
        rows, cols = np.nonzero(sym == 256)
        splits = np.searchsorted(rows, np.arange(1, sym.shape[0]))
        his = np.split(cols.astype(np.int64), splits)
    return low, his


def unpack257_rows(low: np.ndarray, his: Sequence[np.ndarray], *,
                   out: np.ndarray | None = None) -> np.ndarray:
    """Inverse of pack257_rows.  ``out`` (int32, same shape) receives
    the expansion in place — pooled-buffer staging for restore/scrub."""
    from repro.exec.staging import staged
    with staged("pack"):
        if out is None:
            out = np.asarray(low).astype(np.int32)
        else:
            low = np.asarray(low)
            if out.shape != low.shape or out.dtype != np.int32:
                raise ValueError(f"out must be int32 {low.shape}, got "
                                 f"{out.dtype} {out.shape}")
            np.copyto(out, low)
        for i, hi in enumerate(his):
            out[i, hi] = 256
    return out


def packed_nbytes(sym: np.ndarray) -> int:
    low, hi = pack257(sym)
    return low.nbytes + hi.nbytes


__all__ = [
    "DEFAULT_P", "add", "sub", "mul", "neg", "pow_", "inv", "matmul",
    "matvec", "gauss_inverse", "gauss_det", "nullspace", "solve",
    "bytes_to_symbols", "symbols_to_bytes",
    "pack257", "unpack257", "pack257_rows", "unpack257_rows", "packed_nbytes",
]
