"""Plain reference of the double-circulant code over GF(p), in NumPy.

It shares no code with the program under test: the encode follows the
paper's eq. (2) written out as a generator matrix, the any-k decode is a
Gauss-Jordan solve over GF(p), and the on-disk layout is decoded here
from its published description.  Matrix products run in
floating point only where it holds every partial sum exactly: a product
of two symbols is at most (p-1)**2 = 2**16, so a row of m of them stays
below 2**24, exact in float32, while m < 256; float64 beyond.  The
remainder is taken on the exact integer result.

Every check returns a count of mismatched symbols (0 when correct), so a
run can print the number beside its limit.  ``symbol_bits=8`` computes the
same thing with each symbol held in one byte, the shortcut that drops the
field's 257th value: that is the control, which has to read as wrong.
"""
from __future__ import annotations

import numpy as np


def gen_rows(c, p: int) -> np.ndarray:
    """(n, n) int64: row i-1 holds the coefficients of r_i over a_0..a_{n-1},
    r_i = sum_{u=1..k} c_u a_{(i-k-u) mod n} (paper eq. (2))."""
    k = len(c)
    n = 2 * k
    g = np.zeros((n, n), np.int64)
    for i in range(1, n + 1):
        for u in range(1, k + 1):
            g[i - 1, (i - k - u) % n] += int(c[u - 1])
    return g % p


def mat_mod(mat: np.ndarray, x: np.ndarray, p: int,
            symbol_bits: int = 9) -> np.ndarray:
    """(mat @ x) mod p as int32, for symbols ``x`` in 0..p-1.  With
    ``symbol_bits=8`` the inputs and the result are cut to one byte each
    (the control)."""
    x = np.asarray(x)
    if symbol_bits == 8:
        x = x & 0xFF
    mat = np.asarray(mat, np.int64) % p
    exact32 = (p - 1) ** 2 * mat.shape[1] < 2 ** 24
    dt = np.float32 if exact32 else np.float64
    out = (mat.astype(dt) @ x.astype(dt)).astype(
        np.int32 if exact32 else np.int64) % p
    out = out.astype(np.int32)
    return out & 0xFF if symbol_bits == 8 else out


def encode(c, data: np.ndarray, p: int, symbol_bits: int = 9) -> np.ndarray:
    """Redundancy blocks r_1..r_n of the (n, W) data blocks."""
    return mat_mod(gen_rows(c, p), data, p, symbol_bits)


def solve(mat: np.ndarray, rhs: np.ndarray, p: int) -> np.ndarray:
    """Gauss-Jordan solve of mat @ x = rhs over GF(p), int64 throughout.
    Raises ``ValueError`` on a singular system."""
    m = np.concatenate([np.asarray(mat) % p, np.asarray(rhs) % p],
                       axis=1).astype(np.int64)
    n = m.shape[0]
    for col in range(n):
        piv = next((r for r in range(col, n) if m[r, col]), None)
        if piv is None:
            raise ValueError("singular system")
        m[[col, piv]] = m[[piv, col]]
        m[col] = m[col] * pow(int(m[col, col]), p - 2, p) % p
        for r in range(n):
            if r != col and m[r, col]:
                m[r] = (m[r] - m[r, col] * m[col]) % p
    return m[:, n:]


def decode(c, nodes, a_rows: np.ndarray, r_rows: np.ndarray,
           p: int) -> np.ndarray:
    """All n data blocks from the (a, r) pairs of k code nodes (0-based
    ``nodes``, sorted): the any-k property of the code."""
    n = 2 * len(c)
    g = gen_rows(c, p)
    nodes = list(nodes)
    mat = np.concatenate([np.eye(n, dtype=np.int64)[nodes], g[nodes]])
    return solve(mat, np.concatenate([a_rows, r_rows]), p)


def stripe_mismatches(c, a: np.ndarray, r: np.ndarray, p: int, rng,
                      symbol_bits: int = 9) -> int:
    """Mismatched symbols of one stripe: ``r`` against the reference
    encode of ``a``, then ``a`` against the reference decode from the
    pairs of a random k of the n code nodes."""
    a = np.asarray(a, np.int64)
    r = np.asarray(r, np.int64)
    n = a.shape[0]
    bad = int(np.count_nonzero(encode(c, a, p, symbol_bits) != r))
    nodes = np.sort(rng.choice(n, size=n // 2, replace=False))
    try:
        dec = decode(c, nodes, a[nodes], r[nodes], p)
    except ValueError:
        return bad + a.size
    return bad + int(np.count_nonzero(dec != a))


def bytes_to_blocks(payload: np.ndarray, n: int) -> np.ndarray:
    """A byte string laid out as n equal data blocks, zero-padded: the
    systematic placement of a checkpoint (byte j of the state is symbol
    j mod S of block j div S, S = ceil(len / n))."""
    payload = np.asarray(payload, np.uint8).reshape(-1)
    s = -(-payload.size // n)
    out = np.zeros(n * s, np.uint8)
    out[:payload.size] = payload
    return out.reshape(n, s)


def unpack_red(low: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """A redundancy block as stored on disk: its symbols' low bytes plus
    the positions of the symbols equal to 256."""
    out = np.asarray(low).astype(np.int64)
    out[np.asarray(hi, np.int64)] = 256
    return out
