"""Fused batched repair engine (core/repair.py, DESIGN.md §4).

  * fused single-matmul regeneration is BIT-EXACT vs the unfused reference
    for every node, every registered backend, k in {2, 3, 4, 8};
  * batched (vmapped + stream-tiled) regeneration matches per-node calls;
  * the decode-inverse LRU serves repeated reconstructions from ONE
    ``gf.gauss_inverse`` per node subset, order-insensitively.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gf
from repro.core.circulant import CodeSpec
from repro.core.msr import DoubleCirculantMSR
from repro.core.repair import RepairEngine, build_repair_matrix

# native `pallas` needs a real TPU; interpret mode covers its semantics here
BACKENDS = ["jnp-int32", "jnp-f32", "pallas-interpret"]
if jax.default_backend() == "tpu":
    BACKENDS.append("pallas")

KS = (2, 3, 4, 8)


def random_blocks(n, s, p, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, p, size=(n, s), dtype=np.int64), jnp.int32)


def helpers_for(code, data, red, i):
    plan = code.repair_plan(i)
    return red[plan.prev_node - 1], data[jnp.asarray(plan.data_indices)]


# ------------------------------------------------------------ fused parity
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("k", KS)
def test_fused_regenerate_bit_exact_every_node(backend, k):
    spec = CodeSpec.make(k, 257)
    code = DoubleCirculantMSR(spec, backend=backend)
    n = spec.n
    data = random_blocks(n, 48, 257, seed=k)
    red = code.encode(data)
    for i in range(1, n + 1):
        r_prev, nxt = helpers_for(code, data, red, i)
        a_f, r_f = code.regenerate(i, r_prev, nxt)
        a_u, r_u = code.regenerate_reference(i, r_prev, nxt)
        np.testing.assert_array_equal(np.asarray(a_f), np.asarray(a_u),
                                      err_msg=f"{backend} k={k} node={i}")
        np.testing.assert_array_equal(np.asarray(r_f), np.asarray(r_u),
                                      err_msg=f"{backend} k={k} node={i}")
        # and both ARE the lost pair
        np.testing.assert_array_equal(np.asarray(a_f), np.asarray(data[i - 1]))
        np.testing.assert_array_equal(np.asarray(r_f), np.asarray(red[i - 1]))


def test_repair_matrix_node_invariant_and_small():
    spec = CodeSpec.make(4, 257)
    code = DoubleCirculantMSR(spec)
    r = build_repair_matrix(spec)
    assert r.shape == (2, spec.k + 1)
    assert r.dtype == np.int32
    assert int(r.min()) >= 0 and int(r.max()) < spec.p
    for i in (1, 3, spec.n):
        np.testing.assert_array_equal(code.repair.repair_matrix(i), r)
    with pytest.raises(ValueError):
        code.repair.repair_matrix(spec.n + 1)


def test_fused_regenerate_custom_matmul():
    """Custom injected matmuls keep every field op routed through the
    injected function — the fused path still applies (non-jitted)."""
    calls = []

    def mm(a, b, p):
        calls.append(np.asarray(a).shape)
        return gf.matmul(a, b, p)

    spec = CodeSpec.make(3, 257)
    code = DoubleCirculantMSR(spec, matmul=mm)
    data = random_blocks(spec.n, 32, 257, seed=1)
    red = code.encode(data)
    r_prev, nxt = helpers_for(code, data, red, 2)
    calls.clear()
    a_new, r_new = code.regenerate(2, r_prev, nxt)
    np.testing.assert_array_equal(np.asarray(a_new), np.asarray(data[1]))
    np.testing.assert_array_equal(np.asarray(r_new), np.asarray(red[1]))
    assert calls == [(2, spec.k + 1)]       # ONE fused matmul, nothing else


# ------------------------------------------------------------------ batched
@pytest.mark.parametrize("tile", [None, 7, 48])
def test_regenerate_batch_matches_single(tile):
    spec = CodeSpec.make(4, 257)
    code = DoubleCirculantMSR(spec)
    n = spec.n
    data = random_blocks(n, 48, 257, seed=9)
    red = code.encode(data)
    nodes = list(range(1, n + 1))
    r_prevs = jnp.stack([helpers_for(code, data, red, i)[0] for i in nodes])
    next_all = jnp.stack([helpers_for(code, data, red, i)[1] for i in nodes])
    out = code.regenerate_batch(nodes, r_prevs, next_all, tile_symbols=tile)
    assert out.shape == (n, 2, 48)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(data))
    np.testing.assert_array_equal(np.asarray(out[:, 1]), np.asarray(red))


def test_regenerate_batch_subset_and_shape_validation():
    spec = CodeSpec.make(2, 257)
    code = DoubleCirculantMSR(spec)
    data = random_blocks(spec.n, 16, 257, seed=3)
    red = code.encode(data)
    nodes = [2, 4]
    r_prevs = jnp.stack([helpers_for(code, data, red, i)[0] for i in nodes])
    next_all = jnp.stack([helpers_for(code, data, red, i)[1] for i in nodes])
    out = code.regenerate_batch(nodes, r_prevs, next_all)
    for row, i in enumerate(nodes):
        np.testing.assert_array_equal(np.asarray(out[row, 0]),
                                      np.asarray(data[i - 1]))
    with pytest.raises(ValueError):
        code.regenerate_batch([2], r_prevs, next_all)   # F mismatch


def test_regenerate_batch_does_not_hide_a_vmap_failure():
    """A jittable engine whose matmul has no batching rule raises: only
    engines built with ``jittable=False`` dispatch node by node."""
    from jax.extend.core import Primitive
    from jax.interpreters import mlir

    def impl(a, b):
        return jnp.einsum("mk,kn->mn", a, b) % 257

    prim = Primitive("gf_matmul_without_batching_rule")
    prim.def_impl(impl)
    prim.def_abstract_eval(lambda a, b: jax.core.ShapedArray(
        (a.shape[0], b.shape[1]), jnp.int32))
    mlir.register_lowering(prim, mlir.lower_fun(impl,
                                                multiple_results=False))
    engine = RepairEngine(CodeSpec.make(2, 257),
                          lambda a, b, p: prim.bind(a, b))
    with pytest.raises(NotImplementedError):
        engine.regenerate_batch([1, 2], np.zeros((2, 16), np.int32),
                                np.zeros((2, 2, 16), np.int32))


# ------------------------------------------------------- decode-inverse LRU
def test_repeated_reconstruct_single_gauss_inverse(monkeypatch):
    """Acceptance: repeated `reconstruct` on the same node subset performs
    exactly one `gf.gauss_inverse` — order of the subset irrelevant."""
    calls = []
    real = gf.gauss_inverse
    monkeypatch.setattr(gf, "gauss_inverse",
                        lambda m, p: (calls.append(1), real(m, p))[1])
    spec = CodeSpec.make(4, 257)
    code = DoubleCirculantMSR(spec)
    n = spec.n
    data = random_blocks(n, 24, 257, seed=5)
    red = code.encode(data)

    def rec(ids):
        sel = jnp.asarray([i - 1 for i in ids])
        got = code.reconstruct(ids, data[sel], red[sel])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(data))

    rec([1, 3, 5, 7])
    rec([1, 3, 5, 7])
    rec([7, 1, 5, 3])          # same subset, different order: still cached
    assert len(calls) == 1
    info = code.repair.decode_cache.cache_info()
    assert (info.hits, info.misses, info.size) == (2, 1, 1)
    rec([2, 4, 6, 8])          # new subset: one more solve
    assert len(calls) == 2


def test_decode_cache_lru_eviction():
    spec = CodeSpec.make(2, 257)
    code = DoubleCirculantMSR(spec, inverse_cache_size=2)
    cache = code.repair.decode_cache
    cache.inverse((1, 2))
    cache.inverse((1, 3))
    cache.inverse((1, 2))      # refresh 1,2 -> LRU victim is 1,3
    cache.inverse((1, 4))      # evicts 1,3
    assert cache.cache_info().size == 2
    misses = cache.cache_info().misses
    cache.inverse((1, 3))      # gone: recomputed
    assert cache.cache_info().misses == misses + 1
    with pytest.raises(ValueError):
        cache.inverse((2, 1))  # unsorted keys rejected (engine sorts)


# -------------------------------------------------- one-matmul multi-repair
@pytest.mark.parametrize("n_failed", [1, 2, 4])
def test_reconstruct_with_repair_lost_pairs(n_failed):
    spec = CodeSpec.make(4, 257)
    code = DoubleCirculantMSR(spec)
    n = spec.n
    data = random_blocks(n, 40, 257, seed=n_failed)
    red = code.encode(data)
    failed = list(range(1, n_failed + 1))
    use = [i for i in range(1, n + 1) if i not in failed][: spec.k]
    sel = jnp.asarray([i - 1 for i in use])
    got_data, got_red = code.reconstruct_with_repair(
        use, data[sel], red[sel], failed)
    np.testing.assert_array_equal(np.asarray(got_data), np.asarray(data))
    np.testing.assert_array_equal(
        np.asarray(got_red),
        np.asarray(red[jnp.asarray([f - 1 for f in failed])]))
