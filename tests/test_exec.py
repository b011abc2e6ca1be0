"""Execution-plan layer tests (DESIGN.md §11): bucket-ladder
correctness, padded bit-exactness, cache accounting, the steady-state
zero-recompile guarantee, and the unified pipeline engine."""
import numpy as np
import pytest

from repro.core.circulant import CodeSpec
from repro.core.msr import DoubleCirculantMSR
from repro.exec import plan as plan_mod
from repro.exec.pipeline import Pipeline
from repro.exec.plan import PlanCache, PlanResult, bucket_symbols
from repro.kernels import dispatch

P = 257
SPEC = CodeSpec.make(4, P)


def fresh_planner(bucket_min=32) -> PlanCache:
    """An UNSHARED plan cache (stats start at zero regardless of what
    other tests warmed in the process-wide registry)."""
    return PlanCache(dispatch.get("jnp-int32"), P, bucket_min=bucket_min)


# ------------------------------------------------------------ bucket ladder
class TestBucketLadder:
    def test_floor_and_growth(self):
        assert bucket_symbols(1, bucket_min=64) == 64
        assert bucket_symbols(64, bucket_min=64) == 64
        assert bucket_symbols(65, bucket_min=64) == 128
        assert bucket_symbols(129, bucket_min=64) == 256

    def test_ladder_membership_and_cover(self):
        for s in (1, 7, 100, 4095, 4096, 4097, 1 << 20, (1 << 20) + 1):
            b = bucket_symbols(s)
            assert b >= s
            # b is on the ladder: bucket_min * ratio^j
            j = 0
            x = plan_mod.BUCKET_MIN
            while x < b:
                x = int(x * plan_mod.BUCKET_RATIO)
                j += 1
            assert x == b
            # and it is the SMALLEST such bucket
            assert b == plan_mod.BUCKET_MIN or \
                int(b / plan_mod.BUCKET_RATIO) < s

    def test_log_many_buckets(self):
        # a 1000x size range maps to a handful of plans — the whole point
        buckets = {bucket_symbols(s) for s in range(1 << 10, 1 << 20, 997)}
        assert len(buckets) <= 11

    def test_invalid(self):
        with pytest.raises(ValueError):
            bucket_symbols(0)
        with pytest.raises(ValueError):
            bucket_symbols(10, ratio=1.0)


# ------------------------------------------------------ padded bit-exactness
class TestPlannedOpsBitExact:
    """Bucket padding must be invisible: planned results at odd stream
    extents equal the unpadded reference exactly."""

    rng = np.random.default_rng(7)

    @pytest.mark.parametrize("s", [1, 5, 31, 32, 33, 100])
    def test_matmul(self, s):
        pc = fresh_planner()
        mat = self.rng.integers(0, P, (6, 8)).astype(np.int32)
        blocks = self.rng.integers(0, P, (8, s)).astype(np.int32)
        ref = (mat.astype(np.int64) @ blocks) % P
        out = pc.matmul(mat, blocks).host()
        assert out.shape == ref.shape
        np.testing.assert_array_equal(out, ref)

    @pytest.mark.parametrize("s", [3, 32, 57])
    def test_circulant_encode(self, s):
        pc = fresh_planner()
        code = DoubleCirculantMSR(SPEC)
        data = self.rng.integers(0, P, (SPEC.n, s)).astype(np.int32)
        ref = np.asarray(code.encode(data))
        out = pc.circulant_encode(data, tuple(SPEC.c)).host()
        np.testing.assert_array_equal(out, ref)

    @pytest.mark.parametrize("s", [9, 40])
    def test_regenerate_and_batch(self, s):
        pc = fresh_planner()
        code = DoubleCirculantMSR(SPEC)
        data = self.rng.integers(0, P, (SPEC.n, s)).astype(np.int32)
        red = np.asarray(code.encode(data))
        nodes = [2, 5, 7]
        r_prevs = np.stack([red[code.repair_plan(i).prev_node - 1]
                            for i in nodes])
        helpers = np.stack([data[list(code.repair_plan(i).data_indices)]
                            for i in nodes])
        rmat = code.repair.repair_matrix()
        one = pc.regenerate(rmat, r_prevs[0], helpers[0]).host()
        a, r = code.regenerate(nodes[0], r_prevs[0], helpers[0])
        np.testing.assert_array_equal(one[0], np.asarray(a))
        np.testing.assert_array_equal(one[1], np.asarray(r))
        # batch: BOTH axes padded (F=3 -> batch bucket 4), trimmed back
        batch = pc.regenerate_batch(rmat, r_prevs, helpers).host()
        ref = np.asarray(code.regenerate_batch(nodes, r_prevs, helpers))
        assert batch.shape == ref.shape == (3, 2, s)
        np.testing.assert_array_equal(batch, ref)

    def test_disabled_fallback_bit_exact(self):
        pc = fresh_planner()
        mat = self.rng.integers(0, P, (4, 8)).astype(np.int32)
        blocks = self.rng.integers(0, P, (8, 21)).astype(np.int32)
        ref = (mat.astype(np.int64) @ blocks) % P
        with plan_mod.planning_disabled():
            out = pc.matmul(mat, blocks)
            assert isinstance(out, PlanResult)
            np.testing.assert_array_equal(out.host(), ref)
        assert pc.plan_stats().compiles == 0     # bypassed entirely


# ---------------------------------------------------- uint8 data operands
def _u8_case(op, code, rng, s):
    """(call, data) for one planned op: ``call(pc, x)`` runs ``op`` with
    ``x`` as its data-symbol stream operand (values 0..255)."""
    data = rng.integers(0, 256, (code.n, s)).astype(np.int32)
    red = np.asarray(code.encode(data))
    rmat = code.repair.repair_matrix()
    nodes = [2, 5, 7]
    r_prevs = np.stack([red[code.repair_plan(i).prev_node - 1]
                        for i in nodes])
    idx = [list(code.repair_plan(i).data_indices) for i in nodes]
    if op == "circulant_encode":
        return (lambda pc, x: pc.circulant_encode(x, tuple(SPEC.c))), data
    if op == "matmul":
        mat = rng.integers(0, P, (code.n, code.n)).astype(np.int32)
        return (lambda pc, x: pc.matmul(mat, x)), data
    if op == "regenerate":
        return (lambda pc, x: pc.regenerate(rmat, r_prevs[0], x)), \
            data[idx[0]]
    return (lambda pc, x: pc.regenerate_batch(rmat, r_prevs, x)), \
        np.stack([data[i] for i in idx])


@pytest.mark.parametrize("op", ["circulant_encode", "regenerate",
                                "regenerate_batch", "matmul"])
def test_uint8_stream_operand_matches_int32(op, monkeypatch):
    """A uint8 data operand gives the int32 operand's exact result (odd
    stream tails padded in uint8), compiles one executable of its own
    that a second extent in the bucket reuses, and leaves the int32 key
    and executable as they were; donation is off for it and the
    unplanned fallback takes it too."""
    rng = np.random.default_rng(11)
    code = DoubleCirculantMSR(SPEC)
    pc = PlanCache(dispatch.get("jnp-int32"), P, bucket_min=32,
                   donate=True)
    donated = []
    jit = plan_mod.jax.jit

    def spy(fn, donate_argnums=(), **kw):
        donated.append(donate_argnums)
        return jit(fn, donate_argnums=donate_argnums, **kw)

    monkeypatch.setattr(plan_mod.jax, "jit", spy)
    call, x32 = _u8_case(op, code, rng, 45)         # bucket 64: padded
    ref = call(pc, x32).host()
    (k32,) = pc._plans
    assert "uint8" not in k32
    got = call(pc, x32.astype(np.uint8)).host()
    np.testing.assert_array_equal(got, ref)
    assert set(pc._plans) == {k32, k32 + ("uint8",)}
    assert donated[1] == ()
    assert pc.plan_stats().compiles == 2
    # a second extent in the same bucket: no compile, for either dtype
    call2, y32 = _u8_case(op, code, rng, 51)
    np.testing.assert_array_equal(call2(pc, y32.astype(np.uint8)).host(),
                                  call2(pc, y32).host())
    assert pc.plan_stats().compiles == 2
    with plan_mod.planning_disabled():
        np.testing.assert_array_equal(
            np.asarray(call(pc, x32.astype(np.uint8)).host()), ref)
    assert pc.plan_stats().compiles == 2


# -------------------------------------------------------- cache accounting
class TestPlanStats:
    def test_hits_misses_compiles(self):
        pc = fresh_planner(bucket_min=32)
        mat = np.eye(8, dtype=np.int32)
        for s, expect in ((10, (0, 1)), (20, (1, 1)), (32, (2, 1)),
                          (33, (2, 2)), (40, (3, 2)), (10, (4, 2))):
            pc.matmul(mat, np.ones((8, s), np.int32))
            st = pc.plan_stats()
            assert (st.hits, st.misses) == expect, s
            assert st.compiles == st.misses
        # a different op at the same bucket is its own plan
        pc.circulant_encode(np.ones((8, 10), np.int32), tuple(SPEC.c))
        assert pc.plan_stats().misses == 3
        pc.reset_stats()
        assert pc.plan_stats() == (0, 0, 0)

    def test_registry_aggregates_and_shares(self):
        be = dispatch.get("jnp-int32")
        a = plan_mod.get_planner(be, P)
        b = plan_mod.get_planner(be, P)
        assert a is b                      # one cache per (backend, p, ...)
        agg = plan_mod.plan_stats()
        assert agg.compiles >= a.plan_stats().compiles


# --------------------------------------------- steady-state recompile guard
class TestRecompileRegression:
    def test_store_and_checkpoint_steady_state(self, tmp_path):
        """A put/get/restore loop over varied sizes performs ZERO new
        compiles after its warm-up pass — the PR's acceptance bar."""
        from repro.store import CodedObjectStore
        from repro.checkpoint.msr_checkpoint import MSRCheckpointer

        rng = np.random.default_rng(0)
        store = CodedObjectStore(SPEC, n_nodes=SPEC.n + 2,
                                 stripe_symbols=256)
        ck = MSRCheckpointer(tmp_path, SPEC, keep_last=10)
        sizes = [300, 1700, 5000, 9000, 12000]

        def one_pass(tag):
            for i, size in enumerate(sizes):
                payload = bytes(rng.integers(0, 256, size,
                                             dtype=np.int64)
                                .astype(np.uint8))
                store.put(f"{tag}/{i}", payload)
                assert store.get(f"{tag}/{i}") == payload
                state = {"x": np.frombuffer(payload, np.uint8)
                         .astype(np.float32)}
                ck.save(i, state)
                got, _ = ck.restore(state, i, failed_nodes=[2],
                                    repair=False)
                np.testing.assert_array_equal(got["x"], state["x"])

        one_pass("warm")                       # compiles land here
        store.fail_node(1)
        one_pass("warm2")                      # degraded-read plans land
        warm = plan_mod.plan_stats()
        one_pass("steady")                     # same buckets, new sizes
        one_pass("steady2")
        steady = plan_mod.plan_stats()
        assert steady.compiles == warm.compiles, (
            f"steady-state recompiles: {steady.compiles - warm.compiles}")
        assert steady.hits > warm.hits         # the loop really ran planned


# ----------------------------------------------------------------- pipeline
class TestPipeline:
    @pytest.mark.parametrize("depth", [1, 2, 3])
    def test_stream_tiles_in_order_and_complete(self, depth):
        out = np.empty(103, np.int64)
        order = []
        with Pipeline(io_workers=2, depth=depth) as pipe:
            pipe.stream_tiles(
                103, 10,
                lambda sl: np.arange(sl.start, sl.stop),
                lambda sl, r: (order.append(sl.start),
                               out.__setitem__(sl, r)))
        np.testing.assert_array_equal(out, np.arange(103))
        assert order == sorted(order)          # consumed in stream order

    def test_map_with_read_prefetch(self):
        reads, consumed = [], []
        pipe = Pipeline(io_workers=2, depth=2)
        pipe.map(list(range(7)),
                 lambda i, d: d * 10,
                 lambda i, r: consumed.append(r),
                 read=lambda i: (reads.append(i), i + 1)[1])
        pipe.close()
        assert consumed == [10, 20, 30, 40, 50, 60, 70]
        assert sorted(reads) == list(range(7))

    def test_depth_one_is_serial(self):
        """depth=1: item t is fully consumed before t+1's compute —
        the benchmark's no-overlap baseline."""
        events = []
        with Pipeline(io_workers=1, depth=1) as pipe:
            pipe.map([0, 1, 2],
                     lambda i: events.append(("c", i)),
                     lambda i, r: events.append(("u", i)))
        assert events == [("c", 0), ("u", 0), ("c", 1), ("u", 1),
                          ("c", 2), ("u", 2)]

    def test_submit_error_surfaces_on_exit(self):
        def boom():
            raise OSError("disk on fire")
        with pytest.raises(OSError, match="disk on fire"):
            with Pipeline(io_workers=1) as pipe:
                pipe.submit(boom)

    def test_barrier_clears_and_reuse_after_close(self):
        pipe = Pipeline(io_workers=1)
        fut = pipe.submit(lambda: 42)
        pipe.barrier()
        assert fut.result() == 42
        pipe.close()
        assert pipe.submit(lambda: 1).result() == 1    # fresh pool spins up
        pipe.close()


# ------------------------------------------------------------- plan result
def test_plan_result_trims_stream_and_batch():
    raw = np.arange(4 * 2 * 8).reshape(4, 2, 8)
    res = PlanResult(raw, symbols=5, batch=3)
    out = res.host()
    assert out.shape == (3, 2, 5)
    np.testing.assert_array_equal(out, raw[:3, :, :5])
    np.testing.assert_array_equal(np.asarray(res), out)   # __array__


def test_store_close_releases_pool_and_store_stays_usable():
    from repro.store import CodedObjectStore
    with CodedObjectStore(SPEC, stripe_symbols=64) as store:
        store.put("x", b"abc")
        assert store.get("x") == b"abc"
    assert store.pipeline._ex is None          # pool released on exit
    store.put("y", b"def")                     # lazily respawns
    assert store.get("y") == b"def"
    store.close()


def test_planned_validation_errors():
    code = DoubleCirculantMSR(SPEC)
    with pytest.raises(ValueError, match="helper"):
        code.repair.regenerate_planned(1, np.ones(8, np.int32),
                                       np.ones((SPEC.k + 1, 8), np.int32))
    with pytest.raises(ValueError, match="blocks"):
        code.encode_planned(np.ones((SPEC.n - 1, 8), np.int32))
