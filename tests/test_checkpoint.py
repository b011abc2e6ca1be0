"""MSR checkpointing: roundtrips, failure paths, byte accounting (gamma)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.circulant import CodeSpec
from repro.checkpoint.msr_checkpoint import MSRCheckpointer


def make_state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (37, 19), jnp.float32),
                   "b": jnp.arange(11, dtype=jnp.int32)},
        "opt": {"mu": jax.random.normal(k, (37, 19), jnp.float32) * 1e-3,
                "step": jnp.asarray(7, jnp.int32)},
    }


def assert_state_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture
def ckpt(tmp_path):
    return MSRCheckpointer(tmp_path, CodeSpec.make(4, 257))


def test_save_restore_systematic(ckpt):
    state = make_state()
    ckpt.save(3, state)
    got, report = ckpt.restore(state, 3)
    assert_state_equal(got, state)
    assert report.path == "systematic"
    # systematic restore reads only the n data blocks = ~B bytes
    n, s = ckpt.spec.n, None
    assert report.bytes_read <= report.bytes_total_stored // 2 + 64


def test_restore_latest_step(ckpt):
    s1, s2 = make_state(1), make_state(2)
    ckpt.save(1, s1)
    ckpt.save(2, s2)
    got, rep = ckpt.restore(s1)
    assert rep.step == 2
    assert_state_equal(got, s2)


def test_single_failure_regeneration_gamma(ckpt):
    """The paper's headline: repairing one node reads (k+1)/(2k) of B."""
    state = make_state()
    ckpt.save(5, state)
    got, report = ckpt.restore(state, 5, failed_nodes=[3])
    assert_state_equal(got, state)
    assert report.path == "regenerate"
    assert report.repaired_nodes == (3,)
    # repair-only bandwidth (isolated):
    b = ckpt.repair_node(5, 2)
    k = ckpt.spec.k
    n = ckpt.spec.n
    manifest_block = report.bytes_total_stored // (2 * n)   # ~S bytes
    ideal = (k + 1) * manifest_block
    assert b <= ideal * 1.10, (b, ideal)       # within 10% (packing overhead)
    assert b < 2 * k * manifest_block * 0.75   # strictly better than B


def test_multi_failure_reconstruction(ckpt):
    state = make_state()
    ckpt.save(1, state)
    got, report = ckpt.restore(state, 1, failed_nodes=[1, 4, 6])
    assert_state_equal(got, state)
    assert report.path == "reconstruct"
    assert set(report.repaired_nodes) == {1, 4, 6}
    # repaired files are valid: a fresh systematic restore succeeds
    got2, rep2 = ckpt.restore(state, 1)
    assert rep2.path == "systematic"
    assert_state_equal(got2, state)


def test_unrecoverable_raises(ckpt):
    state = make_state()
    ckpt.save(1, state)
    with pytest.raises(RuntimeError):
        ckpt.restore(state, 1, failed_nodes=[1, 2, 3, 4, 5])


@pytest.mark.parametrize("n_failed", [2, 3, 4])   # k=4, n=8: up to n-k
def test_multi_failure_repair_and_rewrite(ckpt, n_failed):
    """2..n-k failures: one decode matmul rebuilds data AND every lost
    pair; the repaired files are physically rewritten (newcomer protocol)."""
    state = make_state(n_failed)
    ckpt.save(1, state)
    failed = list(range(2, 2 + n_failed))
    # dead hosts: their files are gone, not just ignored
    for f in failed:
        for path in ckpt._node_files(1, f):
            path.unlink()
    got, report = ckpt.restore(state, 1, failed_nodes=failed)
    assert_state_equal(got, state)
    assert report.path == "reconstruct"
    assert report.repaired_nodes == tuple(failed)
    for f in failed:
        for path in ckpt._node_files(1, f):
            assert path.exists()
    # the rewritten step is fully consistent again
    assert ckpt.scrub(1).clean
    got2, rep2 = ckpt.restore(state, 1)
    assert rep2.path == "systematic"
    assert_state_equal(got2, state)


def test_multi_failure_no_repair(ckpt):
    """repair=False: degraded read only — state comes back, nothing is
    rewritten."""
    state = make_state(9)
    ckpt.save(1, state)
    failed = [3, 7]
    for f in failed:
        for path in ckpt._node_files(1, f):
            path.unlink()
    got, report = ckpt.restore(state, 1, failed_nodes=failed, repair=False)
    assert_state_equal(got, state)
    assert report.path == "reconstruct"
    assert report.repaired_nodes == ()
    for f in failed:
        for path in ckpt._node_files(1, f):
            assert not path.exists()


def test_scrub_clean_then_flags_corruption(ckpt):
    state = make_state(11)
    ckpt.save(1, state)
    report = ckpt.scrub(1)
    assert report.clean and report.mismatched_nodes == ()
    assert report.nodes_checked == ckpt.spec.n
    # scrub reads every pair: ~2B bytes (within packing overhead)
    _, rep = ckpt.restore(state, 1)
    assert report.bytes_read >= 2 * rep.bytes_read
    # flip one symbol of node 5's redundancy block on disk
    from repro.core import gf
    _, rf = ckpt._node_files(1, 5)
    z = np.load(rf)
    r = gf.unpack257(z["low"], z["hi"])
    r[0] = (r[0] + 1) % 257
    low, hi = gf.pack257(r)
    np.savez(rf, low=low, hi=hi)
    report2 = ckpt.scrub(1)
    assert not report2.clean
    assert 5 in report2.mismatched_nodes
    # the flagged node is repairable in place; scrub comes back clean
    ckpt.repair_node(1, 5)
    assert ckpt.scrub(1).clean


def test_every_single_node_repairable(tmp_path):
    spec = CodeSpec.make(3, 257)
    ckpt = MSRCheckpointer(tmp_path, spec)
    state = make_state(4)
    ckpt.save(2, state)
    for node in range(1, spec.n + 1):
        got, report = ckpt.restore(state, 2, failed_nodes=[node])
        assert_state_equal(got, state)
        assert report.path == "regenerate"


def test_gc_keeps_last(tmp_path):
    ckpt = MSRCheckpointer(tmp_path, CodeSpec.make(2, 257), keep_last=2)
    state = make_state()
    for s in (1, 2, 3, 4):
        ckpt.save(s, state)
    assert ckpt.steps() == [3, 4]


def test_bit_exact_across_dtypes(tmp_path):
    """bf16/f32/int mixtures survive the byte<->symbol mapping exactly."""
    ckpt = MSRCheckpointer(tmp_path, CodeSpec.make(2, 257))
    state = {"a": jnp.asarray([[1.5, -2.25]], jnp.bfloat16),
             "b": jnp.asarray([3.14159e-8, 1e30], jnp.float32),
             "c": jnp.asarray([-5, 2**30], jnp.int32)}
    ckpt.save(1, state)
    got, _ = ckpt.restore(state, 1, failed_nodes=[2])
    assert_state_equal(got, state)


def _node_file_contents(step_dir):
    """{file: bytes} of a step's node files: each ``.a.npy`` whole, each
    ``.r.npz`` member by member (the zip container stamps the time)."""
    import zipfile
    out = {}
    for f in sorted(step_dir.iterdir()):
        if f.name.endswith(".a.npy"):
            out[f.name] = f.read_bytes()
        elif f.name.endswith(".r.npz"):
            with zipfile.ZipFile(f) as z:
                for m in sorted(z.namelist()):
                    out[f"{f.name}/{m}"] = z.read(m)
    return out


@pytest.mark.parametrize("path", ["systematic", "regenerate", "reconstruct",
                                  "repair_node", "scrub"])
def test_uint8_blocks_roundtrip(tmp_path, monkeypatch, path):
    """Data blocks stay uint8 from the pytree to the planned executable;
    the node files and manifest are those an int32 (n, S) of the same
    values writes, and every restore path still gives the state back."""
    from repro.core import placement
    spec = CodeSpec.make(4, 257)
    state = make_state(5)
    blocks, _, _ = placement.pytree_to_blocks(state, spec.n)
    assert blocks.dtype == np.uint8
    ckpt = MSRCheckpointer(tmp_path / "u8", spec)
    ckpt.save(1, state)
    step_dir = ckpt._step_dir(1)
    files = _node_file_contents(step_dir)

    to_blocks = placement.pytree_to_blocks

    def widened(*args, **kwargs):
        blocks, treedef, tspec = to_blocks(*args, **kwargs)
        return blocks.astype(np.int32), treedef, tspec

    with monkeypatch.context() as m:
        m.setattr(placement, "pytree_to_blocks", widened)
        ref = MSRCheckpointer(tmp_path / "i32", spec)
        ref.save(1, state)
    assert _node_file_contents(ref._step_dir(1)) == files
    assert (step_dir / "manifest.json").read_bytes() == \
        (ref._step_dir(1) / "manifest.json").read_bytes()

    if path == "scrub":
        report = ckpt.scrub(1)
        assert report.clean and report.nodes_checked == spec.n
    elif path == "repair_node":
        for f in ckpt._node_files(1, 3):
            f.unlink()
        ckpt.repair_node(1, 3)
    else:
        failed = {"systematic": [], "regenerate": [3],
                  "reconstruct": [2, 6]}[path]
        for node in failed:
            for f in ckpt._node_files(1, node):
                f.unlink()
        got, report = ckpt.restore(state, 1, failed_nodes=failed)
        assert report.path == path
        assert_state_equal(got, state)
    assert _node_file_contents(step_dir) == files   # rebuilt bit-exactly


def test_coded_read_server_roundtrips_uint8_blocks():
    """The serving layer encodes ``pytree_to_blocks``' uint8 blocks and
    rebuilds the pytree from the simulator's int32 reads."""
    from repro.serve.engine import CodedReadServer
    spec = CodeSpec.make(4, 257)
    state = make_state(6)
    srv = CodedReadServer.for_pytree(state, spec)
    assert_state_equal(srv.read_state(), state)
    srv.sim.fail_node(3)
    assert_state_equal(srv.read_state(), state)


# ------------------------------------------- crash consistency (DESIGN.md §12)
from repro.io import (FaultInjector, FaultyBlob, GiveUpError, LocalBlob,
                      count_tmp_orphans, fast_retry)


class TestCrashConsistency:
    def test_steps_ignores_uncommitted(self, ckpt, tmp_path):
        ckpt.save(1, make_state())
        # orphans a crashed writer could leave: a staging dir and a
        # manifest-less (torn, pre-protocol) generation
        (tmp_path / "step_000002.tmp").mkdir()
        (tmp_path / "step_000003").mkdir()
        (tmp_path / "step_000003" / "node_01.a.npy").write_bytes(b"x")
        assert ckpt.steps() == [1]
        got, rep = ckpt.restore(make_state())       # latest = committed latest
        assert rep.step == 1

    def test_recover_sweeps_orphans(self, ckpt, tmp_path):
        ckpt.save(1, make_state())
        (tmp_path / "step_000002.tmp").mkdir()
        (tmp_path / "step_000002.tmp" / "junk").write_bytes(b"x")
        (tmp_path / "step_000003").mkdir()
        d1 = ckpt._step_dir(1)
        (d1 / "node_01.a.npy.tmp").write_bytes(b"x")   # torn atomic rewrite
        removed = ckpt.recover()
        assert set(removed) == {"step_000002.tmp", "step_000003",
                                "step_000001/node_01.a.npy.tmp"}
        assert count_tmp_orphans(tmp_path) == 0
        assert not (tmp_path / "step_000003").exists()
        assert ckpt.steps() == [1]
        assert ckpt.scrub(1).clean                     # committed gen intact

    def test_recover_runs_at_construction(self, tmp_path):
        (tmp_path / "step_000009.tmp").mkdir()
        ck = MSRCheckpointer(tmp_path, CodeSpec.make(2, 257))
        assert count_tmp_orphans(tmp_path) == 0

    def test_manifest_carries_content_crcs(self, ckpt):
        import json
        m = ckpt.save(4, make_state())
        n = ckpt.spec.n
        assert len(m["crc"]) == 2 * n
        on_disk = json.loads(
            (ckpt._step_dir(4) / "manifest.json").read_text())
        assert on_disk["crc"] == m["crc"]
        # repair rewrites are bit-exact: CRCs stay valid, no manifest churn
        ckpt.repair_node(4, 1)
        assert ckpt.scrub(4).clean

    def test_save_heals_transient_faults(self, tmp_path):
        faults = FaultInjector(seed=0)
        faults.add(op="write", kind="transient", times=3)
        ck = MSRCheckpointer(tmp_path, CodeSpec.make(2, 257),
                             io_backend=FaultyBlob(LocalBlob(), faults),
                             retry=fast_retry())
        state = make_state()
        ck.save(1, state)
        got, _ = ck.restore(state, 1)
        assert_state_equal(got, state)
        assert ck.retry_stats.retries >= 3 and ck.retry_stats.giveups == 0

    def test_persistent_fault_gives_up_leaves_no_generation(self, tmp_path):
        faults = FaultInjector(seed=0)
        faults.add(op="write", match="step_000002", kind="transient")
        ck = MSRCheckpointer(tmp_path, CodeSpec.make(2, 257),
                             io_backend=FaultyBlob(LocalBlob(), faults),
                             retry=fast_retry())
        state = make_state()
        ck.save(1, state)
        with pytest.raises(GiveUpError):
            ck.save(2, state)
        assert ck.steps() == [1]
        assert count_tmp_orphans(tmp_path) == 0
        got, _ = ck.restore(state)                  # previous gen still good
        assert_state_equal(got, state)

    def test_overwrite_same_step_is_atomic(self, ckpt):
        s1, s2 = make_state(1), make_state(2)
        ckpt.save(1, s1)
        ckpt.save(1, s2)                            # park-old + commit path
        assert ckpt.steps() == [1]
        got, _ = ckpt.restore(s1, 1)
        assert_state_equal(got, s2)
        assert ckpt.scrub(1).clean


class TestWriteBehind:
    def test_save_async_roundtrip_and_barrier(self, ckpt):
        state = make_state()
        fut = ckpt.save_async(7, state)
        manifest = ckpt.barrier()
        assert manifest["step"] == 7 and fut.done()
        assert ckpt.barrier() is None               # idempotent
        got, _ = ckpt.restore(state, 7)
        assert_state_equal(got, state)
        ckpt.close()

    def test_snapshot_isolates_from_mutation(self, ckpt):
        """The write-behind snapshot must capture the state AT CALL TIME:
        host-side mutation after save_async (the donation stand-in) must
        not leak into the checkpoint."""
        state = {"w": np.arange(64, dtype=np.int32)}
        want = state["w"].copy()
        ckpt.save_async(1, state)
        state["w"] += 999                           # "donated"/reused buffer
        ckpt.barrier()
        got, _ = ckpt.restore({"w": want}, 1)
        np.testing.assert_array_equal(np.asarray(got["w"]), want)
        ckpt.close()

    def test_single_inflight(self, ckpt):
        """A second save_async fences the first: generations commit in
        order, never interleaved."""
        for s in (1, 2, 3):
            ckpt.save_async(s, make_state(s))
        ckpt.barrier()
        assert ckpt.steps() == [1, 2, 3]
        got, _ = ckpt.restore(make_state(), 3)
        assert_state_equal(got, make_state(3))
        ckpt.close()

    def test_failure_surfaces_at_barrier(self, tmp_path):
        faults = FaultInjector(seed=0)
        faults.add(op="write", match="step_000002", kind="transient")
        ck = MSRCheckpointer(tmp_path, CodeSpec.make(2, 257),
                             io_backend=FaultyBlob(LocalBlob(), faults),
                             retry=fast_retry())
        ck.save_async(2, make_state())
        with pytest.raises(GiveUpError):
            ck.barrier()
        assert ck.steps() == []
        ck.close()
