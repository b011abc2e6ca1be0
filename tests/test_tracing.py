"""The stage clock as the program's tracing primitive: spans inside the
checkpointer on the profiler's timeline, and the bytes they count."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.checkpoint.msr_checkpoint import MSRCheckpointer
from repro.core.circulant import CodeSpec
from repro.exec import staging
from repro.exec.plan import bucket_symbols

SPEC = CodeSpec.make(4, 257)

# every span a directory-mode save and single-node restore open
SPANS = {"repro.ckpt.save", "repro.ckpt.restore", "repro.serialize",
         "repro.pack", "repro.pad", "repro.h2d", "repro.d2h",
         "repro.format", "repro.write", "repro.fsync", "repro.read",
         "repro.ckpt.commit", "repro.ckpt.gc", "repro.pipe.dispatch",
         "repro.pipe.consume", "repro.assemble", "repro.deserialize"}


def make_state():
    key = jax.random.PRNGKey(3)
    return {"w": jax.random.normal(key, (61, 29), jnp.float32),
            "step": jnp.asarray(9, jnp.int32),
            "host": np.arange(13, dtype=np.int16)}


def device_bytes(state) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(state)
               if isinstance(x, jax.Array))


def traced(log_dir, fn):
    """Run ``fn`` under the profiler (no Python tracer); returns the
    host threads' ``repro.*`` events as {line index: [(name, s, e)]}."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        fn()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name.split("#")[0], e.start_ns,
                    e.start_ns + e.duration_ns) for e in line.events]
            evs = [ev for ev in evs if ev[0].startswith("repro.")]
            if evs:
                lines[(plane.name, i)] = evs
    return lines


@pytest.fixture(scope="module")
def save_restore_trace(tmp_path_factory):
    root = tmp_path_factory.mktemp("ckpt")
    ckpt = MSRCheckpointer(root, SPEC)
    state = make_state()

    def run():
        ckpt.save(1, state)
        got, report = ckpt.restore(state, 1, failed_nodes=[3])
        assert report.path == "regenerate"
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(state["w"]))

    return traced(tmp_path_factory.mktemp("trace"), run)


def test_every_checkpointer_span_is_in_the_trace(save_restore_trace):
    names = {ev[0] for evs in save_restore_trace.values() for ev in evs}
    assert SPANS <= names


def test_fsync_nests_inside_write_on_one_thread(save_restore_trace):
    nested = 0
    for evs in save_restore_trace.values():
        writes = [ev for ev in evs if ev[0] == "repro.write"]
        for w in writes:
            inner = [ev for ev in evs if ev[0] == "repro.fsync"
                     and w[1] <= ev[1] and ev[2] <= w[2]]
            assert len(inner) == 1, w
            nested += 1
    # 16 node files and the manifest of the save, the rewritten pair
    assert nested == 2 * SPEC.n + 1 + 2


def test_every_span_falls_inside_its_operation(save_restore_trace):
    evs = [ev for line in save_restore_trace.values() for ev in line]
    ops = [ev for ev in evs if ev[0] in ("repro.ckpt.save",
                                         "repro.ckpt.restore")]
    assert sorted(name for name, _, _ in ops) == ["repro.ckpt.restore",
                                                  "repro.ckpt.save"]
    for name, s, e in evs:
        assert any(os_ <= s and e <= oe for _, os_, oe in ops), name


def test_staged_counts_without_a_trace():
    t0, b0 = staging.stage_times(), staging.stage_bytes()
    with staging.staged("test.probe", nbytes=100, meta="x") as first:
        sum(range(1000))
    with staging.staged("test.probe") as span:
        span.nbytes = 23
    assert first.seconds > 0.0
    assert staging.stage_times()["test.probe"] - t0.get("test.probe", 0.0) \
        == pytest.approx(first.seconds + span.seconds)
    assert staging.stage_bytes()["test.probe"] - b0.get("test.probe", 0) \
        == 123


def test_staged_counts_a_block_that_raises():
    b0 = staging.stage_bytes().get("test.raise", 0)
    with pytest.raises(ValueError):
        with staging.staged("test.raise", nbytes=5):
            raise ValueError("boom")
    assert staging.stage_bytes()["test.raise"] - b0 == 5


@pytest.mark.parametrize("op", ["save", "restore"])
def test_link_bytes_are_the_padded_operands(tmp_path, op):
    """A save ships the (n, S) uint8 data blocks padded to their bucket
    and pulls the (n, S_pad) int32 redundancy back, besides the device
    leaves; a single-node regeneration ships the int32 repair matrix and
    r_prev and the k uint8 helper blocks, and pulls the (2, S_pad) int32
    pair."""
    ckpt = MSRCheckpointer(tmp_path, SPEC)
    state = make_state()
    s_sym = -(-sum(np.asarray(x).nbytes
                   for x in jax.tree_util.tree_leaves(state)) // SPEC.n)
    s_pad = bucket_symbols(s_sym)
    if op == "restore":
        ckpt.save(1, state)
    b0 = staging.stage_bytes()
    if op == "save":
        ckpt.save(1, state)
        h2d = SPEC.n * s_pad
        d2h = SPEC.n * s_pad * 4 + device_bytes(state)
    else:
        ckpt.restore(state, 1, failed_nodes=[2])
        h2d = 2 * (SPEC.k + 1) * 4 + s_pad * 4 + SPEC.k * s_pad
        d2h = 2 * s_pad * 4
    b1 = staging.stage_bytes()
    assert b1["h2d"] - b0.get("h2d", 0) == h2d
    assert b1["d2h"] - b0.get("d2h", 0) == d2h


def test_node_file_bytes_are_counted(tmp_path):
    ckpt = MSRCheckpointer(tmp_path, SPEC)
    b0 = staging.stage_bytes()
    ckpt.save(1, make_state())
    written = sum(os.path.getsize(p) for p in
                  glob.glob(os.path.join(str(tmp_path), "step_000001", "*")))
    assert staging.stage_bytes()["write"] - b0.get("write", 0) == written
    r0 = staging.stage_bytes().get("read", 0)
    ckpt.restore(make_state(), 1)
    a_files = glob.glob(os.path.join(str(tmp_path), "step_000001", "*.a.npy"))
    manifest = os.path.join(str(tmp_path), "step_000001", "manifest.json")
    assert staging.stage_bytes()["read"] - r0 == \
        sum(os.path.getsize(p) for p in a_files) + os.path.getsize(manifest)
