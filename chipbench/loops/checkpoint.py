"""The ``checkpoint`` loop: a training job's checkpoint shard, held on
the device, saved or restored back to back through ``MSRCheckpointer``
into node files on the local disk."""
from __future__ import annotations

import pathlib
import shutil
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench import reference, work
from chipbench.generator import Check, Loop, advance_fn, make_state, \
    state_bytes


def _disk(path: pathlib.Path) -> dict:
    """The filesystem that holds ``path``: its type and its free bytes."""
    best, fstype = "", "unknown"
    with open("/proc/mounts") as f:
        for line in f:
            _dev, mnt, typ, *_ = line.split()
            inside = str(path) == mnt or str(path).startswith(
                mnt.rstrip("/") + "/")
            if inside and len(mnt) > len(best):
                best, fstype = mnt, typ
    return {"mount": best, "fstype": fstype,
            "free_bytes": shutil.disk_usage(path).free}


class CheckpointLoop(Loop):
    """Saves (``op: save``) or restores after node loss (``op: restore``)
    of a device-resident shard, back to back.

    The node files go to a new directory under ``$TMPDIR`` through the
    local blob backend, flushed as the configuration's ``writer`` says;
    ``close`` removes it.  A restore first deletes the files of the mix's
    next failed node set, then restores with those nodes failed, which
    rebuilds and rewrites them; ``expect_path`` names the restore path
    the mix exercises.  With ``host_chips`` > 1 the checkpointer encodes
    through ``StreamMesh(host_chips)``."""

    def setup(self, seconds: float) -> None:
        import jax
        from repro.checkpoint.msr_checkpoint import MSRCheckpointer
        from repro.io.blob import LocalBlob
        from repro.sharding.mesh import StreamMesh

        with self.phase("state"):
            self.state = make_state(self.config["leaves"], self.device_key(),
                                    self.host_chips)
            jax.block_until_ready(self.state)
        self.nbytes = int(sum(x.nbytes
                              for x in jax.tree_util.tree_leaves(self.state)))
        writer = self.config["writer"]
        self.root = pathlib.Path(tempfile.mkdtemp(prefix="chipbench_ckpt_"))
        # a host's chips: the program's stream-axis mesh over them
        mesh = StreamMesh(self.host_chips) if self.host_chips > 1 else None
        self.ckpt = MSRCheckpointer(self.root, self.spec(),
                                    io_backend=LocalBlob(fsync=writer["fsync"]),
                                    keep_last=writer["keep_last"], mesh=mesh)
        self.s = -(-self.nbytes // self.n)
        self.saved: dict[int, object] = {}      # step -> device state
        self.advance = advance_fn()
        with self.phase("warmup"):
            with self.span("save"):
                self.ckpt.save(0, self.state)
            self.saved[0] = self.state
            if self.mix["op"] == "save":
                jax.block_until_ready(self.advance(self.state))
            else:
                self._restore(0, self.mix["failed_nodes"][-1])

    def _files(self, step: int, i: int) -> tuple:
        """Node i's data and redundancy files at ``step``, as the
        checkpoint's on-disk layout names them."""
        d = self.root / f"step_{step:06d}"
        return d / f"node_{i:02d}.a.npy", d / f"node_{i:02d}.r.npz"

    def _kill(self, step: int, nodes) -> None:
        for i in nodes:
            for path in self._files(step, i):
                path.unlink()

    def _restore(self, step: int, nodes):
        self._kill(step, nodes)
        with self.span("restore"):
            return self.ckpt.restore(self.state, step, failed_nodes=nodes)

    def _save(self, step: int) -> None:
        import jax
        with self.span("advance"):
            self.state = self.advance(self.state)
            jax.block_until_ready(self.state)
        with self.span("save"):
            self.ckpt.save(step, self.state)
        self.saved[step] = self.state
        for old in sorted(self.saved)[:-self.ckpt.keep_last]:
            del self.saved[old]
        self.gf_bytes += work.encode(self.n, self.s)

    def _restore_next(self, i: int) -> None:
        nodes = self.mix["failed_nodes"][(i - 1) % len(self.mix["failed_nodes"])]
        tree, report = self._restore(0, nodes)
        self.restored.append((tree, report.path))
        if report.path == "regenerate":
            self.gf_bytes += work.regenerate(self.k, self.s)

    def window(self, seconds: float) -> float:
        one = self._save if self.mix["op"] == "save" else self._restore_next
        self.restored: list = []
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            i += 1
            start = time.perf_counter() - t0
            if not self.attempt(self.mix["op"], start, t0, self.nbytes,
                                lambda: one(i)):
                break
        self.facts.update(ops=len(self.ops),
                          op_s=[op.end - op.start for op in self.ops],
                          state_bytes=self.nbytes,
                          block_symbols=self.s,
                          steps_kept=self.ckpt.steps(),
                          ckpt_dir_disk=_disk(self.root))
        return t0

    def free(self) -> None:
        self.ckpt.close()

    def close(self) -> None:
        if hasattr(self, "root"):
            shutil.rmtree(self.root, ignore_errors=True)

    # ..................................................................
    def _node_arrays(self, step: int):
        """Per node: (data bytes, low bytes, positions of 256), read back
        from the committed files; None for a file that is not there."""
        out = []
        for i in range(1, self.n + 1):
            a_path, r_path = self._files(step, i)
            try:
                a = np.load(a_path)
                with np.load(r_path) as z:
                    low, hi = z["low"], z["hi"]
            except FileNotFoundError:
                out.append(None)
                continue
            out.append((a.reshape(-1), low.reshape(-1),
                        np.asarray(hi, np.int64).reshape(-1)))
        return out

    def _check_files(self, chk: Check, step: int, payload: np.ndarray,
                     chunk: int = 1 << 21) -> None:
        """The step's node files against the reference: every data file
        against the state's bytes, every redundancy file against the
        reference encode, and the any-k decode on three column windows."""
        c = self.spec().c
        blocks = reference.bytes_to_blocks(payload, self.n)
        nodes = self._node_arrays(step)
        chk.bump("missing_node_files", sum(x is None for x in nodes))
        if any(x is None for x in nodes):
            return
        s = blocks.shape[1]

        def chunk_bad(lo: int) -> tuple[int, int]:
            hi_ = min(s, lo + chunk)
            a = blocks[:, lo:hi_]
            r_ref = reference.encode(c, a, self.p)
            bad_a = bad_r = 0
            for i, (fa, low, pos) in enumerate(nodes):
                if fa.size != s or low.size != s:
                    bad_a += hi_ - lo
                    continue
                bad_a += int(np.count_nonzero(fa[lo:hi_] != a[i]))
                r = low[lo:hi_].astype(np.int32)
                r[pos[(pos >= lo) & (pos < hi_)] - lo] = 256
                bad_r += int(np.count_nonzero(r != r_ref[i]))
            return bad_a, bad_r

        # NumPy releases the GIL in these array passes: chunks in parallel
        with ThreadPoolExecutor(max_workers=8) as pool:
            counts = list(pool.map(chunk_bad, range(0, s, chunk)))
        bad_a = sum(a for a, _ in counts)
        bad_r = sum(r for _, r in counts)
        chk.bump("data_mismatch_bytes", bad_a)
        chk.bump("redundancy_mismatch_symbols", bad_r)
        w = min(4096, s)
        bad_d = 0
        for lo in sorted({0, (s - w) // 2, s - w}):
            a = np.stack([x[0][lo:lo + w] for x in nodes]).astype(np.int64)
            r = np.stack([reference.unpack_red(
                x[1][lo:lo + w], x[2][(x[2] >= lo) & (x[2] < lo + w)] - lo)
                for x in nodes])
            bad_d += reference.stripe_mismatches(c, a, r, self.p, self.rng)
        chk.bump("anyk_decode_mismatch_symbols", bad_d)

    def check(self) -> Check:
        chk = self.new_check()
        chk.add("missing_node_files", 0)
        if self.mix["op"] == "save":
            done = [i for i, op in enumerate(self.ops, 1) if op.ok]
            kept = done[-self.ckpt.keep_last:]
            chk.add("unkept_steps", len(set(kept) - set(self.ckpt.steps())))
            for step in kept:
                if step in self.ckpt.steps() and step in self.saved:
                    self._check_files(chk, step, state_bytes(self.saved[step]))
            return chk
        want = state_bytes(self.state)
        bad = wrong_path = 0
        for tree, path in self.restored:
            got = state_bytes(tree)
            bad += want.size if got.size != want.size \
                else int(np.count_nonzero(got != want))
            wrong_path += path != self.mix["expect_path"]
        chk.add("restored_mismatch_bytes", bad)
        chk.add("wrong_restore_path", wrong_path)
        self._check_files(chk, 0, want)
        return chk


LOOP = CheckpointLoop
