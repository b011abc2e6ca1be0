#!/usr/bin/env python
"""Smoke run of the MSR checkpointer and the coded object store on a TPU.

    python chip_smoke.py             # one chip: checkpoint and store phases
    python chip_smoke.py --chips 4   # four chips: the stream-mesh path only

One chip, two phases, one process:

* checkpoint -- the repo's training client at the ``100m`` preset of
  ``examples/train_tiny_lm.py`` (12 layers, d_model 768, vocab 8192:
  about 0.1 B parameters, about 1 GB of parameters plus AdamW state held
  in HBM) takes 3 steps; ``MSRCheckpointer`` over the [8, 4] code saves
  the device state, restores it once through single-node regeneration
  and once through any-k reconstruction with n - k nodes gone, and one
  more step from the restored state must equal the step from the
  original, bit for bit;
* store -- at least 256 MiB of seeded objects (4 KiB .. 16 MiB) go into a
  12-node, 4-rack ``CodedObjectStore``; a whole rack fails; every object
  is read back through ``ReadFrontEnd``; the ``RepairScheduler`` drains
  and ``store.verify()`` must hold.

Four chips: the store phase and the checkpoint save/restore run under
``use_mesh(StreamMesh(4))`` and are compared bit for bit with the same
operations run with no mesh, in the same process; the sharded outputs
must span four distinct devices.

Every phase is also checked on a sample of stripes against a plain NumPy
int64 GF(257) encode and any-k decode that shares no code with
``repro.kernels``.  The script fails, and prints no result line, unless
JAX finds a TPU and the GF backend is the native Pallas one.  Earlier
lines report each phase; the last line of standard output is
``{"ok": true, "device": {...}}``.  The persistent compilation cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import resource
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

P = 257
K = 4
SEED = 0
TRAIN_STEPS = 3
STORE_BYTES = 256 << 20
OBJ_MIN, OBJ_MAX = 4 << 10, 16 << 20
SAMPLE_STRIPES = 16
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
T0 = time.perf_counter()


class SmokeError(RuntimeError):
    """A check of the smoke run failed."""


def expect(cond, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def peak_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def log(msg: str) -> None:
    """Progress to standard error; standard output carries the results."""
    print(f"[{time.perf_counter() - T0:8.2f}s peak rss "
          f"{peak_rss() / 2**30:5.1f} GiB] {msg}", file=sys.stderr,
          flush=True)


# --------------------------------------------------------------- reference
def ref_rows(c, p: int = P) -> np.ndarray:
    """(n, n) int64: row i-1 holds the coefficients of r_i over a_0..a_{n-1},
    r_i = sum_{u=1..k} c_u a_{(i-k-u) mod n} (paper eq. (2))."""
    k = len(c)
    n = 2 * k
    g = np.zeros((n, n), np.int64)
    for i in range(1, n + 1):
        for u in range(1, k + 1):
            g[i - 1, (i - k - u) % n] += int(c[u - 1])
    return g % p


def ref_solve(mat: np.ndarray, rhs: np.ndarray, p: int = P) -> np.ndarray:
    """Gauss-Jordan solve of mat @ x = rhs over GF(p), int64 throughout."""
    m = np.concatenate([mat % p, rhs % p], axis=1).astype(np.int64)
    n = mat.shape[0]
    for col in range(n):
        piv = next((r for r in range(col, n) if m[r, col]), None)
        expect(piv is not None, "reference decode: singular system")
        m[[col, piv]] = m[[piv, col]]
        m[col] = m[col] * pow(int(m[col, col]), p - 2, p) % p
        for r in range(n):
            if r != col and m[r, col]:
                m[r] = (m[r] - m[r, col] * m[col]) % p
    return m[:, n:]


def ref_check_stripe(a: np.ndarray, r: np.ndarray, c, rng,
                     what: str) -> None:
    """``a``/``r``: the (n, W) data and redundancy blocks of one stripe.
    Raises unless r is the reference encode of a and a random k of the n
    node pairs decode back to a."""
    n = 2 * len(c)
    a = np.asarray(a, np.int64)
    r = np.asarray(r, np.int64)
    g = ref_rows(c)
    expect(np.array_equal(g @ a % P, r), f"{what}: redundancy != reference")
    nodes = np.sort(rng.choice(n, size=n // 2, replace=False))
    mat = np.concatenate([np.eye(n, dtype=np.int64)[nodes], g[nodes]])
    dec = ref_solve(mat, np.concatenate([a[nodes], r[nodes]]))
    expect(np.array_equal(dec, a),
           f"{what}: reference any-k decode from nodes {nodes + 1} != data")


# ------------------------------------------------------------ measurement
class Clock:
    """Phase clock: wall time, set-up time, and the backend compiles JAX
    reports: seconds (a persistent-cache hit counts only its retrieval),
    executables compiled, and executables read from the cache."""

    compile_s = 0.0
    requests = 0
    cache_hits = 0

    @classmethod
    def install(cls) -> None:
        def on_duration(event, secs, **_):
            if event == BACKEND_COMPILE_EVENT:
                cls.compile_s += secs
                cls.requests += 1

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                cls.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def __init__(self, phase: str):
        self.phase = phase
        self.t0 = time.perf_counter()
        self.c0 = self.totals()
        self.setup_s = 0.0

    @staticmethod
    def totals() -> tuple:
        return Clock.compile_s, Clock.requests, Clock.cache_hits

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t0

    def report(self, **fields) -> dict:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        secs, requests, hits = (now - then for now, then
                                in zip(self.totals(), self.c0))
        rec = {"phase": self.phase,
               "wall_s": time.perf_counter() - self.t0,
               "setup_s": self.setup_s,
               "compile_s": secs,
               "compiles": requests - hits,
               "cache_hits": hits,
               **fields,
               "peak_host_rss_bytes": peak_rss(),
               "peak_device_bytes_in_use": peaks}
        print(json.dumps(rec), flush=True)
        return rec


def require_native(code, what: str) -> None:
    """The code's GF backend must be the native Pallas one: named
    ``pallas`` and every planned executable lowered to a Mosaic kernel."""
    expect(code.backend_name == "pallas",
           f"{what}: GF backend is {code.backend_name!r}, not 'pallas'")
    exes = list(code.planner._plans.values())
    expect(exes, f"{what}: no planned executable ran")
    expect(all("tpu_custom_call" in e.as_text() for e in exes),
           f"{what}: a planned executable has no native TPU kernel")


# ------------------------------------------------------------- trees
def same_tree(a, b) -> bool:
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb or len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.shape != y.shape:
            return False
        if not np.array_equal(x.reshape(-1).view(np.uint8),
                              y.reshape(-1).view(np.uint8)):
            return False
    return True


def tree_bytes(tree) -> int:
    return sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(tree))


# -------------------------------------------------------- checkpoint phase
def build_trainer(preset: str = "100m", seed: int = SEED):
    """The training client of ``examples/train_tiny_lm.py`` at ``preset``:
    (device state, jitted donating step, batch-for-step)."""
    from examples.train_tiny_lm import PRESETS
    from repro.configs import get_config
    from repro.data import pipeline
    from repro.launch.steps import make_train_step
    from repro.models import Model
    from repro.optim import adamw
    from repro.train.loop import init_state

    pre = PRESETS[preset]
    cfg = get_config("paper-tiny-lm").reduced(**pre["model"])
    model = Model(cfg)
    opt = adamw.AdamWConfig(lr=3e-3, warmup_steps=1,
                            total_steps=pre["steps"])
    step_fn = jax.jit(make_train_step(model, opt), donate_argnums=(0,))
    dcfg = pipeline.DataConfig(vocab_size=cfg.vocab_size,
                               seq_len=pre["seq"],
                               global_batch=pre["batch"], seed=seed)

    def batch(step: int) -> dict:
        return {k: jnp.asarray(v)
                for k, v in pipeline.batch_at(dcfg, step).items()}

    return init_state(model, opt, seed), step_fn, batch


def ref_check_checkpoint(ckpt, step: int, rng, width: int = 4096) -> None:
    """Reference-check three column windows of a committed step, read
    straight from its node files with the on-disk format decoded here
    (data bytes; redundancy as low bytes plus the positions of 256)."""
    n = ckpt.spec.n
    a_path, _ = ckpt._node_files(step, 1)
    s = np.load(a_path, mmap_mode="r").shape[0]
    los = sorted({0, s // 2, max(0, s - width)})
    a = np.zeros((len(los), n, width), np.int64)
    r = np.zeros((len(los), n, width), np.int64)
    for i in range(n):
        a_path, r_path = ckpt._node_files(step, i + 1)
        data = np.load(a_path, mmap_mode="r")
        with np.load(r_path) as z:
            low, hi = z["low"], z["hi"]
        for w, lo in enumerate(los):
            a[w, i, :len(data[lo:lo + width])] = data[lo:lo + width]
            r[w, i, :len(low[lo:lo + width])] = low[lo:lo + width]
            sel = hi[(hi >= lo) & (hi < lo + width)]
            r[w, i, sel - lo] = 256
    for w, lo in enumerate(los):
        ref_check_stripe(a[w], r[w], ckpt.spec.c, rng,
                         f"checkpoint step {step} @{lo}")


def same_node_files(x, y, step: int) -> bool:
    """Two checkpointers committed identical node contents at ``step``."""
    for i in range(1, x.spec.n + 1):
        (xa, xr), (ya, yr) = x._node_files(step, i), y._node_files(step, i)
        if not np.array_equal(np.load(xa), np.load(ya)):
            return False
        with np.load(xr) as zx, np.load(yr) as zy:
            if not (np.array_equal(zx["low"], zy["low"])
                    and np.array_equal(zx["hi"], zy["hi"])):
                return False
    return True


def save_and_restore(ckpt, step: int, state, host, rng) -> dict:
    """Save ``state``; restore once through regeneration (one node's
    files gone) and once through any-k reconstruction (n - k nodes'
    files gone); both must equal ``host``.  Returns byte counts and the
    reconstructed state."""
    n, k = ckpt.spec.n, ckpt.spec.k
    ckpt.save(step, state)
    log(f"saved step {step}")
    stored = sum(f.stat().st_size
                 for f in ckpt._step_dir(step).iterdir())
    ref_check_checkpoint(ckpt, step, rng)
    log("checked the saved node files against the reference")

    def kill(nodes):
        for i in nodes:
            for path in ckpt._node_files(step, i):
                path.unlink()

    kill([3])
    regen, rep1 = ckpt.restore(host, step, failed_nodes=[3])
    expect(rep1.path == "regenerate", f"restore took {rep1.path}")
    expect(same_tree(regen, host), "regenerated state != device state")
    del regen
    log("restored through regeneration")
    lost = list(range(2, 2 + n - k))
    kill(lost)
    recon, rep2 = ckpt.restore(host, step, failed_nodes=lost)
    expect(rep2.path == "reconstruct", f"restore took {rep2.path}")
    expect(same_tree(recon, host), "reconstructed state != device state")
    log("restored through any-k reconstruction")
    ref_check_checkpoint(ckpt, step, rng)      # the rewritten nodes too
    return {"state_bytes": tree_bytes(host), "stored_bytes": stored,
            "regenerate_read_bytes": rep1.bytes_read,
            "reconstruct_read_bytes": rep2.bytes_read,
            "restored": recon}


def checkpoint_phase(workdir, *, preset: str = "100m", native: bool = True):
    from repro.checkpoint.msr_checkpoint import MSRCheckpointer
    from repro.core.circulant import CodeSpec

    clock = Clock("checkpoint")
    rng = np.random.default_rng(SEED)
    state, step_fn, batch = build_trainer(preset)
    ckpt = MSRCheckpointer(workdir, CodeSpec.make(K, P))
    clock.setup_done()
    for s in range(TRAIN_STEPS):
        state, _ = step_fn(state, batch(s))
    host = jax.device_get(state)
    log(f"trained {TRAIN_STEPS} steps")
    res = save_and_restore(ckpt, TRAIN_STEPS, state, host, rng)
    orig, _ = step_fn(state, batch(TRAIN_STEPS))
    again, _ = step_fn(jax.device_put(res.pop("restored")),
                       batch(TRAIN_STEPS))
    expect(same_tree(jax.device_get(orig), jax.device_get(again)),
           "step from the restored state != step from the original")
    if native:
        require_native(ckpt.code, "checkpoint")
    return clock.report(backend=ckpt.code.backend_name, **res)


# ------------------------------------------------------------- store phase
def seeded_objects(total: int, seed: int = SEED) -> dict[str, bytes]:
    """Objects of log-uniform size in [OBJ_MIN, OBJ_MAX] until ``total``
    bytes, cut from one seeded buffer."""
    rng = np.random.default_rng(seed)
    sizes = []
    while sum(sizes) < total:
        sizes.append(int(math.exp(rng.uniform(math.log(OBJ_MIN),
                                              math.log(OBJ_MAX)))))
    buf = rng.bytes(sum(sizes))
    objs, off = {}, 0
    for i, size in enumerate(sizes):
        objs[f"obj{i:05d}"] = buf[off:off + size]
        off += size
    return objs


def stripe_blocks(store, key: str, t: int):
    """(n, S) data and redundancy blocks of one stripe, by code node."""
    shares = sorted(store.read_share(phys, key, t)
                    for phys in store.placement_of(key, t))
    return (np.stack([s[1] for s in shares]),
            np.stack([s[2] for s in shares]))


def ref_check_store(store, rng, what: str) -> None:
    refs = list(store.stripe_refs())
    for j in rng.choice(len(refs), size=min(SAMPLE_STRIPES, len(refs)),
                        replace=False):
        key, t = refs[j]
        a, r = stripe_blocks(store, key, t)
        ref_check_stripe(a, r, store.spec.c, rng, f"{what} {key}#{t}")


def store_phase(total: int = STORE_BYTES, *, native: bool = True):
    from repro.core.circulant import CodeSpec
    from repro.serve.frontend import ReadFrontEnd
    from repro.sharding.mesh import current_mesh
    from repro.store import CodedObjectStore, RepairScheduler

    mesh = current_mesh()
    clock = Clock("store" if mesh is None else f"store-mesh{mesh.size}")
    rng = np.random.default_rng(SEED)
    objs = seeded_objects(total)
    store = CodedObjectStore(CodeSpec.make(K, P), n_nodes=12, n_racks=4)
    sched = RepairScheduler(store)
    store.subscribe(sched.on_event)
    clock.setup_done()
    for key, payload in objs.items():
        store.put(key, payload)
    ref_check_store(store, rng, "put")
    log(f"put {len(objs)} objects")
    rack = store.layout.nodes_in(0)
    for node in rack:
        store.fail_node(node)
    with ReadFrontEnd(store, scheduler=sched) as fe:
        keys = list(objs)
        for lo in range(0, len(keys), fe.max_queue):
            tickets = [fe.submit(key) for key in keys[lo:lo + fe.max_queue]]
            fe.pump()
            for tk in tickets:
                expect(tk.result() == objs[tk.key],
                       f"read of {tk.key} != its payload")
        served = fe.metrics.summary()
    log("read every object through the front end")
    drain = sched.drain_all()
    log(f"drained {drain.repaired_stripes} stripe repairs")
    expect(sched.pending() == 0, "repair queue not drained")
    expect(store.verify(), "store.verify() failed after the drain")
    ref_check_store(store, rng, "repaired")
    if native:
        require_native(store.code, "store")
    rec = clock.report(
        backend=store.code.backend_name, objects=len(objs),
        put_bytes=sum(map(len, objs.values())),
        failed_nodes=list(rack),
        degraded_stripes=served["degraded_stripes"],
        decode_dispatches=served["decode_dispatches"],
        repaired_stripes=drain.repaired_stripes,
        repair_symbols_moved=drain.symbols_moved)
    return store, rec


# --------------------------------------------------------- four-chip path
def same_store_shares(a, b) -> bool:
    refs = list(a.stripe_refs())
    if refs != list(b.stripe_refs()):
        return False
    for key, t in refs:
        for x, y in zip(stripe_blocks(a, key, t), stripe_blocks(b, key, t)):
            if not np.array_equal(x, y):
                return False
    return True


def spans(code, n_dev: int) -> bool:
    """A real planned encode through ``code``'s planner lands its output
    on ``n_dev`` distinct devices."""
    data = np.arange(code.n * 4096, dtype=np.int32).reshape(code.n, -1) % P
    res = code.encode_planned(data)
    ok = len(res.raw.sharding.device_set) == n_dev
    return ok and np.array_equal(res.host(), ref_rows(code.spec.c) @ data % P)


def mesh_phase(workdir, n_chips: int, *, preset: str = "100m",
               total: int = STORE_BYTES, native: bool = True):
    """The store phase and the checkpoint save/restore, once with no
    mesh and once under ``use_mesh(StreamMesh(n_chips))``, compared."""
    from repro.checkpoint.msr_checkpoint import MSRCheckpointer
    from repro.core.circulant import CodeSpec
    from repro.sharding.mesh import StreamMesh, use_mesh

    mesh = StreamMesh(n_chips)
    expect(len(set(mesh.devices)) == n_chips, "mesh devices not distinct")
    plain_store, _ = store_phase(total, native=native)
    with use_mesh(mesh):
        mesh_store, _ = store_phase(total, native=native)
    expect(same_store_shares(plain_store, mesh_store),
           "sharded store shares != unsharded store shares")
    expect(spans(mesh_store.code, n_chips),
           f"store encode does not span {n_chips} devices")

    clock = Clock(f"checkpoint-mesh{n_chips}")
    rng = np.random.default_rng(SEED)
    state, _, _ = build_trainer(preset)
    host = jax.device_get(state)
    spec = CodeSpec.make(K, P)
    plain = MSRCheckpointer(pathlib.Path(workdir) / "plain", spec)
    with use_mesh(mesh):
        meshed = MSRCheckpointer(pathlib.Path(workdir) / "mesh", spec)
    clock.setup_done()
    plain.save(0, state)
    res = save_and_restore(meshed, 0, state, host, rng)
    res.pop("restored")
    expect(same_node_files(plain, meshed, 0),
           "sharded checkpoint node files != unsharded ones")
    expect(spans(meshed.code, n_chips),
           f"checkpoint encode does not span {n_chips} devices")
    if native:
        require_native(meshed.code, "checkpoint under the mesh")
    return clock.report(backend=meshed.code.backend_name,
                        mesh_devices=[d.id for d in mesh.devices], **res)


# -------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip stream-mesh path")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 2
    env = os.environ.get("REPRO_GF_BACKEND")
    if env not in (None, "", "pallas"):
        print(f"chip_smoke: REPRO_GF_BACKEND={env!r} pins a non-native "
              f"backend", file=sys.stderr)
        return 2

    jax.block_until_ready(jnp.zeros(8) + 1)
    log(f"runtime up on {len(devices)} x {dev.device_kind}")
    from repro.exec.compile_cache import enable_compile_cache
    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    Clock.install()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        if args.chips == 1:
            checkpoint_phase(work)
            store_phase()
        else:
            mesh_phase(work, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
