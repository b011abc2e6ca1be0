"""Multi-device sharded encode/repair scaling over the stream mesh
(DESIGN.md §14).

Measures circulant encode and fused batched regeneration throughput at
mesh sizes 1/2/4/8, asserts every sharded result bit-exact against the
unsharded planner BEFORE timing, and asserts zero steady-state
recompiles on every sharded plan.

The headline scaling claim is asserted in-bench where the numbers are
made: with >= 4 host cores (every CI runner), 4-device encode must be
>= 2x single-device.  On a core-starved host (this includes 1-core dev
containers) the XLA CPU client cannot run the shards in parallel, so
real 2x scaling is PHYSICALLY unavailable; the bench then asserts the
weaker invariant that sharding never regresses below single-device
(the per-shard working sets are smaller, which is worth ~1.7x even
serialized) and records ``scaling_asserted: false`` with the reason —
an honest number beats a lucky one.

Ratios use ALTERNATING paired rounds (same rationale as
bench_regeneration._timeit_pair): on burstable hosts, timing one side
to completion and then the other skews the ratio by whichever capacity
window each phase landed in.

The bench runs in the calling process over ``jax.devices()`` (a chip
belongs to one process, so it never respawns itself): mesh sizes larger
than the device count are skipped.  On a CPU host, set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before the first
jax import to get eight virtual devices.
"""
import json
import os
import time

import jax
import numpy as np

from benchmarks import _timing
from repro.core.circulant import CodeSpec
from repro.exec import plan
from repro.kernels import dispatch

MESHES = (1, 2, 4, 8)


def _timeit_pair(fn_a, fn_b, reps=2, rounds=10):
    """Best-of timing of two alternatives in alternating rounds."""
    jax.block_until_ready(fn_a())          # warm-up: compile + first call
    jax.block_until_ready(fn_b())
    best_a = best_b = float("inf")
    for _ in range(rounds):
        for which, fn in ((0, fn_a), (1, fn_b)):
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn()
            jax.block_until_ready(out)
            t = (time.perf_counter() - t0) / reps
            if which == 0:
                best_a = min(best_a, t)
            else:
                best_b = min(best_b, t)
    return best_a, best_b


def run(fast: bool = False, quiet: bool = False) -> dict:
    k = 8
    enc_symbols = 1 << 20       # large enough that shards beat one body
    rep_symbols = 1 << 18
    rounds = 4 if fast else 10
    spec = CodeSpec.make(k, 257)
    n = spec.n
    c = tuple(int(x) for x in spec.c)
    be = dispatch.select(257, k)
    rng = _timing.rng()
    data = rng.integers(0, 257, (n, enc_symbols), dtype=np.int64
                        ).astype(np.int32)
    rmat = rng.integers(0, 257, (2, k + 1), dtype=np.int64).astype(np.int32)
    rprev = rng.integers(0, 257, (2, rep_symbols), dtype=np.int64
                         ).astype(np.int32)
    downs = rng.integers(0, 257, (2, k, rep_symbols), dtype=np.int64
                         ).astype(np.int32)
    enc_mb = n * enc_symbols / 2**20
    rep_mb = 2 * k * rep_symbols / 2**20

    ref = plan.get_planner(be, 257)
    want_enc = ref.circulant_encode(data, c).host()
    want_reg = ref.regenerate_batch(rmat, rprev, downs).host()

    cpus = os.cpu_count() or 1
    n_dev = len(jax.devices())
    rec = {"n_devices": n_dev, "host_cpus": cpus,
           "platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind,
           "k": k, "n": n, "enc_stream_mb": round(enc_mb, 2),
           "backend": be.name, "encode": [], "repair": []}
    for m in (m for m in MESHES if m <= n_dev):
        pl = plan.get_planner(be, 257, mesh=m)
        # bit-exact parity gates the timing: a wrong fast number is
        # worse than no number
        np.testing.assert_array_equal(
            pl.circulant_encode(data, c).host(), want_enc,
            err_msg=f"sharded encode diverges at mesh={m}")
        np.testing.assert_array_equal(
            pl.regenerate_batch(rmat, rprev, downs).host(), want_reg,
            err_msg=f"sharded regenerate diverges at mesh={m}")
        pl.reset_stats()
        # .raw is the device array; PlanResult itself is an opaque leaf
        # jax.block_until_ready would silently NOT block on
        t1, tm = _timeit_pair(
            lambda: ref.circulant_encode(data, c).raw,
            lambda: pl.circulant_encode(data, c).raw, rounds=rounds)
        r1, rm = _timeit_pair(
            lambda: ref.regenerate_batch(rmat, rprev, downs).raw,
            lambda: pl.regenerate_batch(rmat, rprev, downs).raw,
            rounds=max(4, rounds // 2))
        st = pl.plan_stats()
        if m > 1:
            assert st.compiles == 0 and st.misses == 0, (m, st)
        rec["encode"].append({"mesh": m, "s": round(tm, 5),
                              "mbps": round(enc_mb / tm, 1),
                              "speedup_vs_1dev": round(t1 / tm, 2)})
        rec["repair"].append({"mesh": m, "s": round(rm, 5),
                              "mbps": round(rep_mb / rm, 1),
                              "speedup_vs_1dev": round(r1 / rm, 2)})
    rec["parity_ok"] = True
    rec["steady_recompiles"] = 0
    speedup4 = next((r["speedup_vs_1dev"] for r in rec["encode"]
                     if r["mesh"] == 4), None)
    rec["encode_speedup_4dev"] = speedup4
    rec["scaling_asserted"] = speedup4 is not None and cpus >= 4
    if speedup4 is None:
        rec["scaling_skip_reason"] = f"only {n_dev} device(s): no 4-way mesh"
    elif cpus >= 4:
        assert speedup4 >= 2.0, \
            f"4-device encode only {speedup4}x single-device (need >= 2x)"
    else:
        # shards can't run in parallel on < 4 cores; hold the weaker bar
        rec["scaling_skip_reason"] = (
            f"host has {cpus} core(s): 4 shards serialize, 2x parallel "
            f"scaling physically unavailable; asserted no-regression "
            f"instead")
        assert speedup4 >= 1.0, \
            f"4-device encode regressed to {speedup4}x single-device"
    if not quiet:
        for erow, rrow in zip(rec["encode"], rec["repair"]):
            print(f"  mesh={erow['mesh']}: encode {erow['mbps']} MB/s "
                  f"({erow['speedup_vs_1dev']}x), repair {rrow['mbps']} "
                  f"MB/s ({rrow['speedup_vs_1dev']}x)")
    return rec


if __name__ == "__main__":
    print(json.dumps(run(), indent=1))
