"""Every case of ``test_runs.py`` for the cells that span several chips,
run in one process on virtual CPU devices, one JSON line of results.

    python -m chipbench.tests.multichip <cell> <tmp dir>

The parent sets ``XLA_FLAGS=--xla_force_host_platform_device_count=<n>``
before this process starts, so JAX sees the cell's chips.  Each case's
result is the run's result line, or ``{"raised": ...}`` where the run
raised.  Beside them, ``layout`` says how the clean run's states and
planned encodes lay over the devices, and ``fsync_facts`` what the run
with its own ``$TMPDIR`` fsync'd and left there.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import tempfile
import time
import traceback


def run(cell: str, seconds: float = 0.5) -> dict:
    from chipbench import harness
    from chipbench.tests import tiny
    return harness.run_cell(tiny.BENCH, cell, tiny.SEED, seconds, False,
                            time.perf_counter(),
                            config=tiny.tiny_config(cell),
                            peaks=tiny.CPU_PEAKS)


def clean(cell: str) -> tuple:
    """A clean run, and its layout: the devices each state leaf and
    each planned encode's output lie on, and each leaf's first shard's
    shape by path."""
    import jax
    from chipbench import generator
    from chipbench.tests import faults
    from repro.exec import plan

    loops, encodes = [], []
    make_loop = generator.make_loop

    def keep_loop(*a, **k):
        loops.append(make_loop(*a, **k))
        return loops[-1]

    def keep_encode(old):
        def encode(self, *a, **k):
            res = old(self, *a, **k)
            encodes.append(len(res.raw.sharding.device_set))
            return res
        return encode

    generator.make_loop = keep_loop
    try:
        with faults.patched(plan.PlanCache, "circulant_encode", keep_encode):
            res = run(cell)
    finally:
        generator.make_loop = make_loop
    states = [loops[0].state] + list(loops[0].saved.values())
    leaves, _ = jax.tree_util.tree_flatten_with_path(states[0])
    return res, {
        "leaf_devices": [len(x.sharding.device_set) for s in states
                         for x in jax.tree_util.tree_leaves(s)],
        "shard_shapes": {"/".join(k.key for k in path):
                         list(x.addressable_shards[0].data.shape)
                         for path, x in leaves},
        "encode_devices": encodes}


def fsynced(cell: str, tmp: pathlib.Path) -> tuple:
    """A clean run with ``$TMPDIR`` at ``tmp``, the fsync calls it made
    and what it left there."""
    tmp.mkdir(parents=True, exist_ok=True)
    tempfile.tempdir = str(tmp)
    synced = []
    real_fsync = os.fsync
    os.fsync = lambda fd: (synced.append(fd), real_fsync(fd))[1]
    try:
        res = run(cell)
    finally:
        os.fsync = real_fsync
        tempfile.tempdir = None
    return res, {"fsyncs": len(synced),
                 "left": sorted(p.name for p in tmp.iterdir())}


def planted(cell: str, fault: str) -> dict:
    from chipbench import control
    from chipbench.tests import faults

    plant = {"control": control.control_patch,
             "unchanged": faults.UNCHANGED.get(cell)}.get(fault) \
        or getattr(faults, fault)
    with plant():
        try:
            return run(cell, seconds=2 if fault == "unchanged" else 0.5)
        except Exception:
            return {"raised": traceback.format_exc()}


def main(argv: list) -> None:
    cell, tmp = argv[0], pathlib.Path(argv[1])
    import jax
    out = {"devices": len(jax.devices())}
    out["clean"], out["layout"] = clean(cell)
    out["fsync"], out["fsync_facts"] = fsynced(cell, tmp / "tmpdir")
    for fault in ("control", "answer_altered", "half_left_out", "unchanged",
                  "shards_left_out"):
        out[fault] = planted(cell, fault)
    print(json.dumps(out, default=str))


if __name__ == "__main__":
    main(sys.argv[1:])
