#!/usr/bin/env python
"""The control of ``correct``: the plain reference in the program's place,
with each GF(257) symbol held in one byte, and the run's comparison has
to read it as wrong.

    python chipbench/control.py --workload <cell> --seeds 5,6,7 --seconds 5

Every planned GF operation of the program (encode, decode, regenerate,
their batched forms) is replaced by ``reference.mat_mod`` with
``symbol_bits=8``: the shortcut that stores a symbol in a byte and so
turns the field's value 256 into 0.  The rest of the program runs as it
is.  For each seed the cell is set up and run for a short window at its
own size, and the numbers the run compares are printed beside their
limits; a control that reads as correct on any seed exits non-zero.
The benchmark's own runs never take this path.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from chipbench.run import ROOT, require_chips, use_cache_dir  # noqa: E402


@contextlib.contextmanager
def control_patch():
    """Every planned GF op of ``repro.exec.plan.PlanCache`` computed by
    the reference with one-byte symbols."""
    from chipbench import reference
    from repro.exec import plan

    def mm(pc, mat, x):
        return reference.mat_mod(mat, x, pc.p, symbol_bits=8)

    def matmul(self, mat, blocks, *, tag=None):
        blocks = np.asarray(blocks)
        return plan.PlanResult(mm(self, mat, blocks), blocks.shape[-1])

    def circulant_encode(self, data, c, *, tag=None):
        data = np.asarray(data)
        return plan.PlanResult(
            mm(self, reference.gen_rows(c, self.p), data), data.shape[-1])

    def regenerate(self, rmat, r_prev, next_data):
        x = np.concatenate([np.asarray(r_prev)[None], np.asarray(next_data)])
        return plan.PlanResult(mm(self, rmat, x), x.shape[-1])

    def regenerate_batch(self, rmat, r_prevs, next_data):
        out = np.stack([mm(self, rmat, np.concatenate([rp[None], nd]))
                        for rp, nd in zip(np.asarray(r_prevs),
                                          np.asarray(next_data))])
        return plan.PlanResult(out, out.shape[-1], batch=out.shape[0])

    def matmul_batch(self, mats, blocks, *, tag=None):
        out = np.stack([mm(self, m, b) for m, b in zip(np.asarray(mats),
                                                       np.asarray(blocks))])
        return plan.PlanResult(out, out.shape[-1], batch=out.shape[0])

    saved = {name: getattr(plan.PlanCache, name) for name in
             ("matmul", "circulant_encode", "regenerate", "regenerate_batch",
              "matmul_batch")}
    patched = {"matmul": matmul, "circulant_encode": circulant_encode,
               "regenerate": regenerate, "regenerate_batch": regenerate_batch,
               "matmul_batch": matmul_batch}
    try:
        for name, fn in patched.items():
            setattr(plan.PlanCache, name, fn)
        yield
    finally:
        for name, fn in saved.items():
            setattr(plan.PlanCache, name, fn)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from chipbench import harness
    from repro.exec.compile_cache import enable_compile_cache

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.find_cell(bench, args.workload)
    devices = jax.devices()
    require_chips(devices[0].platform, len(devices), cell["chips"])
    use_cache_dir(jax)
    enable_compile_cache()
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        with control_patch():
            res = harness.run_cell(bench, args.workload, seed, args.seconds,
                                   False, time.perf_counter())
        harness.info(control_seed=seed, correct=res["correct"],
                     checks=res["checks"])
        passed.append(res["correct"])
    return 1 if any(passed) else 0


if __name__ == "__main__":
    sys.exit(main())
