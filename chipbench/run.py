#!/usr/bin/env python
"""Run one benchmark cell on the accelerator and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics come from
``BENCHMARK.json`` at the root of the checkout.  The run refuses, with a
non-zero exit and no result, when JAX finds no TPU or fewer chips than
the cell asks for.  JAX's persistent compilation cache lives in
``.jax_cache`` at the root of the checkout, a fixed path, so only the
first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(platform: str, found: int, asked: int) -> None:
    """Refuse a run without a TPU or with too few chips."""
    if platform != "tpu":
        raise SystemExit(f"chipbench: no TPU (JAX found {platform})")
    if found < asked:
        raise SystemExit(f"chipbench: {asked} chips asked, {found} found")


def use_cache_dir(jax) -> None:
    """Point JAX's persistent compilation cache at the checkout's fixed
    directory, whatever the environment says, before the first compile."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def main(argv=None) -> int:
    args = parse(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import harness

    cell = harness.find_cell(bench, args.workload)
    import jax
    devices = jax.devices()
    require_chips(devices[0].platform, len(devices), cell["chips"])
    use_cache_dir(jax)
    from repro.exec.compile_cache import enable_compile_cache
    harness.info(compile_cache=enable_compile_cache(),
                 devices=[f"{d.platform}:{d.device_kind}" for d in devices])
    result = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
