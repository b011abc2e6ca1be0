"""Small configurations for driving whole runs on the CPU in tests."""
from __future__ import annotations

import copy
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CPU_PEAKS = {"hbm_bytes_per_s": 819e9}
CELLS = [c["name"] for c in BENCH["workloads"]]


def config_of(cell: str) -> dict:
    from chipbench import harness
    _cell, config, _mix = harness.resolve(BENCH, cell)
    return copy.deepcopy(config)


def tiny_config(cell: str) -> dict:
    """The cell's configuration at a size a test run can hold: a few
    small leaves of the checkpoint shard."""
    cfg = config_of(cell)
    keep = [leaf for leaf in cfg["leaves"]
            if leaf["path"].endswith(("final_norm/scale", "norm1/scale",
                                      "step", "attn/wk"))]
    for leaf in keep:
        if leaf["path"].endswith("attn/wk"):
            leaf["shape"] = [2, 64, 8, 64]
    cfg["leaves"] = keep
    return cfg
