"""Small configurations for driving whole runs on the CPU in tests."""
from __future__ import annotations

import copy
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CPU_PEAKS = {"hbm_bytes_per_s": 819e9}
SEED = 2**31 + 11          # above 32 signed bits, as the driver's are
CELLS = [c["name"] for c in BENCH["workloads"]]
ONE_CHIP = [c["name"] for c in BENCH["workloads"] if c["chips"] == 1]
MULTI_CHIP = [c["name"] for c in BENCH["workloads"] if c["chips"] > 1]


def config_of(cell: str) -> dict:
    from chipbench import harness
    _cell, config, _mix = harness.resolve(BENCH, cell)
    return copy.deepcopy(config)


def tiny_config(cell: str) -> dict:
    """The cell's configuration at a size a test run can hold: a few
    small leaves of the checkpoint shard (of each chip's shard, where
    the configuration spans a host's chips)."""
    cfg = config_of(cell)
    host_chips = cfg.get("host_chips", 1)
    keep = [leaf for leaf in cfg["leaves"]
            if leaf["path"].endswith(("final_norm/scale", "norm1/scale",
                                      "step", "attn/wk"))]
    for leaf in keep:
        if leaf["path"].endswith("attn/wk"):
            leaf["shape"] = [2, 64 * host_chips, 8, 64]
    cfg["leaves"] = keep
    return cfg
