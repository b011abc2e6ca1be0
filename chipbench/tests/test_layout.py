"""BENCHMARK.json resolves, by name alone, to the files of each cell."""
import json
import re

import pytest

from chipbench import harness
from chipbench.tests.tiny import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [c["name"] for c in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for path in BENCH["paths"]:
        assert (ROOT / path).is_dir()
    for word in BENCH["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


def test_names_units_and_entry_keys():
    seen = set()
    for entry in BENCH["configs"] + BENCH["workloads"] + \
            BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
        assert entry["name"] not in seen
        seen.add(entry["name"])
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    w, config, mix = harness.resolve(BENCH, cell)
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert config["name"] == w["config"]
    assert set(entry["reduced"]) == set(config["reduced"])
    assert mix["loop"] in __import__("chipbench.generator").generator.LOOPS
    e2e = harness.cell_metrics(BENCH, cell, "end_to_end")
    per = harness.cell_metrics(BENCH, cell, "per_layer")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert per
    for m in e2e + per:
        assert callable(harness.load_reader(m["name"]))
    for m in per:
        assert m["moves"] in [e["name"] for e in e2e]


def test_every_config_has_a_cell_and_its_own_file():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_layers_are_named_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert f"`{layer}`" in perf, layer


def test_checkpoint_shard_is_one_sixteenth_of_the_state():
    cfg = json.loads((ROOT / "chipbench/configs/"
                      "ckpt-granite1b-fsdp16-dc8x4.json").read_text())
    total = 0
    for leaf in cfg["leaves"]:
        full, shard = leaf["full_shape"], leaf["shape"]
        if leaf["shard_axis"] is not None:
            ax = leaf["shard_axis"]
            assert shard[ax] == -(-full[ax] // cfg["fsdp_shards"])
            assert shard[:ax] + shard[ax + 1:] == full[:ax] + full[ax + 1:]
        n = 4
        for x in shard:
            n *= x
        total += n
    assert total == cfg["state_bytes"]
