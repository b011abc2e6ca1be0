"""BENCHMARK.json resolves, by name alone, to the files of each cell."""
import json
import re
import time

import pytest

from chipbench import generator, harness
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
    assert generator.loop_path(mix["loop"]).parent == ROOT / "chipbench/loops"
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


def _config(name: str) -> dict:
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    return json.loads((ROOT / entry["file"]).read_text())


def _nbytes(leaf: dict) -> int:
    n = 4
    for x in leaf["shape"]:
        n *= x
    return n


@pytest.mark.parametrize("mix", sorted(
    p.stem for p in (ROOT / "chipbench/traffic").glob("*.json")))
def test_every_mix_names_a_loop_file(mix):
    loop = json.loads((ROOT / f"chipbench/traffic/{mix}.json").read_text())
    path = generator.loop_path(loop["loop"])
    assert path == ROOT / "chipbench/loops" / f"{loop['loop']}.py"


def test_a_new_loop_file_is_found_with_no_edit(tmp_path):
    (tmp_path / "loops").mkdir()
    (tmp_path / "loops" / "echo.py").write_text(
        "from chipbench.generator import Loop\n\n\n"
        "class EchoLoop(Loop):\n"
        "    def setup(self, seconds):\n"
        "        self.ready = seconds\n\n\n"
        "LOOP = EchoLoop\n")
    config = {"code": {"n": 8, "k": 4, "p": 257}}
    loop = generator.make_loop(config, {"loop": "echo"}, 7, base=tmp_path)
    assert type(loop).__name__ == "EchoLoop"
    loop.setup(3.0)
    assert loop.ready == 3.0 and loop.seed == 7
    with pytest.raises(harness.BenchError, match="nothing.py"):
        generator.make_loop(config, {"loop": "nothing"}, 7, base=tmp_path)


def test_more_host_chips_than_the_cell_has_is_refused():
    cell = next(c for c in BENCH["workloads"] if c["chips"] == 1)
    config = dict(_config(cell["config"]), host_chips=4)
    with pytest.raises(harness.BenchError, match="4 chips"):
        harness.run_cell(BENCH, cell["name"], 1, 0.1, False,
                         time.perf_counter(), config=config)


def test_host_config_is_four_one_chip_shards():
    """Each leaf of the v5e-4 host's configuration is four of the
    one-chip configuration's rank shards joined along its shard axis,
    and its state bytes are its leaves' bytes."""
    one = _config("ckpt-granite1b-fsdp16-dc8x4")
    host = _config("ckpt-granite1b-fsdp16-host4-dc8x4")
    chips = host["host_chips"]
    assert chips == 4 and host["fsdp_shards"] == one["fsdp_shards"]
    assert [leaf["path"] for leaf in host["leaves"]] == \
        [leaf["path"] for leaf in one["leaves"]]
    for h, o in zip(host["leaves"], one["leaves"]):
        assert h["shard_axis"] == o["shard_axis"]
        assert (h["dtype"], h["fill"]) == (o["dtype"], o["fill"])
        per_chip = list(h["shape"])
        if h["shard_axis"] is not None:
            assert per_chip[h["shard_axis"]] % chips == 0
            per_chip[h["shard_axis"]] //= chips
        assert per_chip == o["shape"], h["path"]
    assert host["state_bytes"] == sum(_nbytes(x) for x in host["leaves"])
    assert host["state_bytes"] == chips * (one["state_bytes"] - 4) + 4
    for key in ("code", "writer", "model"):
        assert host[key] == one[key]
