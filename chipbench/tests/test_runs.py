"""Whole runs of every cell on the CPU at a small size: a clean run is
correct; the control (the reference with one-byte symbols in the
program's place) and each fault planted under the timed path are not.

A one-chip cell runs in this process.  A cell that spans a host's chips
runs every case in one subprocess with that many virtual CPU devices
(``multichip.py``); each case is still a test of its own here."""
import json
import os
import subprocess
import sys
import tempfile
import time

import pytest

from chipbench import control, harness
from chipbench.tests import faults, tiny

def run(cell, seconds=0.5):
    return harness.run_cell(tiny.BENCH, cell, tiny.SEED, seconds, False,
                            time.perf_counter(),
                            config=tiny.tiny_config(cell),
                            peaks=tiny.CPU_PEAKS)


@pytest.fixture(scope="module")
def multichip(tmp_path_factory):
    """{cell: {case: result}} of the multi-chip cells, each cell's cases
    in one subprocess with the cell's chips as CPU devices."""
    out = {}
    for cell in tiny.MULTI_CHIP:
        chips = harness.find_cell(tiny.BENCH, cell)["chips"]
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
        env["PYTHONPATH"] = os.pathsep.join(
            [str(tiny.ROOT / "src"), str(tiny.ROOT),
             env.get("PYTHONPATH", "")])
        env.pop("REPRO_GF_BACKEND", None)
        res = subprocess.run(
            [sys.executable, "-m", "chipbench.tests.multichip", cell,
             str(tmp_path_factory.mktemp(cell))],
            capture_output=True, text=True, env=env, cwd=tiny.ROOT,
            timeout=300)
        assert res.returncode == 0, res.stderr[-4000:]
        out[cell] = json.loads(res.stdout.strip().splitlines()[-1])
        assert out[cell]["devices"] == chips
    return out


def case(request, cell, name):
    """The result of one case: from the subprocess for a multi-chip
    cell, else None (the test runs it here)."""
    if cell in tiny.MULTI_CHIP:
        return request.getfixturevalue("multichip")[cell][name]
    return None


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_clean_run_is_correct(cell, request):
    res = case(request, cell, "clean") or run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    names = {m["name"] for m in harness.cell_metrics(tiny.BENCH, cell,
                                                     "end_to_end")}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == harness.find_cell(tiny.BENCH,
                                                       cell)["chips"]


@pytest.mark.parametrize("cell", tiny.MULTI_CHIP)
def test_state_and_encode_span_the_host_chips(cell, request):
    """Every leaf of every state lies on all of the host's chips, each
    sharded leaf as one rank's shard a chip, and every planned encode's
    output spans them too."""
    layout = case(request, cell, "layout")
    chips = harness.find_cell(tiny.BENCH, cell)["chips"]
    assert layout["leaf_devices"] and set(layout["leaf_devices"]) == {chips}
    leaves = tiny.tiny_config(cell)["leaves"]
    assert set(layout["shard_shapes"]) == {leaf["path"] for leaf in leaves}
    for leaf in leaves:
        want = list(leaf["shape"])
        if leaf["shard_axis"] is not None:
            want[leaf["shard_axis"]] //= chips
        assert layout["shard_shapes"][leaf["path"]] == want, leaf["path"]
    assert layout["encode_devices"]
    assert set(layout["encode_devices"]) == {chips}


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_node_files_are_fsynced_in_tmpdir_and_removed(cell, request,
                                                      monkeypatch, tmp_path):
    res = case(request, cell, "fsync")
    if res is None:
        monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
        synced = []
        real_fsync = os.fsync
        monkeypatch.setattr(os, "fsync",
                            lambda fd: (synced.append(fd), real_fsync(fd))[1])
        res = run(cell)
        facts = {"fsyncs": len(synced), "left": list(tmp_path.iterdir())}
    else:
        facts = case(request, cell, "fsync_facts")
    assert res["correct"], res["checks"]
    # per step: n data and n redundancy files, the manifest, the directory
    n = tiny.config_of(cell)["code"]["n"]
    assert facts["fsyncs"] >= 2 * n + 2
    assert facts["left"] == []


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_control_reads_as_wrong(cell, request):
    res = case(request, cell, "control")
    if res is None:
        with control.control_patch():
            res = run(cell)
    assert "raised" not in res, res["raised"]
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out",
                                   "unchanged"])
@pytest.mark.parametrize("cell", tiny.CELLS)
def test_fault_reads_as_wrong(cell, fault, request):
    res = case(request, cell, fault)
    if res is None:
        plant = faults.UNCHANGED[cell] if fault == "unchanged" \
            else getattr(faults, fault)
        with plant():
            try:
                res = run(cell, seconds=2 if fault == "unchanged" else 0.5)
            except Exception:
                return      # the run prints no result line: refused as surely
    if "raised" in res:
        return
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", tiny.MULTI_CHIP)
def test_shards_left_out_read_as_wrong(cell, request):
    """A save that reads each leaf from its first chip alone."""
    res = case(request, cell, "shards_left_out")
    assert "raised" not in res, res["raised"]
    assert not res["correct"], res["checks"]
    assert res["checks"]["data_mismatch_bytes"]["value"] > 0
