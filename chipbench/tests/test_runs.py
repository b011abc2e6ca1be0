"""Whole runs of every cell on the CPU at a small size: a clean run is
correct; the control (the reference with one-byte symbols in the
program's place) and each fault planted under the timed path are not."""
import os
import tempfile
import time

import pytest

from chipbench import control, harness
from chipbench.tests import faults, tiny

SEED = 2**31 + 11          # above 32 signed bits, as the driver's are


def run(cell, seconds=0.5):
    return harness.run_cell(tiny.BENCH, cell, SEED, seconds, False,
                            time.perf_counter(),
                            config=tiny.tiny_config(cell),
                            peaks=tiny.CPU_PEAKS)


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_clean_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    names = {m["name"] for m in harness.cell_metrics(tiny.BENCH, cell,
                                                     "end_to_end")}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_node_files_are_fsynced_in_tmpdir_and_removed(cell, monkeypatch,
                                                      tmp_path):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    synced = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync",
                        lambda fd: (synced.append(fd), real_fsync(fd))[1])
    res = run(cell)
    assert res["correct"], res["checks"]
    # per step: n data and n redundancy files, the manifest, the directory
    n = tiny.config_of(cell)["code"]["n"]
    assert len(synced) >= 2 * n + 2
    assert list(tmp_path.iterdir()) == []


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_control_reads_as_wrong(cell):
    with control.control_patch():
        res = run(cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out",
                                   "unchanged"])
@pytest.mark.parametrize("cell", tiny.CELLS)
def test_fault_reads_as_wrong(cell, fault):
    plant = faults.UNCHANGED[cell] if fault == "unchanged" \
        else getattr(faults, fault)
    with plant():
        try:
            res = run(cell, seconds=2 if fault == "unchanged" else 0.5)
        except Exception:
            return      # the run prints no result line: refused as surely
    assert not res["correct"], res["checks"]
