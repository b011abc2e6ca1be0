"""The readers of the program's stage clock: a value per GB of work
where the stage ran in the window, nothing where it did not."""
import pytest

from chipbench import harness


def ctx(stage_delta, work_bytes=2e9):
    return harness.Context(ops=[], setup_s=1.0, work_bytes=work_bytes,
                           gf_bytes=0.0, stage_delta=stage_delta,
                           plan_delta={}, trace=None, peaks={})


@pytest.mark.parametrize("metric,stages", [
    ("io_write_s_per_GB.save", ("write",)),
    ("io_read_s_per_GB.restore", ("read",)),
    ("host_stage_s_per_GB.restore", ("pack", "pad")),
])
def test_reader_is_seconds_per_GB_of_its_stages(metric, stages):
    read = harness.load_reader(metric)
    delta = {name: 3.0 for name in stages}
    delta["other"] = 100.0
    assert read(ctx(delta)) == pytest.approx(1.5 * len(stages))


@pytest.mark.parametrize("metric", ["io_write_s_per_GB.save",
                                    "io_write_s_per_GB.restore",
                                    "io_read_s_per_GB.restore",
                                    "host_stage_s_per_GB.restore"])
def test_reader_is_silent_without_its_stage(metric):
    read = harness.load_reader(metric)
    assert read(ctx({"pipe.dispatch": 2.0})) is None
    assert read(ctx({})) is None
