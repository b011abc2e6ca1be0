"""The trace reduction: busy union, idle share, attribution by executable
and idle gaps named by the host span."""
import json
import pathlib

import pytest

from chipbench import harness, trace

MS = 1e6    # nanoseconds


def planes(*, ops_dev1=None, spans=()):
    host = ("/host:CPU", [
        ("python", [("bench.window", 0 * MS, 100 * MS),
                    ("bench.save", 5 * MS, 60 * MS),
                    ("bench.restore", 60 * MS, 95 * MS)]),
        ("io-pool", list(spans)),
    ])
    dev0 = ("/device:TPU:0", [
        ("XLA Modules", [("jit__lambda(12)", 10 * MS, 30 * MS),
                         ("jit_fn(7)", 70 * MS, 80 * MS),
                         ("jit_bench_advance(3)", 90 * MS, 100 * MS)]),
        ("XLA Ops", [("fusion.1", 10 * MS, 20 * MS),
                     ("fusion.2", 15 * MS, 30 * MS),     # overlaps fusion.1
                     ("dot.3", 70 * MS, 80 * MS),
                     ("copy.4", 90 * MS, 110 * MS)]),    # runs past the window
    ])
    out = [host, dev0, ("/device:TPU:0 SparseCore", [])]
    if ops_dev1 is not None:
        out.append(("/device:TPU:1", [("XLA Ops", ops_dev1)]))
    return out


def test_busy_union_and_idle_share():
    red = trace.reduce(planes(), gf_modules={"jit__lambda", "jit_fn"},
                       chips=1)
    assert red["window_s"] == pytest.approx(0.1)
    # 10..30 (union of two overlapping ops), 70..80, 90..100 (clipped)
    assert red["busy_s"] == pytest.approx(0.04)
    assert red["devices"] == 1


def test_time_is_attributed_by_executable():
    red = trace.reduce(planes(), gf_modules={"jit__lambda", "jit_fn"},
                       chips=1)
    assert red["gf_device_s"] == pytest.approx(0.03)
    names = dict((k, v) for k, v in red["executables"])
    assert names["jit_bench_advance"] == pytest.approx(0.01)


def test_idle_gaps_are_named_by_the_host_span():
    red = trace.reduce(planes(), gf_modules=set(), chips=1)
    gaps = dict((round(s, 6), name) for name, s in red["idle_gaps"])
    assert gaps[0.04] == "bench.save"        # 30..70, mid 50
    assert gaps[0.01] in ("bench.save", "bench.restore")
    assert red["idle_gaps"][0] == ["bench.save", pytest.approx(0.04)]


def test_idle_gaps_are_named_by_the_programs_innermost_span():
    """A gap inside ``bench.save`` > ``repro.write`` > ``repro.fsync``
    reads ``repro.fsync``: the program's spans name the gap."""
    red = trace.reduce(planes(spans=[("repro.write", 32 * MS, 68 * MS),
                                     ("repro.fsync", 40 * MS, 60 * MS),
                                     ("other.span", 45 * MS, 55 * MS)]),
                       gf_modules=set(), chips=1)
    assert red["idle_gaps"][0] == ["repro.fsync", pytest.approx(0.04)]


def test_busy_is_averaged_over_devices():
    red = trace.reduce(planes(ops_dev1=[("x", 0, 100 * MS)]), gf_modules=set(),
                       chips=2)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((0.04 + 0.1) / 2)


def test_only_the_cells_chips_are_read():
    """A device plane past the cell's chips is another process's chip
    and is not read; a chip of the cell with no operation is idle."""
    red = trace.reduce(planes(ops_dev1=[("x", 0, 100 * MS)]), gf_modules=set(),
                       chips=1)
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(0.04)
    idle = planes() + [("/device:TPU:1", [])]
    red = trace.reduce(idle, gf_modules=set(), chips=2)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(0.04 / 2)


def _gf_planes(work_per_device: list) -> list:
    """A window of 1 s in which device i runs one GF executable for
    ``work_per_device[i]`` seconds."""
    host = ("/host:CPU", [("python", [("bench.window", 0, 1000 * MS)])])
    out = [host]
    for i, secs in enumerate(work_per_device):
        ev = [("gf_exe(1)", 0, secs * 1000 * MS)]
        out.append((f"/device:TPU:{i}", [("XLA Modules", ev),
                                         ("XLA Ops", ev)]))
    return out


def test_roofline_is_read_over_the_chips_that_share_the_work():
    """Two chips that each do half of the GF work in t read the share
    that one chip doing all of it in 2t reads."""
    read = harness.load_reader("gf_roofline_pct.save")

    def share(work_per_device):
        red = trace.reduce(_gf_planes(work_per_device), {"gf_exe"},
                           chips=len(work_per_device))
        return read(harness.Context(
            ops=[], setup_s=0, work_bytes=1, gf_bytes=819e9 * 0.2,
            stage_delta={}, plan_delta={}, trace=red,
            peaks={"hbm_bytes_per_s": 819e9}))

    assert share([0.4]) == pytest.approx(50.0)
    assert share([0.2, 0.2]) == pytest.approx(share([0.4]))
    assert share([0.1, 0.1, 0.1, 0.1]) == pytest.approx(50.0)


def test_top_device_ops():
    red = trace.reduce(planes(), gf_modules=set(), chips=1)
    assert red["device_ops"][0][0] == "fusion.2"
    assert len(red["device_ops"]) == 4


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.reduce([("/host:CPU", [("t", [("other", 0, 1)])])], set(), 1)


def test_union():
    assert trace.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]


def test_module_name_drops_the_program_id():
    assert trace.module_name("jit__lambda(123)") == "jit__lambda"
    assert trace.module_name("jit_fn") == "jit_fn"


def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5 lite: three planned encodes, one
    regenerate and one decode of the plan cache, then a jitted lambda of
    64 x 2**20 random words, inside a ``bench.save`` span."""
    from jax.profiler import ProfileData

    path = pathlib.Path(__file__).parent / "data" / "v5e_gf_ops.xplane.pb"
    red = trace.reduce(trace.planes_of(ProfileData.from_file(str(path))),
                       gf_modules={"jit__lambda", "jit_fn"}, chips=1,
                       window_span="bench.save")
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.0648302)
    assert red["busy_s"] == pytest.approx(0.002831115)
    execs = dict((k, v) for k, v in red["executables"])
    assert execs["jit_fn"] == pytest.approx(0.000418042)
    assert red["gf_device_s"] == pytest.approx(execs["jit__lambda"]
                                               + execs["jit_fn"])
    ops = dict((k, v) for k, v in red["device_ops"])
    assert ops["circulant_encode.1"] == pytest.approx(3 * 0.000292502,
                                                      rel=1e-3)
    assert {label for label, _ in red["idle_gaps"]} == {"bench.save"}


def test_recorded_v5e_trace_reads_as_before():
    """Read over one chip, the recorded trace gives every number and
    label it gave before the reduction took the cell's chips
    (``v5e_gf_ops.reduced.json``), exactly."""
    from jax.profiler import ProfileData

    data = pathlib.Path(__file__).parent / "data"
    red = trace.reduce(
        trace.planes_of(ProfileData.from_file(
            str(data / "v5e_gf_ops.xplane.pb"))),
        gf_modules={"jit__lambda", "jit_fn"}, chips=1,
        window_span="bench.save")
    before = json.loads((data / "v5e_gf_ops.reduced.json").read_text())
    assert json.loads(json.dumps(red)) == before
