"""The trace reduction: busy union, idle share, attribution by executable
and idle gaps named by the host span."""
import pytest

from chipbench import trace

MS = 1e6    # nanoseconds


def planes(*, ops_dev1=None):
    host = ("/host:CPU", [
        ("python", [("bench.window", 0 * MS, 100 * MS),
                    ("bench.save", 5 * MS, 60 * MS),
                    ("bench.restore", 60 * MS, 95 * MS)]),
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
    red = trace.reduce(planes(), gf_modules={"jit__lambda", "jit_fn"})
    assert red["window_s"] == pytest.approx(0.1)
    # 10..30 (union of two overlapping ops), 70..80, 90..100 (clipped)
    assert red["busy_s"] == pytest.approx(0.04)
    assert red["devices"] == 1


def test_time_is_attributed_by_executable():
    red = trace.reduce(planes(), gf_modules={"jit__lambda", "jit_fn"})
    assert red["gf_device_s"] == pytest.approx(0.03)
    names = dict((k, v) for k, v in red["executables"])
    assert names["jit_bench_advance"] == pytest.approx(0.01)


def test_idle_gaps_are_named_by_the_host_span():
    red = trace.reduce(planes(), gf_modules=set())
    gaps = dict((round(s, 6), name) for name, s in red["idle_gaps"])
    assert gaps[0.04] == "bench.save"        # 30..70, mid 50
    assert gaps[0.01] in ("bench.save", "bench.restore")
    assert red["idle_gaps"][0] == ["bench.save", pytest.approx(0.04)]


def test_busy_is_averaged_over_devices():
    red = trace.reduce(planes(ops_dev1=[("x", 0, 100 * MS)]), gf_modules=set())
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((0.04 + 0.1) / 2)


def test_top_device_ops():
    red = trace.reduce(planes(), gf_modules=set())
    assert red["device_ops"][0][0] == "fusion.2"
    assert len(red["device_ops"]) == 4


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.reduce([("/host:CPU", [("t", [("other", 0, 1)])])], set())


def test_union():
    assert trace.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]


def test_module_name_drops_the_program_id():
    assert trace.module_name("jit__lambda(123)") == "jit__lambda"
    assert trace.module_name("jit_fn") == "jit_fn"


def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5 lite: three planned encodes, one
    regenerate and one decode of the plan cache, then a jitted lambda of
    64 x 2**20 random words, inside a ``bench.save`` span."""
    import pathlib
    from jax.profiler import ProfileData

    path = pathlib.Path(__file__).parent / "data" / "v5e_gf_ops.xplane.pb"
    red = trace.reduce(trace.planes_of(ProfileData.from_file(str(path))),
                       gf_modules={"jit__lambda", "jit_fn"},
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
