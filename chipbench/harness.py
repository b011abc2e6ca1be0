"""One run of one cell: set up, warm up, measure, check, report.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
cell's ``config`` file (``configs/<config>.json``), its traffic mix
(``traffic/<traffic>.json``, run by the loop ``loops/<loop>.py`` that
the mix names) and a reader for each of its metrics
(``metrics/<name>.py``, else ``metrics/<stem>.py`` for a name
``<stem>.<suffix>``).  Nothing here knows a cell by name.

The result is one JSON line, the last of standard output.  Earlier lines
say what the run did: the GF backend, compiles and cache hits in set-up
and in the window, the set-up phases, peak memory, counts and bytes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import resource
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from typing import Optional

HERE = pathlib.Path(__file__).resolve().parent

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BenchError(RuntimeError):
    """The cell cannot run as asked; nothing is reported."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics the cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader_path(name: str, base: pathlib.Path = HERE) -> pathlib.Path:
    for stem in (name, name.split(".")[0]):
        path = base / "metrics" / f"{stem}.py"
        if path.exists():
            return path
    raise BenchError(f"no reader for metric {name!r} under metrics/")


def load_reader(name: str, base: pathlib.Path = HERE):
    path = reader_path(name, base)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def resolve(bench: dict, cell_name: str, base: pathlib.Path = HERE):
    """(cell, config, mix) of a cell, read from their files."""
    cell = find_cell(bench, cell_name)
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == cell["config"]), None)
    if cfg_entry is None:
        raise BenchError(f"cell {cell_name!r} names unknown config "
                         f"{cell['config']!r}")
    config = load_json(base.parent / cfg_entry["file"])
    mix = load_json(base / "traffic" / f"{cell['traffic']}.json")
    return cell, config, mix


# ---------------------------------------------------------------- clocks
class CompileClock:
    """Backend compiles JAX reports: seconds (a persistent-cache hit
    counts only its retrieval), executables compiled, cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.requests = 0
        self.hits = 0

    def install(self) -> None:
        import jax

        def on_duration(event, secs, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.seconds += secs
                self.requests += 1

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def totals(self) -> tuple:
        return self.seconds, self.requests, self.hits

    @staticmethod
    def delta(now: tuple, then: tuple) -> dict:
        secs, req, hits = (a - b for a, b in zip(now, then))
        return {"compile_s": secs, "compiles": req - hits, "cache_hits": hits}


def peak_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def info(**fields) -> None:
    """One line of what the run did, before the result line."""
    print(json.dumps(fields, default=str), flush=True)


@contextmanager
def span(name: str):
    """A host span of the benchmark's own, named ``bench.<name>`` in the
    profiler's trace; free when no trace is being taken."""
    import jax
    with jax.profiler.TraceAnnotation(f"bench.{name}"):
        yield


# -------------------------------------------------------- metric context
@dataclasses.dataclass
class Context:
    """What a metric reader can read."""
    ops: list
    setup_s: float
    work_bytes: float
    gf_bytes: float
    stage_delta: dict
    plan_delta: dict
    trace: Optional[dict]
    peaks: dict

    def span_end(self) -> float:
        """From the window's start to the end of its last operation."""
        ends = [op.end for op in self.ops if math.isfinite(op.end)]
        return max(ends) if ends else math.inf

    def rate_MBps(self, kind: str) -> Optional[float]:
        done = [op for op in self.ops if op.kind == kind and op.ok]
        if not done:
            return None
        return sum(op.nbytes for op in done) / 1e6 / self.span_end()

    def per_GB(self, amount: float) -> Optional[float]:
        if not self.work_bytes:
            return None
        return amount / (self.work_bytes / 1e9)


# ------------------------------------------------------------------- run
def device_peaks(count: int) -> list:
    """Peak bytes in use on each of the cell's devices."""
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()[:count]]


def device_info(count: int) -> dict:
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": min(count, len(jax.devices())),
            "memory_peak_bytes": max(device_peaks(count))}


def peaks_for(kind: str, base: pathlib.Path = HERE) -> dict:
    table = load_json(base / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, config: Optional[dict] = None,
             peaks: Optional[dict] = None) -> dict:
    """One run of a cell in this process; returns the result line.
    ``config`` replaces the cell's configuration file and ``peaks`` the
    table's entry (tests drive small sizes on the CPU this way)."""
    from chipbench import generator

    cell, cfg_file, mix = resolve(bench, cell_name)
    config = config or cfg_file
    if config.get("host_chips", 1) > cell["chips"]:
        raise BenchError(f"config {cell['config']!r} holds its state on "
                         f"{config['host_chips']} chips; cell "
                         f"{cell_name!r} has {cell['chips']}")
    clock = CompileClock()
    clock.install()
    phases: dict[str, float] = {"runtime_up": time.perf_counter() - t_start}

    @contextmanager
    def phase(name: str):
        t0 = time.perf_counter()
        c0 = clock.totals()
        try:
            yield
        finally:
            phases[name] = phases.get(name, 0.0) + time.perf_counter() - t0
            d = CompileClock.delta(clock.totals(), c0)
            phases[f"{name}_compile_s"] = phases.get(
                f"{name}_compile_s", 0.0) + d["compile_s"]

    loop = generator.make_loop(config, mix, seed, span=span, phase=phase)
    try:
        return _run_loop(bench, cell, loop, seconds, trace, t_start, clock,
                         phases, peaks)
    finally:
        loop.close()


def _run_loop(bench: dict, cell: dict, loop, seconds: float, trace: bool,
              t_start: float, clock: CompileClock, phases: dict,
              peaks: Optional[dict]) -> dict:
    import jax
    from chipbench import trace as trace_mod
    from repro.exec import plan, staging

    c_setup = clock.totals()
    loop.setup(seconds)
    dev = jax.devices()[0]
    peaks = peaks or peaks_for(dev.device_kind)
    backend = _backend_name()
    c_win, st0, pl0 = clock.totals(), staging.stage_times(), plan.plan_stats()
    sb0 = staging.stage_bytes()
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    if trace:
        trace_mod.start(tdir)
    setup_s = time.perf_counter() - t_start
    try:
        with span("window"):
            loop.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    c_end, st1, pl1 = clock.totals(), staging.stage_times(), plan.plan_stats()
    sb1 = staging.stage_bytes()
    window_compiles = CompileClock.delta(c_end, c_win)
    device = device_info(cell["chips"])
    reduced = None
    if trace:
        try:
            reduced = trace_mod.reduce_dir(tdir, gf_modules=_gf_modules(),
                                           chips=cell["chips"])
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    loop.free()
    t_check = time.perf_counter()
    chk = loop.check()
    check_s = time.perf_counter() - t_check

    ctx = Context(
        ops=loop.ops, setup_s=setup_s,
        work_bytes=loop.work_bytes, gf_bytes=loop.gf_bytes,
        stage_delta={k: st1.get(k, 0.0) - st0.get(k, 0.0) for k in st1},
        plan_delta={"hits": pl1.hits - pl0.hits,
                    "misses": pl1.misses - pl0.misses},
        trace=reduced, peaks=peaks)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, cell["name"], kind):
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    info(backend=backend, setup=CompileClock.delta(c_win, c_setup),
         window=window_compiles, setup_phases=phases, setup_s=setup_s,
         span_end_s=ctx.span_end(), check_s=check_s,
         peak_host_rss_bytes=peak_rss(),
         peak_hbm_bytes=device_peaks(cell["chips"]), device=device,
         work_bytes=loop.work_bytes, gf_needed_bytes=loop.gf_bytes,
         stage_delta=ctx.stage_delta,
         stage_bytes_delta={k: v - sb0.get(k, 0) for k, v in sb1.items()},
         plan_delta=ctx.plan_delta, **loop.facts)
    if reduced is not None:
        info(gf_device_s=reduced["gf_device_s"],
             executables=reduced["executables"][:20])
    result = {
        "correct": chk.correct,
        "attempted": len(loop.ops),
        "failed": sum(not op.ok for op in loop.ops),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in chk.values.items()}
    return result


def _backend_name() -> str:
    from repro.kernels import dispatch
    return dispatch.select().name


def _gf_modules() -> set:
    """Names of the HLO modules of every planned GF executable: the plan
    cache's own executables, whatever backend lowered them."""
    from repro.exec import plan
    names = set()
    with plan._LOCK:
        planners = list(plan._REGISTRY.values())
    for pc in planners:
        for exe in list(pc._plans.values()):
            first = exe.as_text().split("\n", 1)[0]
            if first.startswith("HloModule "):
                names.add(first.split()[1].rstrip(","))
    return names


def print_result(result: dict) -> None:
    for name, chk in result["checks"].items():
        print(f"check {name}: {chk['value']} (limit {chk['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)

