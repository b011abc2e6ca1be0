"""From a profiler trace to the per-layer numbers of a traced run.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``ProfileData`` reads it into planes (a device, or the host), lines (a
stream of the device, or a host thread) and events with a start and a
duration in nanoseconds.  Device planes are named ``/device:TPU:<i>``; on
them the ``XLA Ops`` line holds every operation that ran, and the ``XLA
Modules`` line the executable each belongs to; ``Async XLA Ops`` holds
the asynchronous copies, busy as well.  The benchmark's own host
spans are ``TraceAnnotation`` events named ``bench.<what>`` on the host
plane, and ``bench.window`` brackets the measured window; the program's
spans are named ``repro.<what>``.

The reduction, over the window and the cell's ``chips`` devices (the
planes ``/device:TPU:0`` .. ``/device:TPU:<chips-1>``):

* busy -- the union of each device's operation intervals, averaged over
  the devices; the idle share is 1 - busy / window;
* executables -- device seconds per executable (module name without its
  program id), averaged over the devices, and among them the plan
  cache's GF executables, named by the caller: device time is attributed
  by executable, not by kernel;
* device_ops -- the ten operations that took most device time;
* idle_gaps -- the ten longest gaps between busy intervals of the first
  device, each named by the innermost ``bench.*`` or ``repro.*`` span
  the host was in.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Iterable

import jax
from jax.profiler import ProfileData

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = ("bench.", "repro.")
WINDOW_SPAN = "bench.window"
TOP = 10
_PROGRAM_ID = re.compile(r"\(\d+\)$")
_DEVICE = re.compile(r"/device:TPU:(\d+)")


def start(log_dir: str) -> None:
    """Start a trace of the device and of host C++ spans; the Python
    tracer stays off, which would slow every call of the host path."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def planes_of(pd) -> list:
    """(plane name, [(line name, [(event, start_ns, end_ns)])]) of a
    ``ProfileData``: the plain form the reduction reads."""
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(e.name, float(e.start_ns),
                                       float(e.start_ns) + float(e.duration_ns))
                                      for e in line.events]))
        out.append((plane.name, lines))
    return out


def union(intervals: Iterable[tuple]) -> list:
    """Sorted, merged (start, end) intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_name(event: str) -> str:
    return _PROGRAM_ID.sub("", event)


def op_name(event: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = ...`` ->
    ``fusion.3``."""
    return event.split(" = ", 1)[0].lstrip("%")


def chip_planes(planes: list, chips: int) -> list:
    """(index, {line name: events}) of the planes of the first ``chips``
    devices, by index."""
    out = []
    for name, lines in planes:
        m = _DEVICE.fullmatch(name)
        if m and int(m.group(1)) < chips:
            out.append((int(m.group(1)), dict(lines)))
    return sorted(out, key=lambda d: d[0])


def reduce(planes: list, gf_modules: set, chips: int,
           window_span: str = WINDOW_SPAN) -> dict:
    """The window's numbers from ``planes_of`` output over the first
    ``chips`` devices (see module doc); ``window_span`` names the host
    span that brackets the window."""
    spans = []
    for name, lines in planes:
        if name.startswith("/device"):
            continue
        for _line, events in lines:
            spans += [ev for ev in events if ev[0].startswith(SPAN_PREFIX)]
    windows = [ev for ev in spans if ev[0] == window_span]
    if not windows:
        raise ValueError(f"no {window_span} span in the trace")
    lo = min(ev[1] for ev in windows)
    hi = max(ev[2] for ev in windows)
    devices = chip_planes(planes, chips)
    busy_each, execs, ops = [], {}, {}
    first_busy: list = []
    for i, (_index, lines) in enumerate(devices):
        dev_ops = lines.get(OPS_LINE, [])
        busy = union(_clip([(s, e) for _n, s, e in
                            dev_ops + lines.get(ASYNC_LINE, [])], lo, hi))
        busy_each.append(sum(e - s for s, e in busy))
        if i == 0:
            first_busy = busy
        for name, s, e in dev_ops:
            for cs, ce in _clip([(s, e)], lo, hi):
                ops[op_name(name)] = ops.get(op_name(name), 0.0) + (ce - cs)
        for name, s, e in lines.get(MODULES_LINE, []):
            for cs, ce in _clip([(s, e)], lo, hi):
                key = module_name(name)
                execs[key] = execs.get(key, 0.0) + (ce - cs)
    n_dev = max(1, len(devices))
    gaps = []
    edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
    inner = sorted(spans, key=lambda ev: ev[2] - ev[1])
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        label = next((ev[0] for ev in inner if ev[1] <= mid <= ev[2]),
                     "outside bench spans")
        gaps.append((label, (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    by_time = sorted(execs.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_each) / n_dev / 1e9,
        "devices": len(devices),
        "gf_device_s": sum(v for k, v in execs.items()
                           if k in gf_modules) / n_dev / 1e9,
        "executables": [[k, v / n_dev / 1e9] for k, v in by_time],
        "device_ops": [[k, v / n_dev / 1e9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label, secs] for label, secs in gaps[:TOP]],
    }


def reduce_dir(log_dir: str, gf_modules: set, chips: int) -> dict:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {log_dir}, got {paths}")
    return reduce(planes_of(ProfileData.from_file(paths[0])), gf_modules,
                  chips)
