"""Read the profiler's trace of a window and reduce it to what the per-layer
readers need: per device, the intervals in which an operation ran, the
operations and programs by name; on the host, the benchmark's own spans.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device planes
are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation run, the ``XLA Modules`` line one per program run. The host's
spans are the ``TraceAnnotation`` events named ``chipbench.*``. All times
are seconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_CONTROL = {"while", "conditional", "call"}


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)       # (start, end, name)
    modules: list = field(default_factory=list)   # (start, end, name)


@dataclass
class Trace:
    devices: list                                  # [Device], by index
    spans: list                                    # (start, end, name)
    window: tuple                                  # (start, end)


def options():
    """Profiler options: no Python function tracing (it would trace every
    call of the host loop and slow it many times over)."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    return o


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {paths}")
    data = ProfileData.from_file(paths[0])
    devices, spans = {}, []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device(plane.name))
            for line in plane.lines:
                into = {"XLA Ops": dev.ops,
                        "XLA Modules": dev.modules}.get(line.name)
                if into is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    into.append((s, s + ev.duration_ns * 1e-9, ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("chipbench."):
                        s = ev.start_ns * 1e-9
                        spans.append((s, s + ev.duration_ns * 1e-9,
                                      ev.name))
    wins = [(s, e) for s, e, n in spans if n == "chipbench.window"]
    if len(wins) != 1:
        raise RuntimeError(f"expected one chipbench.window span, got {wins}")
    return Trace([devices[k] for k in sorted(devices)], spans, wins[0])


def union(intervals, lo: float, hi: float) -> list:
    """Merged intervals, clipped to [lo, hi]."""
    out = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def instruction(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: an event of
    the ``XLA Ops`` line is named by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def _module_at(modules: list, starts: list, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][1] >= t:
        return modules[i][2].split("(", 1)[0]
    return "?"


def op_totals(trace: Trace) -> dict:
    """Device seconds per operation (``<program>/<instruction>``), summed
    over devices and divided by their number, inside the window. Control
    flow (``while``, ``conditional``, ``call``) holds other operations and
    is left out, so no time is counted twice."""
    lo, hi = trace.window
    tot = {}
    for dev in trace.devices:
        modules = sorted(dev.modules)
        starts = [m[0] for m in modules]
        for s, e, name in dev.ops:
            d = min(e, hi) - max(s, lo)
            ins = instruction(name)
            if d <= 0 or ins.split(".")[0] in _CONTROL:
                continue
            key = f"{_module_at(modules, starts, s)}/{ins}"
            tot[key] = tot.get(key, 0.0) + d
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in tot.items()}


def idle_gaps(trace: Trace, top: int = 10) -> list:
    """Device idle time inside the window, by what the host was doing
    (the innermost ``chipbench.*`` span open at each gap's midpoint;
    "chipbench.loop" where none is), summed over devices and divided by
    their number; the ``top`` largest, as [name, seconds]."""
    lo, hi = trace.window
    # spans nest (step > admit > prefill), so the innermost open span is
    # the latest-starting one that has not yet ended
    spans = sorted(sp for sp in trace.spans if sp[2] != "chipbench.window")
    starts = [sp[0] for sp in spans]
    tot = {}
    for dev in trace.devices:
        edges = [lo]
        for s, e in union(dev.ops, lo, hi):
            edges += [s, e]
        edges.append(hi)
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = 0.5 * (s + e)
            name = "chipbench.loop"
            for i in range(bisect.bisect_right(starts, mid) - 1,
                           max(-1, bisect.bisect_right(starts, mid) - 64),
                           -1):
                if spans[i][1] >= mid:
                    name = spans[i][2]
                    break
            tot[name] = tot.get(name, 0.0) + (e - s)
    n = max(len(trace.devices), 1)
    return sorted(([k, v / n] for k, v in tot.items()),
                  key=lambda kv: -kv[1])[:top]
