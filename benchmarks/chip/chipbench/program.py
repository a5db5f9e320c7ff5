"""The program's own spans and JIT counter, reduced to per-layer numbers.

An armed ``repro.obs.FlightRecorder`` holds one row per host span of the
serving path (``serve.*`` in ``serve_forever``, ``engine.*`` in
``Engine``), stamped with ``time.time_ns()``, with the JIT work booked to
it. The same spans are ``TraceAnnotation``s in the profiler's trace, where
the ``.xplane.pb`` stores each event's start relative to the profile's
start (``profile_start_time`` of its ``Task Environment`` plane) on the
same clock, so rows and their twins line up once that start is added.

Every function returns None where there is nothing to read: no recorder,
a recorder without host spans, or a trace without program spans, as with
a program that has none.
"""
from __future__ import annotations

import bisect
import glob
import os
import statistics

PREFIXES = ("serve.", "engine.")
OUTSIDE = "(no program span)"
SCAN = 256


def rows(rec) -> list | None:
    """The recorder's host spans as dicts, with names decoded."""
    spans = getattr(rec, "host_spans", None)
    if spans is None or not spans.n:
        return None
    names = rec.host_span_names
    return [dict(r, name=names[r["name"]]) for r in spans.rows()]


def _ancestor(r: dict, by_id: dict, name: str) -> dict | None:
    while r["parent"] >= 0:
        r = by_id[r["parent"]]
        if r["name"] == name:
            return r
    return None


def step_host_ms(rec) -> list | None:
    """Host milliseconds of each decode step: the ``engine.step`` span,
    less the ``engine.admit`` spans inside it and its ``engine.sync`` wait
    for the device; steps that decoded (those with an ``engine.sync``)."""
    rs = rows(rec)
    if rs is None:
        return None
    by_id = {r["id"]: r for r in rs}
    less, decoded = {}, set()
    for r in rs:
        if r["name"] not in ("engine.admit", "engine.sync"):
            continue
        step = _ancestor(r, by_id, "engine.step")
        if step is None:
            continue
        less[step["id"]] = less.get(step["id"], 0) + r["t1"] - r["t0"]
        if r["name"] == "engine.sync":
            decoded.add(step["id"])
    out = [1e-6 * (by_id[i]["t1"] - by_id[i]["t0"] - less[i])
           for i in sorted(decoded)]
    return out or None


def prefill_jit_ms(rec) -> list | None:
    """Host milliseconds of tracing, lowering and compiling or loading from
    the compile cache booked to each ``engine.prefill`` span (the union of
    the four, so nested events count once)."""
    rs = rows(rec)
    if rs is None:
        return None
    out = [1e-6 * r["jit_ns"] for r in rs if r["name"] == "engine.prefill"]
    return out or None


def median(values) -> float | None:
    return statistics.median(values) if values else None


def self_ms(rec) -> dict | None:
    """Host milliseconds by span name, each span less its children: where
    the host's time went, summed over the recorder's spans."""
    rs = rows(rec)
    if rs is None:
        return None
    kids = {}
    for r in rs:
        if r["parent"] >= 0:
            kids[r["parent"]] = kids.get(r["parent"], 0) + r["t1"] - r["t0"]
    out = {}
    for r in rs:
        own = r["t1"] - r["t0"] - kids.get(r["id"], 0)
        out[r["name"]] = out.get(r["name"], 0.0) + 1e-6 * own
    return out


def load(log_dir: str) -> tuple | None:
    """(program spans as ``(start, end, name)`` in seconds on the trace's
    clock, the profile's start in ns on the host's clock) from the one
    ``.xplane.pb`` under ``log_dir``; None without program spans."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        return None
    data = ProfileData.from_file(paths[0])
    spans, start = [], None
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    s = ev.start_ns * 1e-9
                    spans.append((s, s + ev.duration_ns * 1e-9, ev.name))
    if not spans or start is None:
        return None
    return sorted(spans), start


def twin_offsets_us(rec, spans, start_ns: int,
                    name: str = "engine.step") -> list | None:
    """Microseconds from each recorder row named ``name`` to its
    annotation in the trace (the n-th row against the n-th event), with
    the profile's start added back; None if the counts differ."""
    rs = rows(rec)
    if rs is None or not spans:
        return None
    mine = sorted(r["t0"] for r in rs if r["name"] == name)
    got = sorted(s for s, _, n in spans if n == name)
    if not mine or len(mine) != len(got):
        return None
    return [(round(g * 1e9) + start_ns - m) * 1e-3 for g, m in zip(got, mine)]


def _idle_gaps(ops, lo: float, hi: float):
    from chipbench import trace as tr
    edges = [lo]
    for s, e in tr.union(ops, lo, hi):
        edges += [s, e]
    edges.append(hi)
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def idle_by_span(trace, spans) -> dict | None:
    """Device idle seconds inside the traced window, by the innermost
    program span open at each gap's midpoint (``OUTSIDE`` where none is),
    summed over devices and divided by their number."""
    if trace is None or not trace.devices or not spans:
        return None
    lo, hi = trace.window
    # a span before the spans it holds, where two start together
    spans = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    starts = [s for s, _, _ in spans]
    tot = {}
    for dev in trace.devices:
        for s, e in _idle_gaps(dev.ops, lo, hi):
            mid = 0.5 * (s + e)
            name = OUTSIDE
            # the latest-starting span still open at ``mid`` is innermost;
            # a loop pass holds far fewer than SCAN spans
            i = bisect.bisect_right(starts, mid)
            for j in range(i - 1, max(-1, i - 1 - SCAN), -1):
                if spans[j][1] >= mid:
                    name = spans[j][2]
                    break
            tot[name] = tot.get(name, 0.0) + (e - s)
    n = len(trace.devices)
    return {k: v / n for k, v in tot.items()}


def idle_host_pct(trace, spans) -> float | None:
    """Device idle in the window while the host was inside a program span
    other than ``serve.wait`` (serving work, not waiting for it), as a
    share of the window. Percent."""
    by = idle_by_span(trace, spans)
    if by is None:
        return None
    lo, hi = trace.window
    host = sum(v for k, v in by.items() if k not in ("serve.wait", OUTSIDE))
    return 100.0 * host / (hi - lo)
