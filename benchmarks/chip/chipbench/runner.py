"""One run of one cell: set up, measure one window, check, report.

``run`` returns the result line as a dict. Everything a cell needs is found
by name: its configuration file (``configs/``), with the reference and the
adapter of its ``family`` (``reference/``, ``adapters/``), its traffic file
(``traffic/``), its limits (``limits/<cell>.json``) and one reader per
per-layer metric (``metrics/<name>.py``).
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import shutil
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ spec
@dataclass
class Cell:
    name: str
    config: str
    conf: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list
    limits: dict = field(default_factory=dict)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    """The cell of BENCHMARK.json named ``name``; or, for the knee sweep and
    the calibration of a cell not yet there, ``<config>.<traffic>`` of the
    files ``configs/<config>.json`` and ``traffic/<traffic>.json``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        for f in (BENCH_DIR / "configs").glob("*.json"):
            if name.startswith(f.stem + "."):
                w = {"name": name, "config": f.stem,
                     "traffic": name[len(f.stem) + 1:], "chips": 1}
        if w is None:
            raise SystemExit(f"chipbench: no workload {name!r}")
    c = next((c for c in bench["configs"] if c["name"] == w["config"]),
              {"file": f"benchmarks/chip/configs/{w['config']}.json"})
    limits = BENCH_DIR / "limits" / f"{name}.json"
    return Cell(
        name=name, config=w["config"],
        conf=json.loads((ROOT / c["file"]).read_text()),
        traffic=json.loads(
            (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text()),
        chips=w["chips"],
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        limits=json.loads(limits.read_text()) if limits.exists() else {})


def family(cell: Cell):
    """(reference module, adapter module) of the cell's configuration."""
    if str(BENCH_DIR) not in sys.path:
        sys.path.insert(0, str(BENCH_DIR))
    fam = cell.conf["family"]
    return (importlib.import_module(f"reference.{fam}"),
            importlib.import_module(f"adapters.{fam}"))


def reader(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- compiles
class CompileWatch:
    """XLA compiles and compile-cache loads, from ``jax.monitoring``. JAX
    reports a backend-compile event for a program loaded from the
    persistent cache too, so a compile is an event that was not a hit."""
    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.events = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == self._BACKEND:
            self.events += 1

    def _event(self, event, **_):
        if event == self._HIT:
            self.hits += 1

    def snap(self) -> tuple:
        """(compiles, cache loads) so far."""
        return self.events - self.hits, self.hits


# ------------------------------------------------------------- context
@dataclass
class Context:
    """What a per-layer reader may read."""
    cell: Cell
    dims: dict
    rec: object
    clock: object
    trace: object
    peaks: dict


def end_to_end(reqs, rec, clock) -> tuple:
    """TTFT of interactive requests due in the window (counted at the
    window's end where no first token came), every gap between output
    tokens of interactive requests inside the window, and all output
    tokens of the window over its length: the statistics a cell may
    report, and those the knee sweep reads."""
    t0, end = clock.t0, clock.end
    ttft, itl, tokens = [], [], 0
    for r in reqs:
        ev = [(t, n) for t, n in rec.tokens.get(r.req_id, []) if t <= end]
        tokens += sum(n for _, n in ev)
        if not r.is_interactive or r.arrival_time >= clock.seconds:
            continue
        due = t0 + r.arrival_time
        ttft.append((ev[0][0] if ev else end) - due)
        last = None
        for t, n in ev:
            if last is not None:
                itl.append(t - last)
            itl += [0.0] * (n - 1)
            last = t
    out = {"tokens_per_s": tokens / clock.seconds}
    if ttft:
        out["ttft_p50_ms"] = 1e3 * statistics.median(ttft)
    if itl:
        q = statistics.quantiles(itl, n=100, method="inclusive")
        for p in (50, 95, 98):
            out[f"itl_p{p}_ms"] = 1e3 * q[p - 1]
    return out, {"ttft": ttft, "itl": itl}


# ------------------------------------------------------------------ run
@dataclass
class Prepared:
    """What one process sets up once for a cell: devices, model, weights
    of one seed, and every program the window runs."""
    cell: Cell
    devs: list
    peaks: dict
    ref: object
    adapter: object
    cfg: object
    weights: object
    params: object
    watch: CompileWatch


@dataclass
class Window:
    """One measured window and what was recorded in it."""
    reqs: list
    rec: object
    clock: object
    compiles: int
    loads: int
    memory_peak: int
    trace: object


def prepare(cell: Cell, seed: int, t_start: float,
            require_tpu: bool = True) -> Prepared:
    import jax

    from chipbench import check, peaks, serve, traffic

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU: JAX's first device is "
                         f"{devs[0].platform}")
    if len(devs) < cell.chips:
        raise SystemExit(f"chipbench: {cell.name} needs {cell.chips} chips, "
                         f"JAX sees {len(devs)}")
    devs = devs[:cell.chips]
    pk = peaks.peaks(devs[0].device_kind) if require_tpu else {}
    ref, adapter = family(cell)
    cfg = adapter.model_config(cell.config, cell.conf)
    watch = CompileWatch()
    log(f"devices ready: {time.monotonic() - t_start:.3f} s")
    weights = make_weights(ref, cell.conf, seed)
    log(f"weights made: {time.monotonic() - t_start:.3f} s")
    lengths = traffic.prompt_lengths(cell.traffic)
    mixed = traffic.classes(cell.traffic) == {"interactive", "batch"}
    params = adapter.program_params(weights)
    spent = serve.warm_up(cfg, params, cell.conf["serve"], devs, lengths,
                          preempts=mixed)
    gc.collect()
    n, hits = watch.snap()
    log(f"warmed up {lengths}: {time.monotonic() - t_start:.3f} s, "
        f"{n} compiles, {hits} cache loads; "
        + ", ".join(f"{k} {v:.3f} s" for k, v in spent.items()))
    return Prepared(cell, devs, pk, ref, adapter, cfg, weights, params,
                    watch)


def make_weights(ref, conf: dict, seed: int):
    """The seed's weights, made on the device in one jitted call."""
    import jax

    from chipbench import check
    return jax.block_until_ready(jax.jit(
        lambda k: ref.init_weights(conf, k))(check.seed_key(seed)))


def requests(prep: Prepared, seed: int, seconds: float,
             rate_scale: float = 1.0) -> list:
    from chipbench import traffic
    from repro.serving.request import make_batch, make_interactive
    out = []
    for a in traffic.generate(prep.cell.traffic, seed, seconds,
                              prep.cell.conf["prompt_vocab"], rate_scale):
        r = (make_interactive if a.interactive else make_batch)(
            a.prompt_len, a.output_len, a.due, model=prep.cfg.name)
        r.prompt_tokens = a.tokens
        out.append(r)
    return out


def measure(prep: Prepared, reqs: list, seconds: float,
            trace: bool) -> Window:
    """Serve ``reqs`` for one window on a fresh cluster."""
    import jax

    from chipbench import serve
    from chipbench import trace as tr

    cluster, ctrl = serve.build(prep.cfg, prep.params, prep.cell.conf["serve"],
                                prep.cell.chips)
    engines = [i.engine for i in cluster.instances]
    for e in engines:
        serve.warm_decode(e)
    rec = serve.Recorder(traced=trace)
    for i, e in enumerate(engines):
        serve.instrument(e, i, rec)

    def on_pass(t):
        limits = [e.max_batch_size for e in engines]
        if not rec.limits or rec.limits[-1][1] != limits:
            rec.limits.append((t, limits))

    clock = serve.WindowClock(seconds, on_pass=on_pass)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir, profiler_options=tr.options())
    c0 = prep.watch.snap()
    serve.window(reqs, ctrl, cluster, clock)
    c1 = prep.watch.snap()
    if trace:
        jax.profiler.stop_trace()
    compiles, loads = c1[0] - c0[0], c1[1] - c0[1]
    if compiles:
        log(f"WARNING: {compiles} XLA compiles inside the window")
    mem = [d.memory_stats() or {} for d in prep.devs]
    memory_peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    tr_data = None
    if trace:
        t = time.monotonic()
        tr_data = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace read: {time.monotonic() - t:.3f} s")
    # the program's state goes before the reference runs
    del cluster, ctrl, engines
    gc.collect()
    return Window(reqs, rec, clock, compiles, loads, memory_peak, tr_data)


def finished(win: Window) -> list:
    return [r for r in win.reqs if r.req_id in win.rec.tokens
            and r.tokens_generated >= r.output_len
            and win.rec.tokens[r.req_id][-1][0] <= win.clock.end]


def compare(prep: Prepared, win: Window, seed: int,
            control: bool = False) -> dict:
    """The check's readings on a sample of the window's finished
    requests (see ``chipbench.check``). The program's logits are brought
    to the host for the sample and freed on the device before the
    reference runs."""
    from chipbench import check
    rec = win.rec
    picked = check.sample(finished(win), rec.served_on,
                          check.highest_slots(rec.decode_inputs), seed,
                          max_n=check.PAD_BATCH, want_tokens=400)
    got = check.served(rec.decode_inputs, rec.prefill_logits, picked)
    rec.decode_inputs.clear()
    rec.prefill_logits.clear()
    gc.collect()
    log("compared: " + ", ".join(
        f"{r.req_id} ({'interactive' if r.is_interactive else 'batch'}, "
        f"{r.prompt_len}+{r.output_len} tokens, engine "
        f"{rec.served_on.get(r.req_id)}, {r.preemptions} preemptions)"
        for r in picked))
    by_id = {r.req_id: r for r in picked}
    bad = [rid for rid, (t, lg) in got.items()
           if len(t) != by_id[rid].output_len - 1 or len(lg) != len(t) + 1]
    if bad:
        log(f"served tokens or logits captured disagree with the output "
            f"length for requests {bad}")
    seqs = [(np.asarray(by_id[rid].prompt_tokens, np.int32), t, lg)
            for rid, (t, lg) in got.items() if rid not in bad]
    if not seqs:
        return {"logit_err": float("inf"), "token_mismatches": 0,
                "positions": 0, "bad": bad}
    t = time.monotonic()
    out = check.readings(prep.ref, prep.cell.conf, prep.weights, seqs,
                         control)
    log(f"reference check: {time.monotonic() - t:.3f} s")
    return dict(out, bad=bad)


def verdict(limits: dict, got: dict) -> dict:
    """Each number compared, beside its limit: the worst relative logit
    error under the cell's limit, no served token off the program's own
    greedy choice, and at least one position compared."""
    return {
        "logit_err": {"value": got["logit_err"],
                      "limit": limits.get("logit_err", {}).get("limit")},
        "token_mismatches": {"value": got["token_mismatches"], "limit": 0},
        "positions_compared": {"value": got["positions"], "limit": 1},
    }


def correct(checks: dict, got: dict) -> bool:
    lim = checks["logit_err"]["limit"]
    return (lim is not None and not got["bad"]
            and checks["logit_err"]["value"] <= lim
            and checks["token_mismatches"]["value"] <= 0
            and checks["positions_compared"]["value"] >= 1)


def report(prep: Prepared, win: Window, got: dict, t_start: float) -> dict:
    """The result line."""
    from chipbench import trace as tr

    cell, rec, clock = prep.cell, win.rec, win.clock
    e2e, samples = end_to_end(win.reqs, rec, clock)
    e2e["setup_s"] = clock.t0 - t_start
    n_batch = sum(not r.is_interactive for r in win.reqs)
    if n_batch and sum(not r.is_interactive and r.req_id in rec.tokens
                       for r in win.reqs) >= n_batch:
        log("WARNING: the batch backlog emptied inside the window")
    due = [r for r in win.reqs if r.arrival_time < clock.seconds]
    log("end to end: " + ", ".join(f"{k} {v!r}" for k, v in e2e.items()))
    log(f"window: {win.compiles} compiles, {win.loads} programs loaded "
        f"from the compile cache, setup {e2e['setup_s']:.3f} s; "
        f"{len(due)} requests due, {len(finished(win))} finished, "
        f"{sum(n for v in rec.tokens.values() for _, n in v)} tokens, "
        f"{len(clock.passes)} loop passes, "
        f"{len(samples['ttft'])} interactive TTFTs, "
        f"{len(samples['itl'])} interactive gaps")
    checks = verdict(cell.limits, got)
    ok = correct(checks, got)
    out = {"correct": ok, "attempted": len(due),
           "failed": 0 if ok else max(1, len(got["bad"]))}
    if win.trace is not None:
        ctx = Context(cell, prep.ref.dims(cell.conf), rec, clock, win.trace,
                      prep.peaks)
        out["metrics"] = {}
        for m in cell.per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end if m["name"] in e2e}
    dev0 = prep.devs[0]
    out["device"] = {"platform": dev0.platform, "kind": dev0.device_kind,
                     "count": len(prep.devs),
                     "memory_peak_bytes": win.memory_peak}
    if win.trace is not None:
        lo, hi = win.trace.window
        devs = win.trace.devices
        out["device"]["busy_s"] = sum(tr.busy(d.ops, lo, hi)
                                      for d in devs) / max(len(devs), 1)
        out["device"]["window_s"] = hi - lo
        ops = sorted(tr.op_totals(win.trace).items(), key=lambda kv: -kv[1])
        out["breakdown"] = {"device_ops": [list(kv) for kv in ops[:10]],
                            "idle_gaps": tr.idle_gaps(win.trace)}
    out["window"] = {"compiles": win.compiles, "cache_loads": win.loads,
                     "passes": len(clock.passes), "seconds": clock.seconds}
    out["checks"] = checks
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True) -> dict:
    prep = prepare(cell, seed, t_start, require_tpu)
    win = measure(prep, requests(prep, seed, seconds), seconds, trace)
    return report(prep, win, compare(prep, win, seed), t_start)
