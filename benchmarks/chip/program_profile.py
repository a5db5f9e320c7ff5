"""The program's own spans and JIT counter in a cell's windows, on the chip.

    python3 benchmarks/chip/program_profile.py --workload <cell> \
        --seeds 11,12,13 --seconds 51 [--traced-seed 14]

One process sets the cell up once (the weights of the first seed, the
warm-up), then serves windows as ``run.py`` does, each on a fresh cluster
with the traffic of its seed. For each seed in ``--seeds``: one window with
the program's flight recorder unarmed and one with it armed
(``serve_forever(telemetry=FlightRecorder())``), in alternating order, no
profile taken; then, with ``--traced-seed``, one armed window under the
profiler. Each window reports the end-to-end metrics, the benchmark's
median ``chipbench.step`` of steps without an admission, and from an armed
recorder ``engine.step_host_ms`` and ``prefill.jit_ms_p50``
(``chipbench.program``); the traced window also the device's idle time by
innermost program span, ``device.idle_host_pct``, the offsets between the
recorder's ``engine.step`` rows and their profiler twins, and the cell's
per-layer metrics. One JSON line per window goes to standard output and to
``<out>/program_profile/<cell>.jsonl`` (``--out``, default
``build/chipbench``).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def plain_step_ms(rec) -> float | None:
    """Median ``chipbench.step`` of steps that decoded and admitted
    nothing: the same reading whether the program's recorder is armed."""
    admits = sorted(a[1] for a in rec.admits)
    out = []
    for _, t0, t1, n in rec.steps:
        if n and not any(t0 <= a <= t1 for a in admits):
            out.append(1e3 * (t1 - t0))
    return statistics.median(out) if out else None


def window(prep, seed: int, seconds: float, armed: bool, traced: bool):
    """Serve one window as ``runner.measure`` does, handing
    ``serve_forever`` a recorder when ``armed``; returns the window, the
    recorder and the program's spans in the trace."""
    import jax

    from chipbench import program, runner, serve
    from chipbench import trace as tr
    from repro.obs import FlightRecorder
    from repro.serving.real_cluster import serve_forever

    reqs = runner.requests(prep, seed, seconds)
    cluster, ctrl = serve.build(prep.cfg, prep.params,
                                prep.cell.conf["serve"], prep.cell.chips)
    engines = [i.engine for i in cluster.instances]
    for e in engines:
        serve.warm_decode(e)
    rec = serve.Recorder(traced=traced)
    for i, e in enumerate(engines):
        serve.instrument(e, i, rec)

    def on_pass(t):
        limits = [e.max_batch_size for e in engines]
        if not rec.limits or rec.limits[-1][1] != limits:
            rec.limits.append((t, limits))

    clock = serve.WindowClock(seconds, on_pass=on_pass)
    obs = FlightRecorder() if armed else None
    trace_dir = None
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="program-profile-")
        jax.profiler.start_trace(trace_dir, profiler_options=tr.options())
    c0 = prep.watch.snap()
    with serve.Annotation("chipbench.window"):
        try:
            serve_forever(reqs, ctrl, cluster, max_steps=1 << 62,
                          clock=clock, telemetry=obs)
        except serve.WindowClosed:
            pass
    c1 = prep.watch.snap()
    if traced:
        jax.profiler.stop_trace()
    tr_data = prog = None
    if traced:
        tr_data = tr.load(trace_dir)
        prog = program.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in prep.devs)
    del cluster, ctrl, engines
    rec.decode_inputs.clear()
    rec.prefill_logits.clear()
    gc.collect()
    win = runner.Window(reqs, rec, clock, c1[0] - c0[0], c1[1] - c0[1], mem,
                        tr_data)
    return win, obs, prog


def report(prep, win, obs, prog, seed: int, armed: bool) -> dict:
    from chipbench import program, runner
    from chipbench import trace as tr

    e2e, samples = runner.end_to_end(win.reqs, win.rec, win.clock)
    row = {"seed": seed, "armed": armed, "traced": win.trace is not None,
           "itl_p50_ms": e2e.get("itl_p50_ms"),
           "itl_p98_ms": e2e.get("itl_p98_ms"),
           "gaps": len(samples["itl"]),
           "chipbench_step_ms_p50": plain_step_ms(win.rec),
           "compiles": win.compiles, "cache_loads": win.loads,
           "passes": len(win.clock.passes)}
    if obs is not None:
        steps = program.step_host_ms(obs)
        jit = program.prefill_jit_ms(obs)
        row.update({
            "engine.step_host_ms": program.median(steps),
            "decode_steps": len(steps or []),
            "prefill.jit_ms_p50": program.median(jit),
            "prefill_jit_ms": [round(v, 3) for v in jit or []],
            "host_spans": obs.host_spans.n,
            "jit": obs.jit_totals(),
            "self_ms": {k: round(v, 3) for k, v in sorted(
                (program.self_ms(obs) or {}).items(), key=lambda kv: -kv[1])},
            "decisions": [list(r.values()) for r in obs.decisions.rows()][:40],
        })
    if win.trace is not None:
        spans, start = prog if prog is not None else (None, None)
        lo, hi = win.trace.window
        by = program.idle_by_span(win.trace, spans) or {}
        idle = sum(by.values())
        offs = program.twin_offsets_us(obs, spans, start) if spans else None
        ctx = runner.Context(prep.cell, prep.ref.dims(prep.cell.conf),
                             win.rec, win.clock, win.trace, prep.peaks)
        row.update({
            "window_s": hi - lo,
            "busy_s": [tr.busy(d.ops, lo, hi) for d in win.trace.devices],
            "device.idle_host_pct": program.idle_host_pct(win.trace, spans),
            "idle_by_span_s": dict(sorted(by.items(), key=lambda kv: -kv[1])),
            "serve_pass_idle_share": (by.get("serve.pass", 0.0) / idle
                                      if idle else None),
            "twin_offsets_us": None if not offs else {
                "n": len(offs), "min": min(offs), "max": max(offs),
                "median": statistics.median(offs)},
            "idle_gaps": tr.idle_gaps(win.trace),
            "per_layer": {m["name"]: runner.reader(m["name"])(ctx)
                          for m in prep.cell.per_layer},
        })
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="",
                    help="seeds of the unarmed/armed pairs of windows")
    ap.add_argument("--traced-seed", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default="build/chipbench",
                    help="directory for the results, under the checkout")
    args = ap.parse_args()
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax

    from chipbench import runner
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell = runner.load_cell(args.workload)
    seeds = [int(x) for x in args.seeds.split(",") if x]
    plan = [(s, armed) for i, s in enumerate(seeds)
            for armed in ((False, True) if i % 2 == 0 else (True, False))]
    if args.traced_seed is not None:
        plan.append((args.traced_seed, None))
    prep = runner.prepare(cell, (seeds or [args.traced_seed])[0], T_START)
    dest = ROOT / args.out / "program_profile"
    dest.mkdir(parents=True, exist_ok=True)
    with open(dest / f"{cell.name}.jsonl", "w") as f:
        for seed, armed in plan:
            traced = armed is None
            win, obs, prog = window(prep, seed, args.seconds,
                                    traced or armed, traced)
            row = report(prep, win, obs, prog, seed, traced or armed)
            row["device"] = prep.devs[0].device_kind
            line = json.dumps(row)
            f.write(line + "\n")
            f.flush()
            print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
