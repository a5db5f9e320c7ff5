"""Find a cell's knee on the chip: the highest interactive rate at which at
least 90% of interactive requests meet the paper's limits (TTFT 10 s, mean
gap between tokens 0.2 s) and the interactive queue does not grow.

    python3 benchmarks/chip/knee_sweep.py --workload <cell> \
        --rates 0.3,0.5,0.7 --seconds 40 --seed 7

One process sets the cell up once (its weights, warm-up), then serves one
window per rate on a fresh cluster, with every stream's rate scaled so the
interactive stream runs at the rate given. A request that has no first
token at the window's end counts as missing the TTFT limit once it has
waited longer than the limit; before that it is not counted. The queue
grows when more interactive requests wait for a first token at the
window's end than at its middle (by more than two). Results go to
``<out>/knee/<cell>.json`` (``--out``, default ``build/chipbench``) and to
standard output. The chosen rate, 0.8 of the knee, is written into the
traffic file by hand.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def waiting(win, t: float) -> int:
    t0 = win.clock.t0
    n = 0
    for r in win.reqs:
        if not r.is_interactive or t0 + r.arrival_time > t:
            continue
        ev = win.rec.tokens.get(r.req_id)
        if not ev or ev[0][0] > t:
            n += 1
    return n


def attainment(win, slo: dict) -> dict:
    from chipbench import runner
    t0, end = win.clock.t0, win.clock.end
    met = missed = 0
    for r in win.reqs:
        if not r.is_interactive or r.arrival_time >= win.clock.seconds:
            continue
        ev = [(t, n) for t, n in win.rec.tokens.get(r.req_id, []) if t <= end]
        due = t0 + r.arrival_time
        if not ev:
            if end - due > slo["ttft_s"]:
                missed += 1
            continue
        gaps = [b[0] - a[0] for a, b in zip(ev, ev[1:])]
        gaps += [0.0] * sum(n - 1 for _, n in ev)
        ok = ev[0][0] - due <= slo["ttft_s"] and \
            (not gaps or statistics.fmean(gaps) <= slo["itl_s"])
        met, missed = met + ok, missed + (not ok)
    e2e, _ = runner.end_to_end(win.reqs, win.rec, win.clock)
    mid = waiting(win, t0 + 0.5 * win.clock.seconds)
    last = waiting(win, end)
    return dict(e2e, attainment=met / max(met + missed, 1), counted=met +
                missed, waiting_mid=mid, waiting_end=last,
                queue_grows=last > mid + 2)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=7)
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
    inter = [s for s in cell.traffic["streams"]
             if s["class"] == "interactive" and "rate" in s]
    if len(inter) != 1:
        raise SystemExit("knee_sweep: the cell needs one interactive stream")
    base = inter[0]["rate"]
    prep = runner.prepare(cell, args.seed, T_START)
    rows = []
    for rate in [float(x) for x in args.rates.split(",")]:
        reqs = runner.requests(prep, args.seed, args.seconds, rate / base)
        win = runner.measure(prep, reqs, args.seconds, trace=False)
        row = dict(rate=rate, compiles=win.compiles,
                   **attainment(win, cell.traffic["slo"]["interactive"]))
        del win     # and the logits it holds on the device
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = [r["rate"] for r in rows
          if r["attainment"] >= 0.9 and not r["queue_grows"]]
    knee = max(ok) if ok else None
    out = {"workload": cell.name, "seed": args.seed,
           "seconds": args.seconds, "rows": rows, "knee": knee,
           "device": prep.devs[0].device_kind}
    dest = ROOT / args.out / "knee"
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"{cell.name}.json").write_text(json.dumps(out, indent=1))
    print(json.dumps({"workload": cell.name, "knee": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
