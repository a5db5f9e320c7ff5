"""Readings that a cell's output limit is set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 101,102,... --seconds 40

One process sets the cell up once, then for each seed makes that seed's
weights and traffic, serves one window at the cell's own load, and reads
on a sample of the finished requests (``chipbench.check``): the program's
worst relative logit error (``logit_err``, the lower reading) and the
control's, the reference computed in bfloat16 in the program's place, at
the same positions (``control_logit_err``, the upper reading;
``reference/precision.py``). The limit, between the two, goes by hand
into ``limits/<cell>.json`` with the readings. Results go to
``<out>/calibrate/<cell>.json`` (``--out``, default ``build/chipbench``)
and to standard output.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
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
    seeds = [int(x) for x in args.seeds.split(",")]
    prep = runner.prepare(cell, seeds[0], T_START)
    rows = []
    for seed in seeds:
        if seed != seeds[0]:
            prep.weights = prep.params = None
            gc.collect()
            prep.weights = runner.make_weights(prep.ref, cell.conf, seed)
            prep.params = prep.adapter.program_params(prep.weights)
        win = runner.measure(prep, runner.requests(prep, seed, args.seconds),
                             args.seconds, trace=False)
        got = runner.compare(prep, win, seed, control=True)
        row = {"seed": seed, "logit_err": got["logit_err"],
               "control_logit_err": got.get("control_logit_err"),
               "token_mismatches": got["token_mismatches"],
               "positions": got["positions"], "bad": got["bad"],
               "compiles": win.compiles,
               "restored": sum(r.preemptions > 0 for r in runner.finished(win))}
        rows.append(row)
        print(json.dumps(row), flush=True)
    lower = max(r["logit_err"] for r in rows)
    upper = min(r["control_logit_err"] or float("inf") for r in rows)
    out = {"workload": cell.name, "seconds": args.seconds, "rows": rows,
           "lower": lower, "upper": upper, "ratio": upper / max(lower, 1e-30),
           "device": prep.devs[0].device_kind}
    dest = ROOT / args.out / "calibrate"
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"{cell.name}.json").write_text(json.dumps(out, indent=1))
    print(json.dumps({k: out[k] for k in ("workload", "lower", "upper",
                                          "ratio")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
