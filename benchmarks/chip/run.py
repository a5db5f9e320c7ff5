"""The benchmark's command: one run of one cell on the chips of this host.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Loads the cell named in BENCHMARK.json, makes its weights and traffic from
the seed, warms up every program the window runs, serves one window of
``--seconds`` through the program's ``serve_forever``, checks the served
tokens against the plain reference, and prints the result as the last line
of standard output: one JSON object. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiled
window. Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chipbench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    from chipbench import runner
    cell = runner.load_cell(args.workload)
    from repro.launch.compile_cache import use_compile_cache
    import jax
    cache = use_compile_cache()
    # every program, however quick to compile, goes to the cache, so that
    # no run after a checkout's first compiles anything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    runner.log(f"{cell.name}: seed {args.seed}, {args.seconds:g} s, "
               f"trace {args.trace}, compile cache {cache}")
    out = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                     t_start=T_START)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
