"""Benchmark runner: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Sections:
  fig3   ITL/throughput vs batch size          (perf model)
  fig6   request-group hysteresis              (sim, via fig19 module)
  fig9   W_A interactive sweep                 (sim)
  fig10  W_B batch-queue sweep                 (sim)
  fig11  local-autoscaler convergence          (closed loop)
  fig12  convergence time 8B vs 70B            (closed loop)
  fig13  queue size vs batch TTFT SLO          (sim)
  fig14  waiting-time estimator R^2            (statistical)
  fig16  ITL SLO sweep table                   (sim)
  fig17  burstiness robustness                 (sim)
  fig18  ablation                              (sim)
  fig19  GPUs-over-time + fig2 GPU savings     (sim)
  kernels  micro-benchmarks                    (jit on CPU)
  roofline per-(arch x shape) dry-run terms    (reads results/)

Run a subset: ``python -m benchmarks.run fig9 fig18``.
"""
import importlib
import sys
import time

MODULES = [
    "fig3_batch_tradeoff",
    "fig6_request_groups",
    "fig9_interactive",
    "fig10_batch",
    "fig11_convergence",
    "fig13_queue_slo",
    "fig14_estimator",
    "fig16_itl_sweep",
    "fig17_burstiness",
    "fig18_ablation",
    "fig19_timeline",
    "scenario_sweep",
    "arch_sweep",
    "appendix_a1_load_time",
    "kernels_micro",
    "roofline_table",
]


def main() -> None:
    want = sys.argv[1:]
    mods = [m for m in MODULES
            if not want or any(w in m for w in want)]
    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for name in mods:
        mod = importlib.import_module(f"benchmarks.{name}")
        try:
            for row in mod.run():
                row.print()
        except Exception as e:
            print(f"{name}/ERROR,0,{type(e).__name__}={e}")
            failed.append(name)
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
