"""Serving launcher: run a real continuous-batching instance with Chiron's
local autoscaler closed-loop on measured ITL/throughput.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b \
      --requests 24 --max-slots 8 --itl-slo 0.5

Serves the reduced (smoke) model variant by default, or the full config
with ``--full-config``, as one unsharded float32 engine on JAX's default
device. ``chip_smoke.py`` at the repo root is the full-width TPU check.
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.core.backpressure import LocalMetrics
from repro.core.local_autoscaler import LocalAutoscaler
from repro.launch.compile_cache import use_compile_cache
from repro.serving.engine import Engine
from repro.sim.workload import WorkloadSpec, generate


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=160)
    ap.add_argument("--itl-slo", type=float, default=0.5)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full assigned config (TPU-scale)")
    ap.add_argument("--autoscale-every", type=int, default=5)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch) if args.full_config \
        else get_smoke_config(args.arch)
    print(f"serving {cfg.name} ({cfg.arch_type}), "
          f"{cfg.param_count()/1e6:.1f}M params")
    eng = Engine(cfg, max_slots=args.max_slots, max_len=args.max_len,
                 dtype=jnp.float32)
    scaler = LocalAutoscaler(itl_slo=args.itl_slo, init_batch=2,
                             max_batch=args.max_slots)

    spec = WorkloadSpec(n_requests=args.requests, arrival_rate=50.0,
                        interactive_frac=0.7, model=cfg.name)
    reqs = generate(spec)
    for r in reqs:
        r.prompt_len = min(r.prompt_len, args.max_len // 3)
        r.output_len = min(r.output_len, args.max_len // 3)
        eng.submit(r)

    # repro-lint: ok(DET202, real-engine wall clock)
    t0 = time.monotonic()
    steps = 0
    while eng.waiting or eng.n_active:
        stats = eng.step()
        steps += 1
        if steps % args.autoscale_every == 0 and stats.n_active:
            bs = scaler.update(LocalMetrics(
                observed_itl=stats.itl, throughput=stats.throughput or 1.0,
                itl_slo=args.itl_slo))
            eng.set_max_batch_size(bs)
            print(f"step {steps:4d}: active={stats.n_active} itl="
                  f"{stats.itl*1e3:.0f}ms thr={stats.throughput:.1f} tok/s "
                  f"-> max_batch={bs}")

    # repro-lint: ok(DET202, real-engine wall clock)
    wall = time.monotonic() - t0
    done = [r for r in reqs if r.state.value == "finished"]
    toks = sum(r.tokens_generated for r in reqs)
    print(f"\nserved {len(done)}/{len(reqs)} requests, {toks} tokens in "
          f"{wall:.1f}s ({toks/wall:.1f} tok/s), final batch size "
          f"{scaler.max_batch_size}")
    itl_ok = sum(r.itl_met() for r in done)
    print(f"ITL SLO met: {itl_ok}/{len(done)}")


if __name__ == "__main__":
    main()
