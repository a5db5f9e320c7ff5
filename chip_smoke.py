"""Bring-up check of the served path on TPU chips.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four replicas on four chips

The default run, in one process on one chip:

1. runs the Pallas ``ssd_scan`` against the jnp reference at mamba2-1.3b
   widths on seeded inputs;
2. serves mamba2-1.3b at its published widths and depth (random weights
   from a seed, float32 as the engine serves) through ``RealCluster``,
   ``ChironController`` and ``serve_forever``: eight seeded requests from
   ``sim/workload.py``, interactive and batch, every one of which must
   finish.

``--four-chips`` runs only the replica path: the same requests served by
one replica, then by four replicas on four chips; every request's prefill
logits must agree between the two runs.

Times printed here are bring-up records, not benchmark metrics. The last
line of standard output is one JSON object naming the device. The script
exits non-zero, without that line, when JAX finds no TPU or a check fails.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.local_autoscaler import LocalAutoscaler  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.obs import FlightRecorder  # noqa: E402
from repro.obs.host import jit_booking  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402
from repro.serving.real_cluster import RealCluster, serve_forever  # noqa: E402
from repro.serving.request import RequestState, RequestType  # noqa: E402
from repro.sim.cluster import InstanceType  # noqa: E402
from repro.sim.controllers import ChironController  # noqa: E402
from repro.sim.workload import WorkloadSpec, generate  # noqa: E402

ARCH = "mamba2-1.3b"
MAX_SLOTS, MAX_LEN = 8, 1024
N_REQUESTS, SEED = 8, 0
PROMPT_LEN, OUTPUT_LEN = (64, 512), (16, 64)
INIT_BATCH = 2          # local autoscaler's starting batch on each replica
# Kernel error is measured against the largest reference value. The
# reference runs its matmuls at HIGHEST precision; the kernel's float32
# matmuls may take single bf16 passes on the MXU (8-bit mantissa, about
# 4e-3 relative error per product before errors average out), while a wrong
# decay, mask or carried state is off by O(1).
SSD_TOL = 1e-2
# Replicas run the same program with the same params on identical chips,
# so their logits should match bit for bit; allow rounding noise only.
REPLICA_TOL = 1e-5


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def jit_record(jit: dict) -> str:
    """The program's JIT counter (``repro.obs.host.jit_booking``) as one
    phrase: XLA compiles apart from programs loaded from the compile
    cache."""
    return (f"XLA compile {jit['compile_s']:.1f} s, compile-cache loads "
            f"{jit['load_s']:.1f} s ({jit['cache_hits']} hits, "
            f"{jit['cache_misses']} misses)")


def check_ssd_scan(cfg) -> None:
    h, p, n = cfg.n_ssm_heads, cfg.ssm.head_dim, cfg.ssm.state_dim
    chunk = cfg.ssm.chunk_size
    b, s = 2, 3 * chunk + 100          # several chunks and a padded tail
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    # step sizes in Mamba2's initial range (1e-3 .. 1e-1), so the carried
    # state reaches across chunk boundaries
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 4.0)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    h0 = 0.1 * jax.random.normal(ks[5], (b, h, p, n))
    y_k, h_k = ops.ssd_scan(x, dt, A, B, C, h0, chunk=chunk, backend="tpu")
    with jax.default_matmul_precision("highest"):
        y_r, h_r = ops.ssd_scan(x, dt, A, B, C, h0, chunk=chunk,
                                backend="ref")
    errs = {}
    for name, k, r in (("y", y_k, y_r), ("state", h_k, h_r)):
        k, r = np.asarray(k), np.asarray(r)
        if k.shape != r.shape or not np.isfinite(k).all():
            fail(f"ssd_scan {name}: shape {k.shape} vs {r.shape} or "
                 f"non-finite values")
        errs[name] = float(np.max(np.abs(k - r)) / np.max(np.abs(r)))
    print(f"ssd_scan tpu vs ref at {cfg.name} widths (b={b} s={s} h={h} "
          f"p={p} n={n} chunk={chunk}): max error / max |ref| = "
          f"y {errs['y']:.3e}, state {errs['state']:.3e} (tol {SSD_TOL:g})")
    if max(errs.values()) > SSD_TOL:
        fail(f"ssd_scan disagrees with the reference: {errs}")


def check_prefill_uses_kernel(cfg) -> None:
    if ops.default_backend() != "tpu":
        fail(f"ops.default_backend() is {ops.default_backend()!r}")
    model = Model(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), dtype=jnp.float32))
    tokens = {"tokens": jax.ShapeDtypeStruct((1, PROMPT_LEN[0]), jnp.int32)}
    text = jax.jit(functools.partial(model.prefill, dtype=jnp.float32)) \
        .lower(params, tokens).as_text()
    if "tpu_custom_call" not in text:
        fail("the served prefill does not lower to the Pallas kernel")
    print("served prefill lowers to tpu_custom_call (Pallas ssd_scan)")


def make_requests(cfg):
    reqs = generate(WorkloadSpec(n_requests=N_REQUESTS, arrival_rate=16.0,
                                 interactive_frac=0.5, model=cfg.name,
                                 seed=SEED))
    rng = np.random.default_rng(SEED)
    t0 = reqs[0].arrival_time
    for r in reqs:
        r.arrival_time -= t0
        r.prompt_len = int(np.clip(r.prompt_len, *PROMPT_LEN))
        r.output_len = int(np.clip(r.output_len, *OUTPUT_LEN))
        r.prompt_tokens = rng.integers(0, cfg.vocab_size, r.prompt_len,
                                       dtype=np.int32)
    kinds = {r.request_type for r in reqs}
    if kinds != {RequestType.INTERACTIVE, RequestType.BATCH}:
        fail(f"workload seed {SEED} does not mix request types: {kinds}")
    return reqs


@contextlib.contextmanager
def recording_prefills(store: dict):
    """Keep each request's prefill logits and the device they ran on."""
    served = Engine._prefill

    def _prefill(engine, req):
        logits, cache = served(engine, req)
        store[req.req_id] = logits
        return logits, cache

    Engine._prefill = _prefill
    try:
        yield store
    finally:
        Engine._prefill = served


def serve(cfg, n_replicas: int, rec: FlightRecorder):
    """Serve the seeded requests on ``n_replicas`` one-chip replicas;
    returns (per-request logits and devices in request order, the
    replicas' devices)."""
    reqs = make_requests(cfg)
    cluster = RealCluster(cfg, max_chips=n_replicas, max_slots=MAX_SLOTS,
                          max_len=MAX_LEN)
    ctrl = ChironController(model=cfg.name, init_batch=INIT_BATCH,
                            max_batch=MAX_SLOTS, min_instances=n_replicas)
    if n_replicas > 1:
        # bring every replica up front: the controller scales on load,
        # and eight requests need not make it add all four
        for _ in range(n_replicas):
            cluster.provision(cfg.name, InstanceType.MIXED, 0.0,
                              local_autoscaler=LocalAutoscaler(
                                  itl_slo=ctrl.itl_slo_interactive,
                                  init_batch=INIT_BATCH,
                                  max_batch=MAX_SLOTS))
    replicas = list(cluster.instances)
    jit0 = rec.jit_totals()
    with recording_prefills({}) as prefills:
        out = serve_forever(reqs, ctrl, cluster, max_steps=5000,
                            telemetry=rec)
    jit = {k: v - jit0[k] for k, v in rec.jit_totals().items()}
    replicas = replicas or list(cluster.instances)
    holds = [sorted({str(d) for a in jax.tree.leaves(
        (i.engine.params, i.engine.pool)) for d in a.devices()})
        for i in replicas]
    toks = sum(r.tokens_generated for r in reqs)
    itl = np.median([t for r in reqs for t in r.itl_samples] or [np.nan])
    print(f"{n_replicas} replica(s) served {out['finished']}/{out['total']} "
          f"requests ({sum(r.is_interactive for r in reqs)} interactive), "
          f"{toks} tokens, {out['steps']} loop steps, wall "
          f"{out['wall_s']:.1f} s, {jit_record(jit)}, "
          f"median step gap {itl * 1e3:.1f} ms, "
          f"scale-ups {out['scale_ups']}, replica devices {holds}")
    for r in reqs:
        if r.state != RequestState.FINISHED or \
                r.tokens_generated < r.output_len:
            fail(f"request {r.req_id} ended {r.state.value} after "
                 f"{r.tokens_generated}/{r.output_len} tokens")
        if r.req_id not in prefills:
            fail(f"request {r.req_id} was never prefilled")
        logits = np.asarray(prefills[r.req_id])
        if logits.shape != (1, cfg.vocab_size) or \
                not np.isfinite(logits).all():
            fail(f"request {r.req_id}: prefill logits {logits.shape} "
                 f"not finite")
    return [(np.asarray(prefills[r.req_id]),
             {str(d) for d in prefills[r.req_id].devices()})
            for r in reqs], holds


def one_chip(cfg, rec: FlightRecorder) -> None:
    check_ssd_scan(cfg)
    check_prefill_uses_kernel(cfg)
    serve(cfg, 1, rec)


def four_chips(cfg, rec: FlightRecorder) -> None:
    if len(jax.devices()) != 4:
        fail(f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    ref, _ = serve(cfg, 1, rec)
    gc.collect()        # free the one-replica cluster before the next
    got, holds = serve(cfg, 4, rec)
    if any(len(h) != 1 for h in holds) or \
            len({h[0] for h in holds}) != 4:
        fail(f"replicas do not sit on four distinct devices: {holds}")
    served_on = set().union(*(devs for _, devs in got))
    if len(served_on) != 4:
        fail(f"prefills ran on {sorted(served_on)}, not on all four chips")
    errs = [float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
            for (g, _), (r, _) in zip(got, ref)]
    print(f"prefill logits, four replicas vs one: max error / max |ref| "
          f"per request {['%.1e' % e for e in errs]} (tol {REPLICA_TOL:g})")
    if max(errs) > REPLICA_TOL:
        fail("four-replica prefill logits disagree with one replica")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica path and its "
                         "one-replica comparison")
    args = ap.parse_args()
    cache_dir = use_compile_cache()
    dev = device_info()
    if dev["platform"] != "tpu":
        fail(f"no TPU: JAX's first device is {dev['platform']}")
    print(f"device: {dev} (compile cache {cache_dir})")
    cfg = get_config(ARCH)
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.param_count() / 1e9:.2f} B params, float32, "
          f"{MAX_SLOTS} slots x {MAX_LEN} positions")
    rec = FlightRecorder()
    # repro-lint: ok(DET202, wall time of a chip run, printed as a record)
    t0 = time.monotonic()
    with jit_booking(rec):
        (four_chips if args.four_chips else one_chip)(cfg, rec)
    # repro-lint: ok(DET202, wall time of a chip run, printed as a record)
    wall = time.monotonic() - t0
    print(f"total {wall:.1f} s, of which {jit_record(rec.jit_totals())}")
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
