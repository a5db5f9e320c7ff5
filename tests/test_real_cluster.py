"""Real-plane cluster: the identical ChironController over real JAX
engines — provision, route, preempt, migrate, retire."""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.serving.global_queue import GlobalQueue
from repro.serving.real_cluster import RealCluster, RealInstance, serve_forever
from repro.serving.request import (Request, RequestState, RequestType,
                                   make_batch, make_interactive)
from repro.sim.cluster import InstanceType
from repro.sim.controllers import ChironController


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("olmo-1b")


def test_chiron_controller_drives_real_engines(cfg):
    cluster = RealCluster(cfg, max_chips=4, max_slots=3, max_len=64)
    ctrl = ChironController(model="llama-8b", init_batch=2, max_batch=3)
    reqs = ([make_interactive(8, 6, arrival=0.0) for _ in range(4)] +
            [make_batch(8, 10, arrival=0.0, ttft_slo=30.0)
             for _ in range(4)])
    # deterministic fake clock: one "second" per call
    t = iter(range(100000))
    out = serve_forever(reqs, ctrl, cluster,
                        clock=lambda: float(next(t)) * 0.05,
                        max_steps=800)
    assert out["finished"] == out["total"] == 8, out
    assert cluster.scale_ups >= 1
    for r in reqs:
        assert r.state == RequestState.FINISHED
        assert r.tokens_generated >= r.output_len


def test_migration_preserves_generation(cfg):
    a = RealInstance(cfg, InstanceType.MIXED, 0.0, max_slots=2, max_len=64)
    b = RealInstance(cfg, InstanceType.MIXED, 0.0, max_slots=2, max_len=64)
    a.activate_if_ready(0.0)
    b.activate_if_ready(0.0)
    req = make_batch(8, 16)
    a.admit(req, 0.0)
    for _ in range(5):
        a.step(0.0)
    toks_before = req.tokens_generated
    assert toks_before > 0

    cluster = RealCluster.__new__(RealCluster)  # migrate() only needs ducks
    assert RealCluster.migrate(cluster, req.req_id, a, b)
    assert a.n_running == 0
    while req.state != RequestState.FINISHED:
        st = b.step(0.0)
        if not st.n_active and not b.engine.waiting:
            break
    assert req.state == RequestState.FINISHED
    assert req.tokens_generated >= req.output_len
    assert req.tokens_generated >= toks_before  # no progress lost


def test_rebalance_moves_batch_off_crowded(cfg):
    cluster = RealCluster(cfg, max_chips=2, max_slots=2, max_len=64)
    a = cluster.provision("x", InstanceType.MIXED, 0.0, static_batch=2)
    b = cluster.provision("x", InstanceType.MIXED, 0.0, static_batch=2)
    a.activate_if_ready(0.0)
    b.activate_if_ready(0.0)
    for r in (make_batch(8, 30), make_batch(8, 30)):
        a.admit(r, 0.0)
    a.step(0.0)
    assert a.n_running == 2 and b.n_running == 0
    moved = cluster.rebalance(0.0)
    b.step(0.0)
    assert moved == 1
    assert a.n_running == 1 and b.n_running == 1


def test_one_routing_pass_spreads_arrivals(cfg):
    """A request handed to an instance counts against its batch limit
    before the engine's next step, so one routing pass fills instances
    up to their limit instead of queueing every arrival on one."""
    cluster = RealCluster(cfg, max_chips=2, max_slots=2, max_len=64)
    insts = [cluster.provision("m", InstanceType.MIXED, 0.0, static_batch=2)
             for _ in range(2)]
    for inst in insts:
        inst.activate_if_ready(0.0)
    queue = GlobalQueue()
    for _ in range(5):
        queue.push(make_interactive(8, 4, model="m"))
    ChironController(model="m").route(cluster, queue, 0.0)
    assert [i.n_running for i in insts] == [2, 2]
    assert len(queue) == 1


def test_engine_preempted_batch_is_requeued_and_finishes(cfg):
    """An interactive request waiting on a full engine evicts batch work
    at the engine's step; serve_forever hands the victim back to the
    global queue, so it resumes and finishes instead of being dropped."""
    cluster = RealCluster(cfg, max_chips=1, max_slots=2, max_len=64)
    inst = cluster.provision("m", InstanceType.MIXED, 0.0, static_batch=1)
    inst.activate_if_ready(0.0)
    batch = make_batch(8, 12, model="m", ttft_slo=30.0)
    inter = make_interactive(8, 4, model="m")
    inst.admit(batch, 0.0)
    inst.step(0.0)
    inst.admit(inter, 0.0)            # waits in the engine behind a full batch
    t = iter(range(100000))
    serve_forever([], ChironController(model="m", max_batch=1), cluster,
                  clock=lambda: float(next(t)) * 0.05, max_steps=400)
    assert batch.preemptions == 1
    for r in (inter, batch):
        assert r.state == RequestState.FINISHED
        assert r.tokens_generated >= r.output_len


_PLACEMENT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.models import Model
    from repro.serving.real_cluster import RealCluster
    from repro.serving.request import make_interactive
    from repro.sim.cluster import InstanceType

    cfg = get_smoke_config("mamba2-1.3b")
    cluster = RealCluster(cfg, max_chips=4, max_slots=2, max_len=64)
    insts = [cluster.provision(cfg.name, InstanceType.MIXED, 0.0)
             for _ in range(4)]
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 40)
    ref, _ = Model(cfg).prefill(cluster._shared_params,
                                {"tokens": jax.numpy.asarray(prompt)[None]})
    out = []
    for inst in insts:
        leaves = jax.tree.leaves((inst.engine.params, inst.engine.pool))
        req = make_interactive(len(prompt), 4, model=cfg.name)
        req.prompt_tokens = prompt
        logits, _ = inst.engine._prefill(req)
        out.append({
            "holds": sorted({str(d) for a in leaves for d in a.devices()}),
            "logits_on": [str(d) for d in logits.devices()],
            "err": float(np.max(np.abs(np.asarray(logits) -
                                       np.asarray(ref))))})
    print(json.dumps({"n_dev": len(jax.devices()), "insts": out}))
""")


def test_replicas_on_distinct_devices():
    """Four instances on a four-device host each hold their params and
    slot pool on a device of their own, and prefill there to the same
    logits as the one-device run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _PLACEMENT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_dev"] == 4
    holds = [i["holds"] for i in rec["insts"]]
    assert all(len(h) == 1 for h in holds), holds
    assert len({h[0] for h in holds}) == 4, holds
    for i in rec["insts"]:
        assert i["logits_on"] == i["holds"], i
        assert i["err"] == 0.0, i
