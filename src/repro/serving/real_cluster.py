"""Real-plane serving cluster: the SAME ChironController that drives the
simulator drives actual JAX engines here (duck-typed to the SimInstance /
SimCluster protocol the controllers use). Each instance is one unsharded
float32 ``Engine`` replica placed on a device of its own from
``jax.devices()`` (instances share a device once there are more instances
than devices, as on a one-device CPU host). ``chip_smoke.py`` serves
mamba2-1.3b at full width this way on one TPU chip, and four replicas on
four chips; sharded (multi-chip) instances are not implemented.

Also implements Llumnix-style cross-instance request migration on top of
the engine's slot read/restore (used for rebalancing mixed instances).
"""
from __future__ import annotations

# mirror-sync: module ok(real engine has no RequestLedger/InstancePlane)
# The columnar mirrors exist only in the simulated data plane.
import contextlib
import itertools
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.local_autoscaler import LocalAutoscaler
from repro.core.backpressure import LocalMetrics
from repro.obs.host import jit_booking, span
from repro.obs.recorder import resolve
from repro.serving.engine import Engine, StepStats
from repro.serving.request import Request, RequestState, RequestType
from repro.sim.cluster import SLOW_SUSPECT_RATIO, InstanceState, InstanceType
from repro.sim.perf_model import PerfModel

_inst_ids = itertools.count(1000)


class RealInstance:
    """Engine + instance type + local autoscaler; SimInstance-compatible."""

    # the cluster that provisioned it, and the flight recorder (repro.obs)
    # the cluster hands over while telemetry is armed
    cluster = None
    obs = None

    def __init__(self, cfg: ModelConfig, itype: InstanceType, now: float, *,
                 max_slots: int = 6, max_len: int = 128,
                 local_autoscaler: Optional[LocalAutoscaler] = None,
                 static_batch: Optional[int] = None,
                 load_time: float = 0.0, params=None, seed: int = 0,
                 model: str = "llama-8b",
                 device: Optional[jax.Device] = None):
        self.id = next(_inst_ids)
        self.cfg = cfg
        self.model = model           # served model (multi-model routing key)
        self.itype = itype
        self.state = InstanceState.LOADING
        self.ready_time = now + load_time
        self.local = local_autoscaler
        self.static_batch = static_batch
        self.engine = Engine(cfg, key=jax.random.PRNGKey(seed),
                             params=params, max_slots=max_slots,
                             max_len=max_len,
                             max_batch_size=(local_autoscaler.max_batch_size
                                             if local_autoscaler
                                             else static_batch or max_slots),
                             dtype=jnp.float32, device=device,
                             instance_id=self.id)
        self._last_stats: Optional[StepStats] = None
        # slow-node health protocol (SimInstance parity): the routing
        # layer reads ``suspected_slow``; a real deployment would EWMA
        # observed step time against a per-hardware baseline, but the
        # reduced CPU engines here have no meaningful expected-ITL model,
        # so real instances never self-report degradation
        self.health_ewma = 1.0

    def update_health(self, alpha: float = 0.5) -> None:
        pass

    @property
    def suspected_slow(self) -> bool:
        return self.health_ewma > SLOW_SUSPECT_RATIO

    # ------------------------------------------------ protocol: state
    def activate_if_ready(self, now: float) -> None:
        # Real engine: no simulated-float drift between ready_time and now.
        # repro-lint: ok(DET205, both times come from one monotonic clock)
        if self.state == InstanceState.LOADING and now >= self.ready_time:
            self.state = InstanceState.ACTIVE

    @property
    def active(self) -> bool:
        return self.state == InstanceState.ACTIVE

    @property
    def max_batch_size(self) -> int:
        if self.local is not None:
            return self.local.max_batch_size
        return self.static_batch or self.engine.max_slots

    @property
    def n_running(self) -> int:
        # an admitted request waits in the engine until its next step; it
        # counts against the batch limit at once, as on a SimInstance, so
        # one routing pass cannot hand every arrival to the same instance
        return self.engine.n_active + self.engine.n_waiting

    @property
    def running(self):
        """SimInstance-protocol: items expose ``.request``."""
        return [s for s in self.engine.slots if s.active]

    def slot_utilization(self) -> float:
        return self.n_running / max(self.max_batch_size, 1)

    def kv_utilization(self) -> float:
        return self.slot_utilization()

    def runs_interactive(self) -> bool:
        return any(s.request.is_interactive for s in self.running)

    def n_running_batch(self) -> int:
        return sum(1 for s in self.running
                   if not s.request.is_interactive)

    def min_itl_slo(self) -> float:
        return min((s.request.slo.itl for s in self.running),
                   default=float("inf"))

    def spare_throughput(self) -> float:
        spare = self.max_batch_size - self.n_running
        thr = self.engine.throughput()
        if spare <= 0 or self.n_running == 0 or thr <= 0:
            return 0.0
        return thr * spare / self.n_running

    # ------------------------------------------------ protocol: intake
    def can_admit(self, req: Request) -> bool:
        if not self.active or self.n_running >= \
                min(self.max_batch_size, self.engine.max_slots):
            return False
        return req.model == self.model   # never serve a wrong-model request

    def admit(self, req: Request, now: float) -> None:
        self.engine.submit(req)

    def evict_one_batch(self, now: float) -> Optional[Request]:
        victim = self.engine.preempt_one_batch(now)
        if victim is not None and self.obs is not None:
            self.obs.record_evict(self.cluster, now, victim, self)
        return victim

    # ------------------------------------------------ telemetry
    def attach(self, obs) -> None:
        """Hand ``obs`` (a FlightRecorder, or None to detach) to this
        instance and its engine; an armed recorder gets the batch limit
        the instance holds now."""
        self.obs = self.engine.obs = obs
        if obs is not None:
            limit = self.engine.max_batch_size
            obs.record_batch_limit(self.cluster, self.cluster.now, self,
                                   limit, limit)

    # ------------------------------------------------ execution
    def step(self, now: float) -> StepStats:
        stats = self.engine.step()
        self._last_stats = stats
        if self.obs is not None:
            for victim in stats.preempted:
                self.obs.record_evict(self.cluster, now, victim, self)
        return stats

    def update_local_autoscaler(self) -> None:
        if self.local is None or self._last_stats is None or \
                self._last_stats.n_active == 0:
            return
        itl, slo = self._last_stats.itl, self.min_itl_slo()
        before = self.engine.max_batch_size
        self.local.update(LocalMetrics(
            observed_itl=itl,
            throughput=max(self._last_stats.throughput, 1e-6),
            itl_slo=slo,
            n_active=self._last_stats.n_active,
            batch_size=self.local.max_batch_size))
        self.engine.set_max_batch_size(self.local.max_batch_size)
        if self.obs is not None and self.engine.max_batch_size != before:
            self.obs.record_batch_limit(self.cluster, self.cluster.now,
                                        self, before,
                                        self.engine.max_batch_size, itl, slo)

    # ------------------------------------------------ migration
    def migrate_out(self, req_id: int) -> Optional[Request]:
        """Remove a running request, carrying its KV state (Llumnix-style
        live migration)."""
        for i, s in enumerate(self.engine.slots):
            if s.active and s.request.req_id == req_id:
                req = s.request
                req.saved_kv = self.engine._read_slot(i)
                req.state = RequestState.PREEMPTED
                self.engine.slots[i] = type(s)()
                return req
        return None


class RealCluster:
    """SimCluster-compatible manager over real engines.

    Instances start from one set of initialized params per model config
    (real clusters load the same checkpoint), copied to each instance's
    device; instances on the device that holds them share them without a
    copy. `load_time` models bring-up delay in the driver's clock without
    sleeping.
    """

    def __init__(self, cfg: ModelConfig, *, max_chips: int = 64,
                 chips_per_instance: int = 1, max_slots: int = 6,
                 max_len: int = 128, load_time: float = 0.0):
        self.cfg = cfg
        self.max_chips = max_chips
        self.chips_per_instance = chips_per_instance
        self.max_slots = max_slots
        self.max_len = max_len
        self.load_time = load_time
        self.instances: List[RealInstance] = []
        self.scale_ups = 0
        self.scale_downs = 0
        self.chip_seconds = 0.0
        self.peak_chips = 0
        # serving time of the last loop pass (``serve_forever`` sets it),
        # which stamps the decision rows of an armed recorder
        self.now = 0.0
        self.obs = None
        model_seed = jax.random.PRNGKey(0)
        from repro.models import Model
        self._shared_params = Model(cfg).init(model_seed, dtype=jnp.float32)
        # planning estimate for Algorithm 2's Theta (perf model of the
        # full-size family member; production would calibrate online)
        self.perf_factory: Callable[[str], PerfModel] = \
            lambda name: PerfModel(name if name in
                                   ("llama-8b", "llama-70b") else "llama-8b")

    # ------------------------------------------------ protocol
    def by_type(self, itype: InstanceType) -> List[RealInstance]:
        return [i for i in self.instances if i.itype == itype]

    def by_model(self, model: str, itype: InstanceType) -> List[RealInstance]:
        return [i for i in self.instances
                if i.itype == itype and i.model == model]

    def instances_of(self, model: str) -> List[RealInstance]:
        return [i for i in self.instances if i.model == model]

    def active_instances(self) -> List[RealInstance]:
        return [i for i in self.instances if i.active]

    def used_chips(self) -> int:
        return len(self.instances) * self.chips_per_instance

    def _free_device(self) -> jax.Device:
        """A device no live instance holds; when every device is taken
        (more instances than devices), the least-shared one."""
        held = [i.engine.device for i in self.instances]
        return min(jax.devices(), key=held.count)

    def provision(self, model: str, itype: InstanceType, now: float,
                  **inst_kw) -> Optional[RealInstance]:
        if self.used_chips() + self.chips_per_instance > self.max_chips:
            return None
        chips0 = self.used_chips()
        inst = RealInstance(self.cfg, itype, now, max_slots=self.max_slots,
                            max_len=self.max_len,
                            load_time=self.load_time,
                            params=self._shared_params, model=model,
                            device=self._free_device(), **inst_kw)
        inst.cluster = self
        self.instances.append(inst)
        self.scale_ups += 1
        self.peak_chips = max(self.peak_chips, self.used_chips())
        if self.obs is not None:
            self.obs.record_provision(self, now, model, itype, chips0,
                                      self.used_chips())
            inst.attach(self.obs)
        return inst

    def retire(self, inst: RealInstance) -> List[Request]:
        chips0 = self.used_chips()
        displaced = []
        for i, s in enumerate(inst.engine.slots):
            if s.active:
                r = inst.migrate_out(s.request.req_id)
                if r is not None:
                    displaced.append(r)
        displaced.extend(inst.engine.waiting)
        inst.engine.waiting.clear()
        inst.state = InstanceState.RETIRED
        self.instances.remove(inst)
        self.scale_downs += 1
        if self.obs is not None:
            self.obs.record_retire(self, self.now, inst, chips0,
                                   self.used_chips())
            inst.attach(None)
        return displaced

    def attach(self, obs) -> None:
        """Hand ``obs`` (a FlightRecorder, or None to detach) to the
        cluster, its instances and their engines; instances provisioned
        later get it too."""
        self.obs = obs
        for inst in self.instances:
            inst.attach(obs)

    def tick_accounting(self, dt: float) -> None:
        self.chip_seconds += self.used_chips() * dt

    # ------------------------------------------------ migration
    def migrate(self, req_id: int, src: RealInstance,
                dst: RealInstance) -> bool:
        """Move a running request between instances, KV state and all."""
        if not dst.active or dst.engine._free_slot() is None:
            return False
        req = src.migrate_out(req_id)
        if req is None:
            return False
        dst.engine.submit(req)
        return True

    def rebalance(self, now: float, threshold: float = 0.9) -> int:
        """Move batch requests off crowded mixed instances onto idle ones
        (Llumnix-style defragmentation); returns migrations performed."""
        moved = 0
        insts = self.active_instances()
        for src in insts:
            if src.slot_utilization() < threshold:
                continue
            dsts = [d for d in insts
                    if d is not src and d.slot_utilization() < 0.5
                    and d.engine._free_slot() is not None]
            if not dsts:
                continue
            victims = [s.request for s in src.running
                       if s.request.request_type == RequestType.BATCH]
            if not victims:
                continue
            dst = min(dsts, key=lambda d: d.slot_utilization())
            if self.migrate(victims[-1].req_id, src, dst):
                moved += 1
        return moved


def serve_forever(requests: List[Request], controller, cluster: RealCluster,
                  *, max_steps: int = 2000, control_every: int = 5,
                  clock=None, telemetry=None) -> Dict:
    """Drive a real cluster: arrivals -> controller.route (shared with the
    sim) -> engine steps -> local autoscaler updates.

    ``telemetry`` arms the flight recorder (``repro.obs``) as the
    simulator's does: a ``FlightRecorder`` (or any truthy value, or
    ``CHIRON_TELEMETRY=1`` when None) gets the controller's signals and
    decisions, the cluster's provisions, retirements, evictions and batch
    limits, one host span per loop pass and engine stage, and the JIT
    work of each span; the result's ``"telemetry"`` holds it. Every pass
    is a profiler annotation either way: ``serve.pass`` with its
    ``serve.control`` and ``serve.route``, or, for a run of passes with
    nothing queued and nothing running, one ``serve.wait``. ``clock`` is
    read once per pass (and at the start and the end), never by a span."""
    from repro.serving.global_queue import GlobalQueue
    clock = clock or time.monotonic
    rec = resolve(telemetry)
    t0 = clock()
    queue = GlobalQueue()
    pending = sorted(requests, key=lambda r: r.arrival_time)
    pi = 0
    steps = 0
    if rec is not None:
        cluster.attach(rec)
        controller.obs = rec
    # the open serve.wait span of the current run of idle passes
    wait = None
    quiet = contextlib.nullcontext()
    try:
        with jit_booking(rec):
            while steps < max_steps:
                now = clock() - t0
                cluster.now = now
                while pi < len(pending) and pending[pi].arrival_time <= now:
                    queue.push(pending[pi])
                    pi += 1
                idle = len(queue) == 0 and \
                    all(i.n_running == 0 for i in cluster.instances)
                if idle and wait is None:
                    wait = span("serve.wait", rec)
                    wait.__enter__()
                elif not idle and wait is not None:
                    wait.__exit__(None, None, None)
                    wait = None
                with quiet if idle else span("serve.pass", rec):
                    for inst in cluster.instances:
                        inst.activate_if_ready(now)
                    if steps % control_every == 0:
                        with quiet if idle else span("serve.control", rec):
                            controller.control(cluster, queue, now)
                            for inst in cluster.active_instances():
                                inst.update_local_autoscaler()
                    with quiet if idle else span("serve.route", rec):
                        controller.route(cluster, queue, now)
                    for inst in cluster.active_instances():
                        # the engine evicts batch work for a waiting
                        # interactive request; the victim goes back to
                        # the global queue
                        for r in inst.step(now).preempted:
                            queue.requeue(r)
                    cluster.tick_accounting(0.0)
                steps += 1
                if pi >= len(pending) and len(queue) == 0 and \
                        all(i.n_running == 0 for i in cluster.instances):
                    break
    finally:
        if wait is not None:
            wait.__exit__(None, None, None)
        if rec is not None:
            cluster.attach(None)
            controller.obs = None
    done = [r for r in requests if r.state == RequestState.FINISHED]
    return {"steps": steps, "finished": len(done), "total": len(requests),
            "wall_s": clock() - t0,
            "scale_ups": cluster.scale_ups,
            "scale_downs": cluster.scale_downs,
            "telemetry": rec}
