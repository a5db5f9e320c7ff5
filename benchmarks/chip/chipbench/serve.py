"""Drive the program's served entry for one window and record what users see.

The window runs ``serve_forever`` over a ``RealCluster`` of one-chip
``RealInstance`` engines with ``ChironController`` in the loop, the path the
program serves on. The benchmark hands ``serve_forever`` its own clock: the
first call opens the window and the first call after ``seconds`` raises
``WindowClosed``, which ends the call at the top of a loop pass. Every time
here is read from that one monotonic clock, never from ``Request.ttft``
(the engine stamps first tokens on a clock of its own).

Wrappers around each engine's ``step``, ``_admit``, ``_prefill`` and
``_decode`` record token times, spans, the decode inputs (the served
tokens) and the logits of every prefill and decode step, without changing
what the engine computes. The logits stay on the device, unread, until the
window has closed.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.core.local_autoscaler import LocalAutoscaler
from repro.models import Model
from repro.serving.engine import Engine
from repro.serving.real_cluster import RealCluster, serve_forever
from repro.serving.request import SLO, Request, RequestType
from repro.sim.cluster import InstanceType
from repro.sim.controllers import ChironController

Annotation = jax.profiler.TraceAnnotation


class WindowClosed(Exception):
    """Raised by ``WindowClock`` at the first loop pass after the window."""


class WindowClock:
    """The clock ``serve_forever`` reads once at the top of each pass."""

    def __init__(self, seconds: float, on_pass=None):
        self.seconds = seconds
        self.t0 = None
        self.end = None
        self.passes = []
        self.on_pass = on_pass

    def __call__(self) -> float:
        t = time.monotonic()
        if self.t0 is None:
            self.t0, self.end = t, t + self.seconds
        self.passes.append(t)
        if t >= self.end:
            raise WindowClosed
        if self.on_pass is not None:
            self.on_pass(t)
        return t


@dataclass
class Recorder:
    """What the wrappers saw, on the window's clock."""
    traced: bool = False
    tokens: dict = field(default_factory=dict)    # req_id -> [(t, n)]
    served_on: dict = field(default_factory=dict)  # req_id -> engine index
    # (engine, slots, input tokens, logits) of every decode step
    decode_inputs: list = field(default_factory=list)
    prefill_logits: dict = field(default_factory=dict)  # req_id -> logits
    steps: list = field(default_factory=list)  # (engine, t0, t1, n_active)
    admits: list = field(default_factory=list)     # (engine, t_start, t_end)
    prefills: list = field(default_factory=list)   # (engine, t0, t1, length)
    limits: list = field(default_factory=list)     # (t, [batch limit per engine])
    devices: dict = field(default_factory=dict)    # engine index -> device id


def instrument(engine: Engine, idx: int, rec: Recorder) -> None:
    """Wrap one engine's entry points (instance attributes shadow the
    class's methods, so the engine's own code calls the wrappers)."""
    step, admit, prefill, decode = (engine.step, engine._admit,
                                    engine._prefill, engine._decode)
    rec.devices[idx] = engine.device.id

    def _step():
        reqs = [s.request for s in engine.slots if s.active]
        reqs += engine.waiting
        if not reqs:        # an idle pass: nothing to record
            return step()
        before = [r.tokens_generated for r in reqs]
        t0 = time.monotonic()
        with Annotation("chipbench.step"):
            stats = step()
        t1 = time.monotonic()
        rec.steps.append((idx, t0, t1, stats.n_active))
        for r, b in zip(reqs, before):
            if r.tokens_generated > b:
                rec.tokens.setdefault(r.req_id, []).append(
                    (t1, r.tokens_generated - b))
                rec.served_on.setdefault(r.req_id, idx)
        return stats

    def _admit(req, now):
        t0 = time.monotonic()
        with Annotation("chipbench.admit"):
            ok = admit(req, now)
            if ok and rec.traced:
                jax.block_until_ready(engine.pool)
        if ok:
            rec.admits.append((idx, t0, time.monotonic()))
        return ok

    def _prefill(req):
        if rec.traced:
            jax.block_until_ready(engine.pool)
        t0 = time.monotonic()
        with Annotation("chipbench.prefill"):
            out = prefill(req)
            if rec.traced:
                jax.block_until_ready(out)
        rec.prefills.append((idx, t0, time.monotonic(), req.prompt_len))
        rec.prefill_logits[req.req_id] = out[0]
        return out

    def _decode(params, tokens, pool):
        slots = tuple(s.request for s in engine.slots)
        with Annotation("chipbench.decode"):
            logits, pool = decode(params, tokens, pool)
        rec.decode_inputs.append((idx, slots, tokens, logits))
        return logits, pool

    engine.step, engine._admit = _step, _admit
    engine._prefill, engine._decode = _prefill, _decode


@contextlib.contextmanager
def served_weights(params):
    """``RealCluster`` initialises its weights from a fixed key; hand it the
    benchmark's seeded weights instead, with no second set on the device."""
    init = Model.init
    Model.init = lambda self, key, dtype=None: params
    try:
        yield
    finally:
        Model.init = init


def _drain(engine: Engine) -> None:
    while engine.n_active or engine.n_waiting:
        for victim in engine.step().preempted:
            engine.submit(victim)


def warm_up(cfg, params, serve: dict, devices, lengths, preempts: bool):
    """Compile (or load from the compile cache) every program the window
    runs, on throwaway engines: a prefill and slot write at each prompt
    length, the decode step, finishing, and, where the traffic mixes
    classes, a preemption and a restore. Programs are shared per device, so
    the window's engines find them. Returns the seconds of each part."""
    slots, max_len = serve["max_slots"], serve["max_len"]

    def req(kind, n_prompt, n_out):
        r = Request(n_prompt, n_out, kind,
                    SLO.interactive() if kind == RequestType.INTERACTIVE
                    else SLO.batch(), model=cfg.name)
        r.prompt_tokens = [0] * n_prompt
        return r

    spent = {"lengths": 0.0, "preempt": 0.0, "stack": 0.0}
    for dev in devices:
        t = time.monotonic()
        # a new engine's pool is not yet committed to its device, so the
        # first write into it is a program of its own: admit each length
        # first into a new pool, then again into a written one
        for n in lengths:
            eng = Engine(cfg, params=params, max_slots=slots,
                         max_len=max_len, dtype=jnp.float32, device=dev)
            for _ in range(2):
                eng.submit(req(RequestType.BATCH, n, 3))
                _drain(eng)
        spent["lengths"] += time.monotonic() - t
        t = time.monotonic()
        if preempts:
            for _ in range(slots):
                eng.submit(req(RequestType.BATCH, lengths[0], 8))
            eng.step()
            eng.submit(req(RequestType.INTERACTIVE, lengths[0], 3))
            _drain(eng)
        del eng
        spent["preempt"] += time.monotonic() - t
        t = time.monotonic()
        # Engine.step stacks one token per slot: a token on the device for
        # an active slot, an uncommitted zero for a free one. Each pattern
        # of the two is a program of its own, so make them all now.
        on_dev = jax.device_put(jnp.zeros((), jnp.int32), dev)
        for mask in range(1 << slots):
            jnp.stack([on_dev if mask >> i & 1 else jnp.zeros((), jnp.int32)
                       for i in range(slots)])
        spent["stack"] += time.monotonic() - t
    return spent


def build(cfg, params, serve: dict, n_instances: int):
    """The window's cluster and controller, with every instance provisioned
    up front (the controller would otherwise build an engine inside the
    window)."""
    slots = serve["max_slots"]
    with served_weights(params):
        cluster = RealCluster(cfg, max_chips=n_instances, max_slots=slots,
                              max_len=serve["max_len"])
    ctrl = ChironController(model=cfg.name, init_batch=slots,
                            max_batch=slots, min_instances=n_instances)
    for _ in range(n_instances):
        cluster.provision(cfg.name, InstanceType.MIXED, 0.0,
                          local_autoscaler=LocalAutoscaler(
                              itl_slo=ctrl.itl_slo_interactive,
                              init_batch=slots, max_batch=slots))
    return cluster, ctrl


def warm_decode(engine: Engine) -> None:
    """A fresh engine's jitted decode step traces once; do it before the
    window. The result is discarded, so the engine's state is untouched."""
    tokens = jnp.zeros((engine.max_slots, 1), jnp.int32)
    jax.block_until_ready(engine._decode(engine.params, tokens, engine.pool))


def window(requests, ctrl, cluster, clock: WindowClock) -> None:
    with Annotation("chipbench.window"):
        try:
            serve_forever(requests, ctrl, cluster, max_steps=1 << 62,
                          clock=clock)
        except WindowClosed:
            pass
