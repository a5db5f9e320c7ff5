"""Host spans and the JIT counter on the real serving path (``repro.obs``
armed through ``serve_forever`` and ``Engine``), on the mamba2 smoke
configuration."""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.configs import get_smoke_config
from repro.core.local_autoscaler import LocalAutoscaler
from repro.obs import FlightRecorder
from repro.obs.host import jit_booking, span
from repro.obs.recorder import (BATCH_LIMIT, EVICT, JIT_COMPILE, JIT_LOWER,
                                JIT_TRACE, KIND_NAMES, PROVISION, RETIRE,
                                JitTally)
from repro.serving.engine import Engine
from repro.serving.real_cluster import RealCluster, serve_forever
from repro.serving.request import make_batch, make_interactive
from repro.sim.cluster import InstanceType
from repro.sim.controllers import ChironController

STEP_CHILDREN = {"engine.schedule", "engine.stack", "engine.decode",
                 "engine.sync", "engine.retire"}
ADMIT_CHILDREN = {"engine.prefill", "engine.slot_write", "engine.restore"}


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("mamba2-1.3b")


def _request(make, n_prompt, n_out, seed, **kw):
    r = make(n_prompt, n_out, model="mamba2-1.3b", **kw)
    r.prompt_tokens = np.random.default_rng(seed).integers(
        0, 500, n_prompt, dtype=np.int32)
    return r


def _rows(rec):
    names = rec.host_span_names
    return [dict(r, name=names[r["name"]]) for r in rec.host_spans.rows()]


def _children(rows):
    by_id = {r["id"]: r for r in rows}
    kids = {r["id"]: [] for r in rows}
    for r in rows:
        if r["parent"] >= 0:
            kids[r["parent"]].append(r)
    return by_id, kids


@pytest.fixture(scope="module")
def preempting_run(cfg):
    """One engine, two slots, armed: two batch requests, then an
    interactive one that evicts the later batch request, which is
    restored once a slot frees."""
    rec = FlightRecorder()
    eng = Engine(cfg, max_slots=2, max_len=128, dtype=jnp.float32)
    eng.obs, eng.instance_id = rec, 7
    reqs = [_request(make_batch, 16, 12, 1), _request(make_batch, 16, 12, 2),
            _request(make_interactive, 16, 4, 3)]
    eng.submit(reqs[0])
    eng.submit(reqs[1])
    stats = [eng.step()]
    eng.submit(reqs[2])
    with jit_booking(rec):
        for _ in range(40):
            st = eng.step()
            stats.append(st)
            for victim in st.preempted:
                eng.submit(victim)
            if not eng.n_active and not eng.waiting:
                break
    return rec, reqs, stats


def test_decode_step_span_tree_is_well_formed(preempting_run):
    rec, _, stats = preempting_run
    rows = _rows(rec)
    by_id, kids = _children(rows)
    steps = [r for r in rows if r["name"] == "engine.step"]
    assert len(steps) == len(stats)
    for r in rows:
        assert r["t0"] <= r["t1"]
        assert r["instance"] == 7
        if r["parent"] >= 0:
            p = by_id[r["parent"]]
            assert p["t0"] <= r["t0"] and r["t1"] <= p["t1"], (p, r)
            # children close, and so are stored, before their parent
            assert rows.index(r) < rows.index(p)
        else:
            assert r["name"] == "engine.step"
    for r in steps:
        names = [k["name"] for k in kids[r["id"]]]
        assert set(names) <= STEP_CHILDREN
        assert names[0] == "engine.schedule" and len(names) == len(set(names))
    for r in rows:
        if r["name"] in ("engine.admit", "engine.preempt"):
            assert by_id[r["parent"]]["name"] == "engine.schedule"
        if r["name"] in ADMIT_CHILDREN:
            assert by_id[r["parent"]]["name"] == "engine.admit"
    # the ids number the spans in the order they opened
    assert sorted(r["id"] for r in rows) == list(range(len(rows)))


def test_one_admit_per_admission_and_one_sync_per_decode_step(
        preempting_run):
    rec, reqs, stats = preempting_run
    rows = _rows(rec)
    _, kids = _children(rows)
    admits = [r for r in rows if r["name"] == "engine.admit"]
    # three prefills and one restore
    assert len(admits) == 4
    assert sorted(r["request"] for r in admits) == sorted(
        [r.req_id for r in reqs] + [reqs[1].req_id])
    decoded = [st for st in stats if st.n_active]
    syncs = [r for r in rows if r["name"] == "engine.sync"]
    assert len(syncs) == len(decoded)
    for r in rows:
        if r["name"] == "engine.step":
            names = [k["name"] for k in kids[r["id"]]]
            assert names.count("engine.sync") == \
                names.count("engine.decode") == names.count("engine.stack")


def test_a_requests_spans_share_its_id(preempting_run):
    rec, reqs, _ = preempting_run
    rows = _rows(rec)
    by_id, _ = _children(rows)
    kept, evicted, inter = reqs
    assert evicted.preemptions == 1 and kept.preemptions == 0
    for req, want in ((kept, ["engine.admit", "engine.prefill",
                              "engine.slot_write"]),
                      (inter, ["engine.admit", "engine.prefill",
                               "engine.slot_write"]),
                      (evicted, ["engine.admit", "engine.preempt",
                                 "engine.prefill", "engine.restore",
                                 "engine.slot_write", "engine.admit"])):
        mine = [r for r in rows if r["request"] == req.req_id]
        assert sorted(r["name"] for r in mine) == sorted(want)
        for r in mine:
            if r["name"] in ADMIT_CHILDREN:
                assert by_id[r["parent"]]["request"] == req.req_id
            if r["name"] == "engine.prefill":
                assert r["arg"] == req.prompt_len
    assert not [r for r in rows if r["request"] >= 0
                and r["request"] not in {q.req_id for q in reqs}]


def test_union_of_nested_jit_events():
    # a trace inside a trace, a lowering after both: 10 + 4, not 16 + 4
    t = JitTally()
    t.intervals = [(JIT_TRACE, 0, 10), (JIT_TRACE, 2, 8),
                   (JIT_LOWER, 10, 14), (JIT_COMPILE, 20, 25)]
    assert t.totals()[:5] == (10, 4, 5, 0, 19)


def test_nested_traces_count_once(cfg):
    """A jit traced inside another's trace reports two trace events whose
    durations overlap; the span books their union."""
    events = []

    def listener(event, start, end, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            events.append((start, end))

    rec = FlightRecorder()
    x = jax.block_until_ready(jnp.ones(5))
    jax.monitoring.register_event_time_span_listener(listener)
    try:
        inner = jax.jit(lambda x: jnp.tanh(x) * 3.0)
        outer = jax.jit(lambda x: inner(x + 1.0).sum())
        with jit_booking(rec), span("outer", rec):
            jax.block_until_ready(outer(x))
    finally:
        jax.monitoring.unregister_event_time_span_listener(listener)
    (row,) = rec.host_spans.rows()
    assert len(events) >= 2
    whole = max(e for _, e in events) - min(s for s, _ in events)
    naive = sum(e - s for s, e in events)
    assert row["trace_ns"] == pytest.approx(whole * 1e9, rel=0.01, abs=2e3)
    assert row["trace_ns"] < naive * 1e9
    assert row["t0"] <= min(s for s, _ in events) * 1e9 + 1e3
    assert row["jit_ns"] <= row["t1"] - row["t0"]


CACHE_KEYS = ("jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def compile_cache(tmp_path):
    """Every program into a fresh persistent cache, as a deployment's
    warm cache would hold it; the process's settings come back after."""
    before = {k: getattr(jax.config, k) for k in CACHE_KEYS}
    for k, v in zip(CACHE_KEYS, (str(tmp_path / "jc"), 0, 0)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_forced_retrace_is_booked_to_its_span_as_a_load(cfg, compile_cache):
    # programs this process compiled before the cache was on are not in it
    jax.clear_caches()
    rec = FlightRecorder()
    eng = Engine(cfg, max_slots=2, max_len=128, dtype=jnp.float32)
    eng.obs = rec
    eng.submit(_request(make_interactive, 16, 12, 4))
    with jit_booking(rec):
        eng.step()                  # admission, and the first decode
        # the second decode finds the program of the first for the pool
        # the first one committed to the device; the third is warm
        eng.step()
        eng.step()
        jax.clear_caches()          # the decode step must be traced again
        eng.step()
    rows = _rows(rec)
    decodes = [r for r in rows if r["name"] == "engine.decode"]
    assert len(decodes) == 4
    first, _, warm, again = decodes
    # first call: traced, lowered, compiled (a cache miss), not loaded
    assert first["trace_ns"] > 0 and first["lower_ns"] > 0
    assert first["compile_ns"] > 0 and first["cache_misses"] >= 1
    assert first["load_ns"] == 0
    assert warm["jit_ns"] == 0 and warm["cache_hits"] == 0
    # after the caches were dropped: traced and lowered again, then
    # loaded from the persistent cache, which is no compile
    assert again["trace_ns"] > 0 and again["lower_ns"] > 0
    assert again["load_ns"] > 0 and again["cache_hits"] >= 1
    assert again["compile_ns"] == 0 and again["cache_misses"] == 0
    # booked to the innermost span only, never to its parents
    by_id, _ = _children(rows)
    assert by_id[again["parent"]]["name"] == "engine.step"
    assert by_id[again["parent"]]["jit_ns"] == 0
    tot = rec.jit_totals()
    assert tot["load_s"] > 0 and tot["cache_hits"] >= 1
    assert tot["jit_s"] <= sum(tot[f"{k}_s"] for k in
                               ("trace", "lower", "compile", "load")) + 1e-9


def test_engines_of_one_config_share_the_prefill_program(cfg):
    """A second engine of the same config and dtype admits a prompt at a
    length the first one prefilled without tracing, lowering or loading
    anything; a length no engine has prefilled is traced and lowered."""
    # a config no other test has prefilled in this process
    cfg = dataclasses.replace(cfg, name="mamba2-shared-prefill")
    rec = FlightRecorder()
    first, second = (Engine(cfg, max_slots=2, max_len=128,
                            dtype=jnp.float32) for _ in range(2))
    assert first._prefill_program is second._prefill_program
    first.obs = second.obs = rec
    with jit_booking(rec):
        for eng, n, seed in ((first, 16, 8), (second, 16, 9),
                             (second, 24, 10)):
            eng.submit(_request(make_interactive, n, 2, seed))
            eng.step()
    cold, shared, new = [r for r in _rows(rec)
                         if r["name"] == "engine.prefill"]
    assert [r["arg"] for r in (cold, shared, new)] == [16, 16, 24]
    assert cold["trace_ns"] > 0 and cold["lower_ns"] > 0
    assert shared["jit_ns"] == 0 and shared["load_ns"] == 0
    assert shared["cache_hits"] == shared["cache_misses"] == 0
    assert new["trace_ns"] > 0 and new["lower_ns"] > 0


def test_a_warmed_decode_step_is_not_lowered_again(cfg):
    """A decode step first called on uncommitted zeros, as a warm-up
    does, runs the same program once the engine's own steps feed it the
    committed tokens and pool: no step lowers or compiles again."""
    rec = FlightRecorder()
    eng = Engine(cfg, max_slots=2, max_len=128, dtype=jnp.float32)
    jax.block_until_ready(eng._decode(
        eng.params, jnp.zeros((2, 1), jnp.int32), eng.pool))
    eng.obs = rec
    eng.submit(_request(make_interactive, 16, 4, 11))
    with jit_booking(rec):
        for _ in range(3):
            eng.step()
    decodes = [r for r in _rows(rec) if r["name"] == "engine.decode"]
    assert len(decodes) == 3
    for r in decodes:
        assert r["lower_ns"] == r["compile_ns"] == r["load_ns"] == 0, r


def _cluster(cfg, slots=2):
    cluster = RealCluster(cfg, max_chips=1, max_slots=slots, max_len=128)
    ctrl = ChironController(model=cfg.name, init_batch=slots,
                            max_batch=slots, min_instances=1)
    cluster.provision(cfg.name, InstanceType.MIXED, 0.0,
                      local_autoscaler=LocalAutoscaler(
                          itl_slo=ctrl.itl_slo_interactive,
                          init_batch=slots, max_batch=slots))
    return cluster, ctrl


def _served(cfg, telemetry):
    """Serve the same three requests on a fresh cluster, the third long
    after the first two are done; return the tokens fed to the decode step
    for each request, the clock's calls, the result and the cluster."""
    cluster, ctrl = _cluster(cfg)
    eng = cluster.instances[0].engine
    reqs = [_request(make_interactive, 16, 6, 5, arrival=0.0),
            _request(make_batch, 24, 9, 6, arrival=0.0),
            _request(make_interactive, 16, 5, 7, arrival=5.0)]
    fed = {r.req_id: [] for r in reqs}
    decode = eng._decode

    def recording(params, tokens, pool):
        for s, t in zip(eng.slots, np.asarray(tokens)[:, 0]):
            if s.active:
                fed[s.request.req_id].append(int(t))
        return decode(params, tokens, pool)

    eng._decode = recording
    calls = []

    def clock():
        calls.append(None)
        return 0.05 * len(calls)

    out = serve_forever(reqs, ctrl, cluster, max_steps=400, clock=clock,
                        telemetry=telemetry)
    return [fed[r.req_id] for r in reqs], len(calls), out, cluster


def test_serve_forever_reads_the_clock_once_per_pass_when_armed(cfg):
    rec = FlightRecorder()
    _, calls, out, cluster = _served(cfg, rec)
    assert out["finished"] == out["total"] == 3
    assert out["telemetry"] is rec
    # one read at the start, one per pass, one for the wall time
    assert calls == out["steps"] + 2
    rows = _rows(rec)
    by_id, _ = _children(rows)
    passes = [r for r in rows if r["name"] == "serve.pass"]
    waits = [r for r in rows if r["name"] == "serve.wait"]
    # the passes between the first two requests' end and the third's
    # arrival (5 s at 0.05 s a pass) are one run of waiting
    assert len(waits) == 1 and len(passes) < out["steps"] - 50
    assert all(r["parent"] == -1 for r in passes + waits)
    (w,) = waits
    assert not [r for r in rows if r["parent"] == w["id"]]
    assert not [p for p in passes if w["t0"] < p["t0"] < w["t1"]]
    for r in rows:
        if r["name"] in ("serve.control", "serve.route", "engine.step"):
            assert by_id[r["parent"]]["name"] == "serve.pass"
    # once serving ends, the cluster and its engines are unarmed
    assert cluster.obs is None and cluster.instances[0].engine.obs is None


def test_armed_and_unarmed_serve_the_same_tokens(cfg):
    rec = FlightRecorder()
    armed, calls_armed, out_armed, _ = _served(cfg, rec)
    plain, calls_plain, out_plain, _ = _served(cfg, None)
    assert out_plain["telemetry"] is None
    assert armed == plain and all(len(t) >= 4 for t in armed)
    assert out_armed["finished"] == out_plain["finished"] == 3
    assert calls_plain == out_plain["steps"] + 2
    assert rec.host_spans.n > 0


def test_decision_ledger_on_the_real_plane(cfg):
    """Provision, eviction, batch limits and retirement reach the decision
    ledger through the hooks the simulator calls."""
    rec = FlightRecorder()
    cluster, ctrl = _cluster(cfg, slots=1)
    inst = cluster.instances[0]
    cluster.attach(rec)
    b = _request(make_batch, 16, 20, 8)
    inst.activate_if_ready(0.0)
    inst.admit(b, 0.0)
    inst.step(0.0)
    inst.update_local_autoscaler()
    inst.admit(_request(make_interactive, 16, 3, 9), 0.0)
    st = inst.step(0.5)
    assert st.preempted == [b]
    extra = cluster.provision(cfg.name, InstanceType.MIXED, 1.0)
    assert extra is None        # one chip: the budget holds
    cluster.now = 2.0
    cluster.retire(inst)
    assert inst.engine.obs is None
    kinds = [KIND_NAMES[k] for k in rec.decisions.col("kind")]
    assert kinds == ["batch_limit", "evict", "retire"]
    dec = list(rec.decisions.rows())
    assert dec[0]["chips_before"] == dec[0]["chips_after"] == 1
    assert dec[0]["peer"] == inst.id
    assert dec[1]["t"] == 0.5 and dec[1]["value"] == 16.0 + 1
    assert dec[2]["t"] == 2.0 and dec[2]["chips_after"] == 0
    assert rec.replay()["evictions"] == 1
    # an armed run provisions through the hook
    rec2 = FlightRecorder()
    cluster2 = RealCluster(cfg, max_chips=1, max_slots=2, max_len=128)
    ctrl2 = ChironController(model=cfg.name, init_batch=1, max_batch=2)
    out = serve_forever([_request(make_interactive, 16, 12, 10)], ctrl2,
                        cluster2, max_steps=200, telemetry=rec2)
    assert out["finished"] == 1
    k2 = rec2.decisions.col("kind")
    assert (k2 == PROVISION).sum() == 1 and (k2 == RETIRE).sum() == 0
    assert (k2 == EVICT).sum() == 0
    limits = [r for r in rec2.decisions.rows() if r["kind"] == BATCH_LIMIT]
    assert limits[0]["chips_after"] == 1
    assert limits[-1]["chips_after"] == 2
    for r in limits[1:]:
        assert r["value"] > 0 and r["threshold"] == 0.2
        assert r["chips_before"] != r["chips_after"]


def test_unarmed_spans_record_nothing():
    rec = FlightRecorder()
    with span("x", None):
        pass
    assert isinstance(span("x", None), jax.profiler.TraceAnnotation)
    with jit_booking(None):
        jax.block_until_ready(jax.jit(lambda x: x * 7.0)(jnp.ones(3)))
    assert rec.host_spans.n == 0 and not rec.jit_unspanned.intervals


def test_rows_start_with_their_profiler_twins(tmp_path):
    """The rows' clock is the profiler's: each row and its annotation in
    the ``.xplane.pb`` start within 0.1 ms, once the profile's start
    (``profile_start_time``) is added back."""
    from jax.profiler import ProfileData
    rec = FlightRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(20):
            with span("probe.outer", rec, request=i):
                with span("probe.inner", rec):
                    jax.block_until_ready(jnp.ones(64) * i)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    start = None
    twins = {"probe.outer": [], "probe.inner": []}
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            for ev in line.events:
                if ev.name in twins:
                    twins[ev.name].append(ev.start_ns)
    assert start is not None
    rows = _rows(rec)
    for name, got in twins.items():
        mine = sorted(r["t0"] for r in rows if r["name"] == name)
        assert len(got) == len(mine) == 20
        off = np.asarray(sorted(got)) + start - np.asarray(mine)
        assert np.abs(off).max() < 1e5, off
