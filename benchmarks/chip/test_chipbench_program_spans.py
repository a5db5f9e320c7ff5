"""The reduction of the program's own spans and JIT counter
(``chipbench.program``), on a small recorder and trace whose answers are
worked out by hand, and on a program that has neither."""
import pytest

from chipbench import program
from chipbench import trace as tr
from repro.obs import FlightRecorder

S = 1_790_000_000_000_000_000   # profile start, ns on the host's clock
LAG_NS = 3_000                  # rows start 3 us after their annotation

# (id, name, start s, end s, parent, request, arg, jit ms); in the order
# the spans close, as the recorder stores them
SPANS = [
    (0, "serve.wait", 0.0, 1.0, -1, -1, -1, 0),
    (2, "serve.route", 1.0, 1.1, 1, -1, -1, 0),
    (6, "engine.prefill", 1.2, 1.7, 5, 42, 100, 300),
    (7, "engine.slot_write", 1.7, 1.8, 5, 42, -1, 0),
    (5, "engine.admit", 1.2, 1.9, 4, 42, -1, 0),
    (4, "engine.schedule", 1.15, 2.0, 3, -1, -1, 0),
    (8, "engine.stack", 2.0, 2.1, 3, -1, -1, 0),
    (9, "engine.decode", 2.1, 2.2, 3, -1, -1, 0),
    (10, "engine.sync", 2.2, 2.7, 3, -1, -1, 0),
    (11, "engine.retire", 2.7, 2.85, 3, -1, -1, 0),
    (3, "engine.step", 1.1, 2.9, 1, -1, -1, 0),
    (1, "serve.pass", 1.0, 3.0, -1, -1, -1, 0),
    (14, "engine.schedule", 3.1, 3.2, 13, -1, -1, 0),
    (15, "engine.sync", 3.5, 3.8, 13, -1, -1, 0),
    (13, "engine.step", 3.08, 3.9, 12, -1, -1, 0),
    (12, "serve.pass", 3.0, 4.0, -1, -1, -1, 0),
    (18, "engine.schedule", 4.03, 4.08, 17, -1, -1, 0),
    (17, "engine.step", 4.02, 4.4, 16, -1, -1, 0),
    (16, "serve.pass", 4.0, 4.5, -1, -1, -1, 0),
]


def _ns(t):
    return S + round(t * 1e9) + LAG_NS


def _recorder():
    rec = FlightRecorder()
    for i, name, t0, t1, parent, req, arg, jit in SPANS:
        rec.host_spans.append(i, rec.host_span_code(name), _ns(t0), _ns(t1),
                              parent, 0, req, arg, 0, 0, 0,
                              round(jit * 1e6), round(jit * 1e6), 1, 0)
    return rec


def _trace():
    dev = tr.Device("/device:TPU:0")
    dev.ops = [(1.75, 1.8, "%fusion.1 = f32[8] fusion(%a)"),
               (2.15, 2.65, "%fusion.2 = f32[8] fusion(%b)"),
               (3.5, 3.75, "%fusion.2 = f32[8] fusion(%b)"),
               (4.45, 4.5, "%fusion.3 = f32[8] fusion(%c)")]
    return tr.Trace([dev], [(0.0, 6.0, "chipbench.window")], (0.0, 6.0))


def _spans():
    return [(t0, t1, name) for _, name, t0, t1, *_ in SPANS]


def test_step_host_time_leaves_out_admissions_and_the_device_wait():
    # first step: 1.8 s less the admission (0.7) and the wait (0.5);
    # second: 0.82 less the wait (0.3); the third decoded nothing
    got = program.step_host_ms(_recorder())
    assert got == pytest.approx([600.0, 520.0])
    assert program.median(got) == pytest.approx(560.0)


def test_prefill_jit_time_per_prefill():
    assert program.prefill_jit_ms(_recorder()) == pytest.approx([300.0])


def test_host_time_by_span_less_children():
    got = program.self_ms(_recorder())
    # the passes: 2.0 - 0.1 - 1.8, 1.0 - 0.82, 0.5 - 0.38
    assert got["serve.pass"] == pytest.approx(1e3 * (0.1 + 0.18 + 0.12))
    assert got["engine.admit"] == pytest.approx(100.0)
    assert got["serve.wait"] == pytest.approx(1000.0)


def test_idle_by_innermost_program_span():
    got = program.idle_by_span(_trace(), _spans())
    assert got == pytest.approx({
        "serve.wait": 1.75,             # 0 - 1.75
        "engine.schedule": 0.35,        # 1.8 - 2.15, midpoint 1.975
        "serve.pass": 0.85,             # 2.65 - 3.5, before the step
        "engine.step": 0.7,             # 3.75 - 4.45, midpoint 4.1
        program.OUTSIDE: 1.5})          # 4.5 - 6.0
    assert sum(got.values()) == pytest.approx(
        6.0 - tr.busy(_trace().devices[0].ops, 0.0, 6.0))
    # serving work, not waiting: (0.35 + 0.85 + 0.7) of 6 s
    assert program.idle_host_pct(_trace(), _spans()) == \
        pytest.approx(100 * 1.9 / 6.0)


def test_rows_against_their_twins_in_the_trace():
    got = program.twin_offsets_us(_recorder(), _spans(), S)
    assert got == pytest.approx([-3.0, -3.0, -3.0])
    assert program.twin_offsets_us(_recorder(), _spans()[:-2], S) is None


def test_nothing_to_read_reads_none():
    """As on a program without host spans or a JIT counter: no recorder,
    an empty one, an object without ``host_spans``, a trace without
    program spans."""
    for rec in (None, FlightRecorder(), object()):
        assert program.rows(rec) is None
        assert program.step_host_ms(rec) is None
        assert program.prefill_jit_ms(rec) is None
        assert program.self_ms(rec) is None
        assert program.twin_offsets_us(rec, _spans(), S) is None
    assert program.median(None) is None
    for spans in (None, []):
        assert program.idle_by_span(_trace(), spans) is None
        assert program.idle_host_pct(_trace(), spans) is None
    assert program.idle_host_pct(None, _spans()) is None


def test_load_reads_program_spans_from_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    empty = tmp_path / "empty"
    jax.profiler.start_trace(str(empty))
    with TraceAnnotation("chipbench.step"):
        jax.block_until_ready(jnp.ones(8) + 1)
    jax.profiler.stop_trace()
    assert program.load(str(empty)) is None
    assert program.load(str(tmp_path / "missing")) is None

    full = tmp_path / "full"
    jax.profiler.start_trace(str(full))
    with TraceAnnotation("serve.pass"):
        with TraceAnnotation("chipbench.step"):
            with TraceAnnotation("engine.step"):
                jax.block_until_ready(jnp.ones(8) + 2)
    jax.profiler.stop_trace()
    spans, start = program.load(str(full))
    assert [n for *_, n in spans] == ["serve.pass", "engine.step"]
    assert spans[0][0] <= spans[1][0] <= spans[1][1] <= spans[0][1]
    assert start > 1.6e18       # ns since the epoch
