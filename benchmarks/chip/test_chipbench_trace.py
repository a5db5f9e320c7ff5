"""The reduction from a trace and the benchmark's spans to per-layer
metrics, on a small synthetic trace whose answers are worked out by hand."""
import statistics

import pytest

from chipbench import flops, runner, serve
from chipbench import trace as tr

WINDOW = (0.0, 10.0)


def _trace():
    dev = tr.Device("/device:TPU:0")
    dev.ops = [
        (1.0, 2.0, "%while.1 = (s32[], f32[8]) while((s32[], f32[8]) %t)"),
        (1.2, 1.5, "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %x)"),
        (1.5, 1.8, "%ssd_scan.6 = (f32[1,2], f32[1]) custom-call(%a, %b)"),
        (1.8, 1.9, "%get-tuple-element.2 = f32[1] get-tuple-element("
                   "%ssd_scan.6)"),
        (3.0, 4.0, "%convert.1 = bf16[2]{0} convert(f32[2]{0} %p)"),
        (3.5, 4.5, "%fusion.9 = f32[8]{0} fusion(%y)"),
        (9.5, 11.0, "%fusion.2 = f32[8]{0} fusion(%z)"),
    ]
    dev.modules = [(1.0, 2.0, "jit_scan(123)"),
                   (3.0, 4.5, "jit_decode_step(9)"),
                   (9.5, 11.0, "jit_decode_step(9)")]
    spans = [(0.0, 10.0, "chipbench.window"),
             (0.9, 2.1, "chipbench.prefill"),
             (2.9, 4.6, "chipbench.step"),
             (3.0, 4.4, "chipbench.decode"),
             (4.5, 9.6, "chipbench.admit")]
    return tr.Trace([dev], spans, WINDOW)


DIMS = {"d": 16, "di": 32, "N": 8, "P": 4, "H": 8, "W": 4, "L": 3,
        "V": 100, "chunk": 32}
PEAKS = {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}


def _ctx(trace=None):
    rec = serve.Recorder(traced=True)
    rec.prefills = [(0, 0.9, 2.1, 100)]
    rec.devices = {0: 0}
    rec.admits = [(0, 0.0, 0.1), (0, 1.0, 1.3), (0, 2.0, 2.2)]
    rec.steps = [(0, 0.2, 0.7, 3), (0, 1.1, 1.3, 0), (0, 2.0, 2.9, 1)]
    rec.limits = [(0.0, [8]), (4.0, [4])]
    clock = serve.WindowClock(10.0)
    clock.t0, clock.end = 0.0, 10.0
    clock.passes = [0.0, 1.0, 2.0, 3.0]
    cell = runner.Cell("x.y", "x", {"family": "mamba2"}, {}, 1, [], [])
    return runner.Context(cell, DIMS, rec, clock, trace or _trace(), PEAKS)


def test_busy_union_clips_and_merges():
    t = _trace()
    assert tr.busy(t.devices[0].ops, *WINDOW) == pytest.approx(3.0)
    assert tr.union([(0, 2), (1, 3), (5, 6)], 0.5, 5.5) == \
        [[0.5, 3], [5, 5.5]]


def test_device_idle_share():
    assert runner.reader("device.idle_pct")(_ctx()) == pytest.approx(70.0)


def test_decode_device_time_counts_whole_runs_inside_the_window():
    assert runner.reader("decode.device_ms")(_ctx()) == pytest.approx(1500.0)


def test_op_totals_leave_out_control_flow_and_name_the_program():
    tot = tr.op_totals(_trace())
    assert "jit_scan/while.1" not in tot
    assert tot["jit_scan/ssd_scan.6"] == pytest.approx(0.3)
    assert tot["jit_decode_step/convert.1"] == pytest.approx(1.0)
    assert tot["jit_decode_step/fusion.2"] == pytest.approx(0.5)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = dict(tr.idle_gaps(_trace()))
    assert gaps == pytest.approx({"chipbench.admit": 5.0,
                                  "chipbench.loop": 2.0})


def test_ssd_scan_operations_and_bytes_from_shapes():
    # 100 positions in 4 chunks of 32, 2 heads of 4 channels, state 8:
    # scores 4 * 2*32*32*8 = 65536; per head and position 2*32*4 + 4*8*4
    ops, nbytes = flops.ssd_scan(100, 2, 4, 8, 32)
    assert ops == 65536 + 100 * 2 * (256 + 128)
    # x and y (2*100*2*4), dt (100*2), B and C (2*100*8), h0 and state
    assert nbytes == 4 * (1600 + 200 + 1600 + 128)


def test_ssd_scan_roofline_share():
    ops, nbytes = flops.ssd_scan(100, 8, 4, 8, 32)
    least = 3 * max(ops / 1e9, nbytes / 1e9)
    got = runner.reader("ssd_scan_roofline")(_ctx())
    # only the custom call counts, not an op that reads its result
    assert got == pytest.approx(100 * least / 0.3)


def test_prefill_mfu_over_busy_time_inside_prefill_spans():
    got = runner.reader("prefill.mfu_pct")(_ctx())
    assert got == pytest.approx(100 * flops.mamba2_prefill(DIMS, 100) / 1.0
                                / 1e9)


def test_host_metrics_from_spans_and_passes():
    ctx = _ctx()
    # passes [0,1] and [2,3] decoded: host 1 - 0.5 and 1 - 0.9 s
    assert runner.reader("loop.host_ms_per_pass")(ctx) == \
        pytest.approx(300.0)
    assert runner.reader("engine.admit_ms_p50")(ctx) == pytest.approx(200.0)
    # 8 slots for 4 s, then 4 for 6 s
    assert runner.reader("chiron.batch_limit_mean")(ctx) == \
        pytest.approx(5.6)


def test_readers_without_a_trace_return_nothing():
    ctx = _ctx()
    ctx.trace = None
    for name in ("device.idle_pct", "decode.device_ms", "prefill.mfu_pct",
                 "ssd_scan_roofline"):
        assert runner.reader(name)(ctx) is None


def test_end_to_end_counts_unserved_requests_at_the_window_end():
    from repro.serving.request import make_batch, make_interactive
    r1 = make_interactive(8, 4, 0.0)
    r2 = make_interactive(8, 4, 1.0)
    r3 = make_batch(8, 4, 0.0)
    rec = serve.Recorder()
    rec.tokens = {r1.req_id: [(0.5, 1), (0.6, 2), (0.9, 1)],
                  r3.req_id: [(2.0, 1), (10.5, 1)]}
    clock = serve.WindowClock(10.0)
    clock.t0, clock.end = 0.0, 10.0
    out, samples = runner.end_to_end([r1, r2, r3], rec, clock)
    assert samples["ttft"] == pytest.approx([0.5, 9.0])
    assert samples["itl"] == pytest.approx([0.1, 0.0, 0.3])
    assert out["ttft_p50_ms"] == pytest.approx(4750.0)
    assert out["tokens_per_s"] == pytest.approx(0.5)
    q = statistics.quantiles([0.1, 0.0, 0.3], n=100, method="inclusive")
    assert out["itl_p50_ms"] == pytest.approx(100.0)
    assert out["itl_p95_ms"] == pytest.approx(1e3 * q[94])
    assert out["itl_p98_ms"] == pytest.approx(1e3 * q[97])
