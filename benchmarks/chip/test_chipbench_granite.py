"""Granite 4.0-H (the layer-pattern hybrid) against its plain reference, at
the program's smoke sizes on the CPU, where program and reference both
compute float32 in full: prefill and then decoding through the engine's
slot pool must give the reference's full-forward logits at every position.
The reference with a muP multiplier dropped, or computed in bfloat16
throughout, must not pass the same tolerance. Then a whole tiny run of the
cell, and the flash prefill's roofline share on a synthetic trace."""
import copy
import importlib.util
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, runner, serve
from chipbench import trace as tr
from repro.configs import get_smoke_config
from repro.serving.engine import Engine
from repro.serving.request import make_interactive

CONFIG = "granite-4.0-h-micro"
# float32 on both sides, the program's matmuls at the CPU's full float32
# precision: rounding alone, which read under 3e-6 of the logits' L2 norm
# at every position; a tenth of the bfloat16 control's least reading
# below (0.1 and more) would still be 1e-2
TOL = 1e-5
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")


def tiny_conf() -> dict:
    """The cell's configuration file at ``smoke_config()``'s widths: one
    whole period of ten layers, GQA 4/2 of 32, every multiplier, NoPE."""
    c = json.loads((runner.BENCH_DIR / "configs" /
                    f"{CONFIG}.json").read_text())
    c.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
             mamba_n_heads=8, mamba_d_head=32, mamba_d_state=16,
             mamba_chunk_size=32, shared_intermediate_size=256,
             intermediate_size=256, vocab_size=512)
    c["serve"].update(max_slots=4, max_len=2304)
    c["prompt_vocab"] = 500
    return c


@pytest.fixture(scope="module")
def served():
    """Two requests served together through one engine: a prompt long
    enough for the long-sequence attention path (2048 and over) and a
    short one in the next slot, 24 tokens each. Returns the reference's
    configuration, its weights and (prompt, served tokens, the program's
    logits rows) per request."""
    conf = tiny_conf()
    cell = runner.Cell(f"{CONFIG}.long_decode", CONFIG, conf, {}, 1, [], [])
    ref, adapter = runner.family(cell)
    w = runner.make_weights(ref, conf, 3)
    cfg = adapter.model_config(CONFIG, conf)
    eng = Engine(cfg, params=adapter.program_params(w), max_slots=4,
                 max_len=conf["serve"]["max_len"], dtype=jnp.float32)
    rec = serve.Recorder()
    serve.instrument(eng, 0, rec)
    rng = np.random.default_rng(0)
    reqs = []
    for n in (2100, 70):
        r = make_interactive(n, 24, 0.0, model=CONFIG)
        r.prompt_tokens = rng.integers(0, 500, n).astype(np.int32)
        reqs.append(r)
        eng.submit(r)
    while eng.n_active or eng.n_waiting:
        eng.step()
    got = check.served(rec.decode_inputs, rec.prefill_logits, reqs)
    seqs = [(np.asarray(r.prompt_tokens), *got[r.req_id]) for r in reqs]
    return ref, conf, w, seqs


def test_smoke_config_is_the_tiny_cells():
    conf = tiny_conf()
    cell = runner.Cell(f"{CONFIG}.long_decode", CONFIG, conf, {}, 1, [], [])
    _, adapter = runner.family(cell)
    got = adapter.model_config(CONFIG, conf)
    smoke = get_smoke_config(CONFIG)
    assert got == smoke.with_(source=got.source, dtype=got.dtype)


def test_engine_prefill_then_decode_matches_reference(served):
    ref, conf, w, seqs = served
    assert [len(t) for _, t, _ in seqs] == [23, 23]
    got = check.readings(ref, conf, w, seqs)
    assert got["positions"] == 48
    assert got["token_mismatches"] == 0
    assert got["logit_err"] <= TOL


def _variant_err(ref, conf, w, seqs, variant):
    """The worst relative distance, over the served positions, between
    the program's logits and a variant of the reference's."""
    control = variant == "bfloat16"
    if not control:
        conf = dict(conf, **{variant: 1.0})
    toks = jnp.asarray(check._batch(seqs))
    h = ref.hidden(conf, w, toks, control=control)
    errs = []
    for i, (p, t, lg) in enumerate(seqs):
        want = ref.logits(conf, w, h[i, len(p) - 1:len(p) + len(t)],
                          control=control)
        errs.append(float(jnp.max(check._rel_err(jnp.asarray(lg), want))))
    return max(errs)


@pytest.mark.parametrize("variant", MULTIPLIERS + ("bfloat16",))
def test_reference_variants_fail_the_tolerance(served, variant):
    """Each multiplier dropped (set to 1) and the bfloat16 control read
    the program's logits as off by more than the tolerance: the check
    separates them from the model as published."""
    ref, conf, w, seqs = served
    assert _variant_err(ref, conf, w, seqs, variant) > 10 * TOL


def test_tiny_long_decode_run_is_correct():
    """The cell's whole path on the CPU: RealCluster, serve_forever and the
    engine, then the check against the reference."""
    t = json.loads((runner.BENCH_DIR / "traffic" /
                    "long_decode.json").read_text())
    t = copy.deepcopy(t)
    for s in t["streams"]:
        s["prompt"].update(round_up=64, min=64, max=192)
        s["output"].update(min=8, max=40, max_total=300)
        s["rate"] = 2.0
    cell = runner.Cell(f"{CONFIG}.long_decode", CONFIG, tiny_conf(), t, 1,
                       [{"name": n, "unit": "ms"}
                        for n in ("itl_p50_ms", "itl_p98_ms")], [],
                       limits={"logit_err": {"limit": 1e-3}})
    out = runner.run(cell, 2 ** 31 + 11, 8.0, False,
                     t_start=time.monotonic(), require_tpu=False)
    assert out["correct"], out
    assert out["checks"]["positions_compared"]["value"] >= 20
    assert set(out["metrics"]) == {"itl_p50_ms", "itl_p98_ms"}


def test_flash_prefill_roofline_share():
    """Two prefills of 4096 and 8192 positions through one attention layer
    of 32/8 heads of 64: compute bounds both, so the least time is the
    operations over the peak, here over 2 s of kernel time."""
    path = runner.BENCH_DIR / "metrics" / "flash_prefill_roofline.py"
    spec = importlib.util.spec_from_file_location("flash_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    read = mod.read
    ops = sum(mod.ops_and_bytes(s, 32, 8, 64)[0] for s in (4096, 8192))
    assert mod.ops_and_bytes(4096, 32, 8, 64) == \
        (2 * 4096 ** 2 * 32 * 64, 4 * 4096 * 64 * 80)
    dev = tr.Device("/device:TPU:0")
    dev.ops = [(1.0, 2.0, "%flash_prefill.1 = f32[1,32,4096,64] custom-call("
                          "%a, %b, %c)"),
               (3.0, 4.0, "%flash_prefill.1 = f32[1,32,8192,64] custom-call("
                          "%a, %b, %c)"),
               (4.0, 5.0, "%fusion.2 = f32[8]{0} fusion(%z)")]
    rec = serve.Recorder(traced=True)
    rec.prefills = [(0, 0.9, 2.1, 4096), (0, 2.9, 4.1, 8192)]
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    dims = {"Hq": 32, "Hkv": 8, "hd": 64, "n_attn": 1}
    cell = runner.Cell("x.y", "x", {"family": "granite"}, {}, 1, [], [])

    def ctx(trace):
        return runner.Context(cell, dims, rec, None, trace, peaks)

    trace = tr.Trace([dev], [], (0.0, 10.0))
    assert read(ctx(trace)) == pytest.approx(
        100.0 * ops / peaks["flops_per_s"] / 2.0)
    dev.ops = dev.ops[2:]
    assert read(ctx(trace)) is None
    assert read(ctx(None)) is None
