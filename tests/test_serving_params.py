"""The serving programs' dot-only weights in bfloat16
(``Model.serving_params``): rounded once where the backend computes a
float32 dot as one bfloat16 pass, with the programs' arithmetic unchanged,
and the probe that decides it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import Model, ssm
from repro.models import layers as L
from repro.obs import FlightRecorder
from repro.serving import engine as engine_mod
from repro.serving.engine import (Engine, default_dot_is_bf16,
                                  dot_is_one_bf16_pass)
from repro.serving.request import make_interactive

ROUNDING = {"ssm": "mamba2-1.3b", "hybrid": "granite-4.0-h-micro",
            "dense": "granite-8b"}
KEEPING = {"moe": "qwen2-moe-a2.7b", "audio": "whisper-base",
           "vlm": "internvl2-2b"}
DOT = set(ssm.DOT_WEIGHTS + L.DOT_WEIGHTS)
BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)


def _setup(arch):
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0), dtype=jnp.float32)


def _named_leaves(tree):
    """(name of the leaf's innermost dict key, leaf) for every leaf."""
    out = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [k.key for k in path if isinstance(k, jax.tree_util.DictKey)]
        out.append((keys[-1], leaf))
    return out


def _emulated_dense(x, w):
    """A float32 ``x @ w`` as a backend computes it in one bfloat16 pass:
    both operands rounded to bfloat16 inside the program, products and
    sums in float32. (Rounded activations make the programs' outputs
    sensitive to the last bit of every sum, so the emulation sums in the
    same kernel as the prepared dot; ``test_dense_is_one_bf16_pass`` holds
    that kernel to a HIGHEST-precision product.)"""
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _prompt(cfg, n, seed):
    return jax.random.randint(jax.random.PRNGKey(seed), (2, n), 0,
                              cfg.vocab_size)


@pytest.mark.parametrize("family", sorted(ROUNDING))
def test_only_dot_weights_are_rounded(family):
    """The dot-only weights come back bfloat16; embeddings, norms, the
    conv, A_log, D and dt_bias stay float32."""
    _, model, params = _setup(ROUNDING[family])
    leaves = _named_leaves(model.serving_params(params, bf16_dots=True))
    rounded = {name for name, a in leaves if a.dtype == BF16}
    assert rounded and rounded <= DOT
    for name, a in leaves:
        assert a.dtype == (BF16 if name in DOT else F32), name
    assert {"tok"} <= {name for name, _ in leaves} - rounded


@pytest.mark.parametrize("family", sorted(ROUNDING) + sorted(KEEPING))
def test_params_unchanged_where_not_engaged(family):
    """Without bf16 dots every family gets its params back as they are;
    the families that name no dot-only weights do so either way."""
    arch = {**ROUNDING, **KEEPING}[family]
    _, model, params = _setup(arch)
    got = model.serving_params(params, bf16_dots=False)
    assert got is params
    if family in KEEPING:
        assert model.serving_params(params, bf16_dots=True) is params


def test_dense_is_one_bf16_pass():
    """``L.dense`` on a bfloat16 weight computes the product of both
    operands rounded to bfloat16 at HIGHEST precision, in float32; on a
    float32 weight it is ``x @ w``."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 384))
    wb = w.astype(jnp.bfloat16)
    got = L.dense(x, wb)
    want = jnp.matmul(x.astype(jnp.bfloat16).astype(jnp.float32),
                      wb.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    assert got.dtype == F32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want).max()))
    assert float(jnp.abs(got - x @ w).max()) > 1e-3
    np.testing.assert_array_equal(np.asarray(L.dense(x, w)),
                                  np.asarray(x @ w))


@pytest.mark.parametrize("family", sorted(ROUNDING))
def test_prepared_programs_compute_the_emulated_arithmetic(family,
                                                           monkeypatch):
    """Prefill and decode on the prepared params give what the float32
    programs give, on params rounded to bfloat16 and back, where each
    ``L.dense`` is a one-pass bfloat16 dot, within 1e-5; and not what the
    plain float32 programs give on the CPU."""
    cfg, model, params = _setup(ROUNDING[family])
    prepared = model.serving_params(params, bf16_dots=True)
    batch = {"tokens": _prompt(cfg, 40, 1)}
    nxt = _prompt(cfg, 1, 2)

    def run(p):
        # new functions each run: a cached trace would skip the patch
        logits, cache = jax.jit(
            lambda p, b: model.prefill(p, b, dtype=jnp.float32))(p, batch)
        step, cache = jax.jit(
            lambda p, t, c: model.decode_step(p, t, c))(p, nxt, cache)
        return [logits, step] + jax.tree.leaves(cache)

    got, plain = run(prepared), run(params)
    with monkeypatch.context() as m:
        m.setattr(L, "dense", _emulated_dense)
        want = run(jax.tree.map(lambda a: a.astype(jnp.float32), prepared))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5 * float(jnp.abs(w).max()))
    off = np.abs(np.asarray(got[1]) - np.asarray(plain[1])).max()
    assert off > 1e-4 * float(jnp.abs(plain[1]).max())


@pytest.mark.parametrize("family", sorted(ROUNDING))
def test_prepared_decode_takes_bf16_weights_with_no_converts(family):
    """The decode step lowers with each rounded weight as a bfloat16
    parameter and no convert of a weight's (or a layer's slice of it)
    shape: the dots read the weights as they are."""
    cfg, model, params = _setup(ROUNDING[family])
    prepared = model.serving_params(params, bf16_dots=True)
    cache = model.init_cache(2, 64, dtype=jnp.float32)
    text = jax.jit(model.decode_step).lower(
        prepared, jnp.zeros((2, 1), jnp.int32), cache).as_text()
    main = next(line for line in text.splitlines()
                if "func.func public @main" in line)
    converts = [line for line in text.splitlines()
                if "stablehlo.convert" in line]
    for name, a in _named_leaves(prepared):
        if a.dtype != BF16:
            continue
        assert f"tensor<{'x'.join(map(str, a.shape))}xbf16>" in main, name
        # a stack's layers enter the scan's body one at a time
        for shape in (a.shape, a.shape[1:]) if a.ndim > 2 else (a.shape,):
            dims = "x".join(map(str, shape))
            assert not [c for c in converts if f"<{dims}x" in c], (name, shape)


def test_probe_reads_the_backend():
    """On the CPU a float32 dot is exact float32, so the probe says no;
    a dot that rounds its operands to bfloat16 is taken for one; one that
    keeps TF32's ten bits, or full float32, is not."""
    dev = jax.devices()[0]
    assert default_dot_is_bf16(dev) is False

    def rounding(x, w):
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    def tf32(a):
        bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFFE000), jnp.float32)

    assert dot_is_one_bf16_pass(rounding, dev) is True
    assert dot_is_one_bf16_pass(lambda x, w: tf32(x) @ tf32(w), dev) is False
    assert dot_is_one_bf16_pass(
        lambda x, w: jnp.matmul(x, w, precision="highest"), dev) is False


@pytest.fixture
def probe_says(monkeypatch):
    """Force the engine's probe's answer, as on a backend whose float32
    dot is (or is not) one bfloat16 pass."""
    def force(answer):
        monkeypatch.setattr(engine_mod, "default_dot_is_bf16",
                            lambda device: answer)
    return force


@pytest.mark.parametrize("family", sorted(ROUNDING))
def test_engine_serves_the_emulated_tokens(family, probe_says,
                                           served_tokens, monkeypatch):
    """An engine whose probe engages serves, token for token, what an
    engine serves on the float32 emulation of one-pass bfloat16 dots."""
    cfg, model, params = _setup(ROUNDING[family])

    def reqs():
        out = [make_interactive(12 + 5 * i, 5 + 3 * i) for i in range(3)]
        for i, r in enumerate(out):
            r.prompt_tokens = np.asarray(_prompt(cfg, r.prompt_len, 10 + i)[0])
        return out

    probe_says(True)
    eng = Engine(cfg, params=params, max_slots=2, max_len=64,
                 dtype=jnp.float32)
    assert {a.dtype for a in jax.tree.leaves(eng.params)} == {BF16, F32}
    got = served_tokens(eng, reqs())

    probe_says(False)
    emulated = jax.tree.map(lambda a: a.astype(jnp.float32),
                            model.serving_params(params, bf16_dots=True))
    monkeypatch.setattr(L, "dense", _emulated_dense)
    ref = Engine(cfg, params=emulated, max_slots=2, max_len=64,
                 dtype=jnp.float32)
    assert ref.params is not None
    assert got == served_tokens(ref, reqs(), eager=True)


@pytest.mark.parametrize("engaged", [True, False])
def test_prepare_weights_span_carries_the_rounded_mib(engaged, probe_says):
    """The engine's ``engine.prepare_weights`` row carries the MiB of
    float32 weights it rounded: every dot-only weight where the probe
    engages, 0 where it does not."""
    cfg = get_smoke_config("mamba2-1.3b").with_(d_model=512)
    params = Model(cfg).init(jax.random.PRNGKey(0), dtype=jnp.float32)
    rec = FlightRecorder()
    probe_says(engaged)
    Engine(cfg, params=params, max_slots=2, max_len=32, dtype=jnp.float32,
           obs=rec, instance_id=3)
    rows = [r for r in rec.host_spans.rows()
            if rec.host_span_names[r["name"]] == "engine.prepare_weights"]
    assert len(rows) == 1 and rows[0]["instance"] == 3
    want = round(sum(a.size * 4 for name, a in _named_leaves(params)
                     if name in DOT) / 2 ** 20)
    assert want >= 10
    assert rows[0]["arg"] == (want if engaged else 0)
