"""The layer-pattern hybrid (granite-4.0-h-micro's granitemoehybrid form):
prefill and decode agree with the full forward pass, every multiplier
reaches the logits, the compiled steps name their mixers and MLPs, and the
config's counts match the published model."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import Model
from repro.sim.perf_model import PerfModel

ARCH = "granite-4.0-h-micro"


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config(ARCH)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 70), 0,
                              cfg.vocab_size)
    return cfg, model, params, toks


def test_prefill_then_decode_match_forward(setup):
    cfg, model, params, toks = setup
    full, _ = model.forward(params, {"tokens": toks})
    last, cache = model.prefill(params, {"tokens": toks[:, :60]},
                                dtype=jnp.float32, cache_len=80)
    scale = float(jnp.max(jnp.abs(full)))
    errs = [float(jnp.max(jnp.abs(last - full[:, 59])))]
    for t in range(60, 70):
        lg, cache = model.decode_step(params, toks[:, t:t + 1], cache)
        errs.append(float(jnp.max(jnp.abs(lg - full[:, t]))))
    assert max(errs) <= 1e-5 * scale
    assert cache["ssm"].shape[0] == 9 and cache["k"].shape[0] == 1


@pytest.mark.parametrize("field", ["embedding_multiplier",
                                   "residual_multiplier",
                                   "attention_multiplier", "logits_scaling"])
def test_every_multiplier_reaches_the_logits(setup, field):
    cfg, model, params, toks = setup
    full, _ = model.forward(params, {"tokens": toks})
    other, _ = Model(cfg.with_(**{field: 1.0})).forward(params,
                                                        {"tokens": toks})
    assert float(jnp.max(jnp.abs(other - full))) > \
        1e-3 * float(jnp.max(jnp.abs(full)))


def test_attention_has_no_positional_encoding(setup):
    """NoPE: with the Mamba2 layers out of the way (a pattern of attention
    alone), a permutation of the earlier tokens leaves the last position's
    logits as they were; with RoPE it would not."""
    cfg, _, _, toks = setup
    cfg = cfg.with_(n_layers=1, layer_types=("attention",))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(2), dtype=jnp.float32)
    perm = np.concatenate([np.random.default_rng(0).permutation(69), [69]])
    a, _ = model.forward(params, {"tokens": toks})
    b, _ = model.forward(params, {"tokens": toks[:, perm]})
    np.testing.assert_allclose(a[:, -1], b[:, -1], atol=1e-5, rtol=1e-5)
    roped, _ = Model(cfg.with_(rope_theta=10000.0)).forward(
        params, {"tokens": toks[:, perm]})
    assert float(jnp.max(jnp.abs(roped[:, -1] - a[:, -1]))) > 1e-4


@pytest.mark.parametrize("step", ["prefill", "decode_step"])
def test_compiled_steps_carry_the_scopes(setup, step):
    cfg, model, params, toks = setup
    if step == "prefill":
        fn = jax.jit(lambda p, t: model.prefill(p, {"tokens": t},
                                                dtype=jnp.float32))
        args = (params, toks)
    else:
        cache = model.init_cache(2, 80, dtype=jnp.float32)
        fn = jax.jit(model.decode_step)
        args = (params, toks[:, :1], cache)
    text = fn.lower(*args).compile().as_text()
    scopes = {part for name in re.findall(r'op_name="([^"]+)"', text)
              for part in name.split("/")}
    assert {"mamba", "attention", "mlp"} <= scopes


def test_published_counts():
    cfg = get_config(ARCH)
    assert cfg.layer_types.count("attention") == cfg.n_attention_layers == 4
    assert cfg.layer_types[:10] == ("mamba",) * 5 + ("attention",) + \
        ("mamba",) * 4
    assert cfg.n_ssm_heads == 64 and cfg.attn_scale == 1 / 64
    # 3.19 B parameters (Granite 4.0-H Micro, "3B"); one period 0.952 B
    assert cfg.param_count() == pytest.approx(3.19e9, rel=0.01)
    cut = cfg.with_(n_layers=10, layer_types=cfg.layer_types[:10])
    assert cut.param_count() == pytest.approx(0.952e9, rel=0.01)
    # the perf model's KV bytes count the four attention layers alone
    pm = PerfModel(ARCH, cfg=cfg, chips=1)
    assert pm.kv_bytes_per_token() == 2 * 4 * 8 * 64 * 2
