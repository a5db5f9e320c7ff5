"""Pure-SSM (Mamba2) decoder model: attention-free, O(1) decode state."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.ssm import (DOT_WEIGHTS, init_mamba_layer, mamba_decode,
                              mamba_forward)

Params = Dict[str, Any]


def init_params(cfg: ModelConfig, key, dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    ke, kl, kn = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_mamba_layer(cfg, k, dtype))(layer_keys)
    return {
        "emb": L.init_embeddings(cfg, ke, dtype),
        "layers": stacked,
        "final_norm": {"w": jnp.ones((cfg.d_model,), dtype)},
    }


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            remat: bool = False) -> Tuple[jax.Array, jax.Array]:
    x = L.embed(params["emb"], tokens)

    def body(x, lp):
        x, _, _ = mamba_forward(cfg, lp, x)
        return x, None

    step = jax.checkpoint(body) if remat else body
    x, _ = L.layer_scan(step, x, params["layers"])
    x = L.rms_norm(x, params["final_norm"]["w"])
    return L.unembed(params["emb"], x), jnp.zeros((), jnp.float32)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> Dict[str, jax.Array]:
    del cache_len  # SSM state is O(1) in context length
    H, P, N = cfg.n_ssm_heads, cfg.ssm.head_dim, cfg.ssm.state_dim
    ch = cfg.d_inner + 2 * N
    return {
        "ssm": jnp.zeros((cfg.n_layers, batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch, cfg.ssm.conv_width - 1, ch), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            dtype=None, **_) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    B, S = tokens.shape
    x = L.embed(params["emb"], tokens)

    def body(x, lp):
        x, h, conv = mamba_forward(cfg, lp, x)
        return x, (h, conv.astype(dtype))

    x, (hs, convs) = L.layer_scan(body, x, params["layers"])
    x = L.rms_norm(x, params["final_norm"]["w"])
    logits = L.unembed(params["emb"], x[:, -1:])
    cache = {"ssm": hs, "conv": convs,
             "pos": jnp.full((B,), S, jnp.int32)}
    return logits[:, 0], cache


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x = L.embed(params["emb"], tokens)

    def body(x, inp):
        lp, h, conv = inp
        x, h, conv = mamba_decode(cfg, lp, x, h, conv)
        return x, (h, conv)

    x, (hs, convs) = L.layer_scan(body, x, (params["layers"], cache["ssm"],
                                            cache["conv"]))
    x = L.rms_norm(x, params["final_norm"]["w"])
    logits = L.unembed(params["emb"], x)[:, 0]
    return logits, dict(cache, ssm=hs, conv=convs, pos=cache["pos"] + 1)


def serving_params(cfg: ModelConfig, params: Params) -> Params:
    """``Model.serving_params``: the in and out projections (and an untied
    head) rounded to bfloat16."""
    return L.round_dot_weights(params, DOT_WEIGHTS + ("head",))
