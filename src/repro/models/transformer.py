"""Decoder-only transformer stack shared by dense, MoE and VLM architectures.

Layers are stacked (leading axis = n_layers) and executed with
``jax.lax.scan`` so the lowered HLO stays compact even for 60-80 layer
configurations — essential for the 40-config dry-run matrix.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.moe import init_moe, moe_forward, moe_forward_batched

Params = Dict[str, Any]


def init_layer(cfg: ModelConfig, key, dtype) -> Params:
    ka, kf, kn1, kn2 = jax.random.split(key, 4)
    p = {
        "attn": L.init_attention(cfg, ka, dtype),
        "norm1": L.init_norm(cfg, kn1, dtype),
        "norm2": L.init_norm(cfg, kn2, dtype),
    }
    if cfg.is_moe:
        p["moe"] = init_moe(cfg, kf, dtype)
    else:
        p["ffn"] = L.init_ffn(cfg, kf, dtype)
    return p


def init_params(cfg: ModelConfig, key, dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    ke, kl, kn = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_layer(cfg, k, dtype))(layer_keys)
    return {
        "emb": L.init_embeddings(cfg, ke, dtype),
        "layers": stacked,
        "final_norm": L.init_norm(cfg, kn, dtype),
    }


def _layer_forward(cfg: ModelConfig, lp: Params, x: jax.Array,
                   positions: jax.Array, prefix_len: int) -> Tuple[jax.Array, jax.Array]:
    h = L.apply_norm(cfg, lp["norm1"], x)
    x = x + L.attention_forward(cfg, lp["attn"], h, positions=positions,
                                prefix_len=prefix_len)
    h = L.apply_norm(cfg, lp["norm2"], x)
    if cfg.is_moe:
        # per-batch-row dispatch keeps MoE buffers data-sharded (§Perf A1)
        y, aux = moe_forward_batched(cfg, lp["moe"], h)
        return x + y, aux
    return x + L.ffn_forward(cfg, lp["ffn"], h), jnp.zeros((), jnp.float32)


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            vision_embeds: Optional[jax.Array] = None,
            remat: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits (B,S,V), aux_loss).

    For VLM configs, ``vision_embeds`` (B, n_vis, d) is prepended to the
    token embeddings (stub frontend per the task carve-out); logits are
    returned for the text positions only.
    """
    x = L.embed(params["emb"], tokens)
    prefix_len = 0
    if vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
        prefix_len = vision_embeds.shape[1]
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    def body(carry, lp):
        x, aux = carry
        x, a = _layer_forward(cfg, lp, x, positions, prefix_len)
        return (x, aux + a), None

    step = jax.checkpoint(body) if remat else body
    (x, aux), _ = L.layer_scan(step, (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    x = L.apply_norm(cfg, params["final_norm"], x)
    if prefix_len:
        x = x[:, prefix_len:]
    return L.unembed(params["emb"], x), aux


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> Dict[str, jax.Array]:
    c = L.init_kv_cache(cfg, batch, cache_len, cfg.n_layers, dtype)
    c["pos"] = jnp.zeros((batch,), jnp.int32)
    c["slot_pos"] = jnp.full((batch, cache_len), -1, jnp.int32)
    return c


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            cache_len: Optional[int] = None,
            vision_embeds: Optional[jax.Array] = None,
            past_cache: Optional[Dict[str, jax.Array]] = None,
            dtype=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Run the prompt, build the KV cache, return last-position logits.

    Uses the full-sequence path and stores the (roped) K/V of the last
    ``cache_len`` positions. With a sliding window the cache is laid out as
    the ring buffer the decode step expects (slot = pos % cache_len, which
    for a contiguous tail is a plain roll).

    ``past_cache``: an existing (non-windowed, fully-filled) cache to
    continue from — the chunked-prefill / prefix-caching path: only the new
    tokens are computed; the returned cache covers past + new.
    """
    dtype = dtype or jnp.dtype(cfg.dtype)
    B, S = tokens.shape
    n_vis = vision_embeds.shape[1] if vision_embeds is not None else 0
    window = cfg.sliding_window or 0
    past_len = 0
    if past_cache is not None:
        assert window == 0, "chunked prefill assumes a non-windowed cache"
        assert n_vis == 0, "vision prefix must be in the first chunk"
        past_len = int(past_cache["k"].shape[2])
    total = S + n_vis
    full_len = past_len + total
    clen = cache_len or (min(full_len, window) if window else full_len)

    x = L.embed(params["emb"], tokens)
    if vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
    positions = jnp.broadcast_to(
        past_len + jnp.arange(total)[None, :], (B, total))

    def body(carry, inp):
        if past_cache is not None:
            lp, pk, pv = inp
            past = (pk, pv)
        else:
            lp, past = inp, None
        x, aux = carry
        h = L.apply_norm(cfg, lp["norm1"], x)
        o, k, v = L.attention_forward(cfg, lp["attn"], h, positions=positions,
                                      prefix_len=n_vis, return_kv=True,
                                      past_kv=past)
        x = x + o
        h = L.apply_norm(cfg, lp["norm2"], x)
        if cfg.is_moe:
            y, a = moe_forward_batched(cfg, lp["moe"], h)
            x = x + y
            aux = aux + a
        else:
            x = x + L.ffn_forward(cfg, lp["ffn"], h)
        return (x, aux), (k.astype(dtype), v.astype(dtype))

    xs = params["layers"] if past_cache is None else \
        (params["layers"], past_cache["k"], past_cache["v"])
    (x, _), (ks, vs) = L.layer_scan(
        body, (x, jnp.zeros((), jnp.float32)), xs)

    if past_cache is not None:
        ks = jnp.concatenate([past_cache["k"].astype(dtype), ks], axis=2)
        vs = jnp.concatenate([past_cache["v"].astype(dtype), vs], axis=2)
    ks, vs, sp = L.fit_cache(ks, vs, full_len, clen, window, B)
    cache = {"k": ks, "v": vs,
             "pos": jnp.full((B,), full_len, jnp.int32),
             "slot_pos": sp}
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.unembed(params["emb"], x[:, -1:])
    return logits[:, 0], cache


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step. tokens (B,1) -> logits (B,V), updated cache."""
    B = tokens.shape[0]
    x = L.embed(params["emb"], tokens)
    pos = cache["pos"]
    S = cache["k"].shape[2]
    slot = pos % S if cfg.sliding_window > 0 else pos
    slot_pos = cache["slot_pos"].at[jnp.arange(B), slot].set(pos)

    def body(carry, inp):
        x, aux = carry
        lp, kc, vc = inp
        h = L.apply_norm(cfg, lp["norm1"], x)
        o, kc, vc = L.attention_decode(cfg, lp["attn"], h, kc, vc, pos, slot_pos)
        x = x + o
        h = L.apply_norm(cfg, lp["norm2"], x)
        if cfg.is_moe:
            y, a = moe_forward(cfg, lp["moe"], h[:, 0])
            x = x + y[:, None]
            aux = aux + a
        else:
            x = x + L.ffn_forward(cfg, lp["ffn"], h)
        return (x, aux), (kc, vc)

    (x, _), (ks, vs) = L.layer_scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (params["layers"], cache["k"], cache["v"]))
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.unembed(params["emb"], x)[:, 0]
    new_cache = dict(cache, k=ks, v=vs, pos=pos + 1, slot_pos=slot_pos)
    return logits, new_cache


def serving_params(cfg: ModelConfig, params: Params) -> Params:
    """``Model.serving_params``: a dense stack's attention and FFN weights
    (and an untied head) rounded to bfloat16. The MoE and vision-language
    stacks keep theirs: no cell serves them, and MoE experts are not fed
    through ``layers.dense``."""
    if cfg.arch_type != "dense":
        return params
    return L.round_dot_weights(params, L.DOT_WEIGHTS)
