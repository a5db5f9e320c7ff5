"""Hybrid Mamba2 + attention decoders, in two forms.

- Zamba2 (``cfg.attn_every``): a Mamba2 backbone and one attention+FFN
  block whose weights are shared, applied after every ``attn_every`` Mamba2
  layers; each application keeps its own KV cache. [arXiv:2411.15242]
- Layer pattern (``cfg.layer_types``, granitemoehybrid): each layer is a
  Mamba2 or an attention mixer, named in order, with weights of its own,
  and every mixer is followed by a SwiGLU MLP. A layer is
  ``x + r * mixer(norm(x))`` then ``x + r * mlp(norm(x))``, with the muP
  multipliers of the config: ``r`` the residual multiplier, the token
  embeddings scaled by the embedding multiplier, attention scores by the
  attention multiplier, the logits divided by the logits scaling.
  Attention has no positional encoding where ``cfg.rope_theta`` is 0.

Both keep two kinds of state in one cache: ``ssm``/``conv`` stacked over
the Mamba2 layers and ``k``/``v`` over the attention layers. The mixers and
MLPs run under ``jax.named_scope`` names ``mamba``, ``attention`` and
``mlp``, which the compiled programs carry as op metadata.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm
from repro.models.ssm import init_mamba_layer, mamba_decode, mamba_forward

Params = Dict[str, Any]


def _n_groups(cfg: ModelConfig) -> int:
    assert cfg.n_layers % cfg.attn_every == 0
    return cfg.n_layers // cfg.attn_every


def _n_mamba(cfg: ModelConfig) -> int:
    return cfg.n_layers - cfg.n_attention_layers if cfg.layer_types \
        else cfg.n_layers


def _segments(cfg: ModelConfig) -> List[Tuple[str, int, int, int]]:
    """The layer pattern as runs: (kind, index of its first layer among
    that kind's stacked weights, its first layer, layers in the run), a run
    being consecutive Mamba2 layers or one attention layer."""
    segs: List[Tuple[str, int, int, int]] = []
    seen = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(cfg.layer_types[:cfg.n_layers]):
        if kind not in seen:
            raise ValueError(f"unknown layer type {kind!r}")
        if kind == "mamba" and segs and segs[-1][0] == "mamba":
            k, j, first, n = segs[-1]
            segs[-1] = (k, j, first, n + 1)
        else:
            segs.append((kind, seen[kind], i, 1))
        seen[kind] += 1
    return segs


def _rows(stacked: Params, lo: int, n: int) -> Params:
    return jax.tree.map(lambda a: a[lo:lo + n], stacked)


def _row(stacked: Params, i: int) -> Params:
    return jax.tree.map(lambda a: a[i], stacked)


def _run_weights(params: Params, kind: str, j: int, first: int,
                 n: int) -> Params:
    """One run's weights, sliced from the stacks by kind: a Mamba2 run's
    mixers, MLPs and MLP norms stacked over its layers; an attention
    layer's mixer, input norm, MLP and MLP norm."""
    norm = params["mlp_norm"]["w"]
    if kind == "mamba":
        return {"mamba": _rows(params["mamba"], j, n),
                "mlp": _rows(params["mlp"], first, n),
                "mlp_norm": norm[first:first + n]}
    return {"attn": _row(params["attn"], j),
            "attn_norm": params["attn_norm"]["w"][j],
            "mlp": _row(params["mlp"], first), "mlp_norm": norm[first]}


def _runs(cfg: ModelConfig, params: Params):
    """The runs of the layer pattern (``_segments``), each with its
    weights: as ``serving_params`` sliced them once, or sliced here."""
    segs = _segments(cfg)
    runs = params.get("runs") or [_run_weights(params, *s) for s in segs]
    return list(zip(segs, runs))


def init_params(cfg: ModelConfig, key, dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.layer_types:
        return _init_pattern(cfg, key, dtype)
    ke, kl, ks, kn = jax.random.split(key, 4)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_mamba_layer(cfg, k, dtype))(layer_keys)
    ka, kf, k1, k2 = jax.random.split(ks, 4)
    shared = {
        "attn": L.init_attention(cfg, ka, dtype),
        "ffn": L.init_ffn(cfg, kf, dtype),
        "norm1": L.init_norm(cfg, k1, dtype),
        "norm2": L.init_norm(cfg, k2, dtype),
    }
    return {
        "emb": L.init_embeddings(cfg, ke, dtype),
        "layers": stacked,
        "shared": shared,
        "final_norm": {"w": jnp.ones((cfg.d_model,), dtype)},
    }


def _init_pattern(cfg: ModelConfig, key, dtype) -> Params:
    """Mamba2 layers (their ``rms_w`` is the layer's input norm) and
    attention layers stacked by kind, an MLP and its norm per layer."""
    n_m, n_a = _n_mamba(cfg), cfg.n_attention_layers
    ke, km, ka, kf = jax.random.split(key, 4)
    d = cfg.d_model
    return {
        "emb": L.init_embeddings(cfg, ke, dtype),
        "mamba": jax.vmap(lambda k: init_mamba_layer(cfg, k, dtype))(
            jax.random.split(km, n_m)),
        "attn": jax.vmap(lambda k: L.init_attention(cfg, k, dtype))(
            jax.random.split(ka, n_a)),
        "attn_norm": {"w": jnp.ones((n_a, d), dtype)},
        "mlp": jax.vmap(lambda k: L.init_ffn(cfg, k, dtype))(
            jax.random.split(kf, cfg.n_layers)),
        "mlp_norm": {"w": jnp.ones((cfg.n_layers, d), dtype)},
        "final_norm": {"w": jnp.ones((d,), dtype)},
    }


# ------------------------------------------------------- zamba2 form


def _group_slice(stacked: Params, g: int, size: int) -> Params:
    return jax.tree.map(lambda a: a[g * size:(g + 1) * size], stacked)


def _shared_block_forward(cfg: ModelConfig, sp: Params, x: jax.Array,
                          positions: jax.Array) -> jax.Array:
    with jax.named_scope("attention"):
        h = L.apply_norm(cfg, sp["norm1"], x)
        x = x + L.attention_forward(cfg, sp["attn"], h, positions=positions)
    with jax.named_scope("mlp"):
        h = L.apply_norm(cfg, sp["norm2"], x)
        return x + L.ffn_forward(cfg, sp["ffn"], h)


# -------------------------------------------------- layer-pattern form


def _mlp(cfg: ModelConfig, fp: Params, norm_w: jax.Array,
         x: jax.Array) -> jax.Array:
    with jax.named_scope("mlp"):
        h = L.rms_norm(x, norm_w)
        return x + cfg.residual_multiplier * L.ffn_forward(cfg, fp, h)


def _embed(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    x = L.embed(params["emb"], tokens)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    x = L.rms_norm(x, params["final_norm"]["w"])
    logits = L.unembed(params["emb"], x)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _pattern_stack(cfg: ModelConfig, params: Params, x: jax.Array, dtype):
    """Every layer of the pattern over a whole sequence x (B,S,d). Returns
    x and the states: Mamba2 ``ssm`` and ``conv`` stacked in layer order,
    and each attention layer's K and V."""
    hs, convs, ks, vs = [], [], [], []

    def mamba_mlp(x, lp):
        mp, fp, nw = lp
        with jax.named_scope("mamba"):
            x, h, conv = mamba_forward(cfg, mp, x)
        return _mlp(cfg, fp, nw, x), (h, conv.astype(dtype))

    for (kind, *_), w in _runs(cfg, params):
        if kind == "mamba":
            x, (h, conv) = L.layer_scan(
                mamba_mlp, x, (w["mamba"], w["mlp"], w["mlp_norm"]))
            hs.append(h)
            convs.append(conv)
            continue
        with jax.named_scope("attention"):
            h = L.rms_norm(x, w["attn_norm"])
            o, k, v = L.attention_forward(cfg, w["attn"], h, return_kv=True)
            x = x + cfg.residual_multiplier * o
        x = _mlp(cfg, w["mlp"], w["mlp_norm"], x)
        ks.append(k.astype(dtype))
        vs.append(v.astype(dtype))
    return x, (hs, convs, ks, vs)


def _pattern_decode(cfg: ModelConfig, params: Params, x: jax.Array,
                    cache: Dict[str, jax.Array], pos: jax.Array,
                    slot_pos: jax.Array):
    """One token through every layer of the pattern; returns x and the
    new ``ssm``, ``conv``, ``k`` and ``v``."""
    hs, convs = [], []
    k_all, v_all = cache["k"], cache["v"]

    def mamba_mlp(x, inp):
        mp, fp, nw, h, conv = inp
        with jax.named_scope("mamba"):
            x, h, conv = mamba_decode(cfg, mp, x, h, conv)
        return _mlp(cfg, fp, nw, x), (h, conv)

    for (kind, j, _, n), w in _runs(cfg, params):
        if kind == "mamba":
            x, (h, conv) = L.layer_scan(mamba_mlp, x, (
                w["mamba"], w["mlp"], w["mlp_norm"],
                cache["ssm"][j:j + n], cache["conv"][j:j + n]))
            hs.append(h)
            convs.append(conv)
            continue
        with jax.named_scope("attention"):
            h = L.rms_norm(x, w["attn_norm"])
            o, kc, vc = L.attention_decode(cfg, w["attn"], h,
                                           k_all[j], v_all[j], pos, slot_pos)
            k_all, v_all = k_all.at[j].set(kc), v_all.at[j].set(vc)
            x = x + cfg.residual_multiplier * o
        x = _mlp(cfg, w["mlp"], w["mlp_norm"], x)
    return x, jnp.concatenate(hs), jnp.concatenate(convs), k_all, v_all


# ------------------------------------------------------------- API


def serving_params(cfg: ModelConfig, params: Params) -> Params:
    """``Model.serving_params``: the Mamba2, attention, MLP and untied-head
    weights rounded to bfloat16; in the layer-pattern form each run's
    weights are also sliced from the stacks now, once, since a run's slice
    of a stack is otherwise a copy the program makes in every call."""
    params = L.round_dot_weights(params, ssm.DOT_WEIGHTS + L.DOT_WEIGHTS)
    if not cfg.layer_types:
        return params
    return {"emb": params["emb"], "final_norm": params["final_norm"],
            "runs": [_run_weights(params, *s) for s in _segments(cfg)]}


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            remat: bool = False) -> Tuple[jax.Array, jax.Array]:
    B, S = tokens.shape
    if cfg.layer_types:
        x, _ = _pattern_stack(cfg, params, _embed(cfg, params, tokens),
                              params["final_norm"]["w"].dtype)
        return _logits(cfg, params, x), jnp.zeros((), jnp.float32)
    x = L.embed(params["emb"], tokens)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    G, A = _n_groups(cfg), cfg.attn_every

    def mamba_body(x, lp):
        with jax.named_scope("mamba"):
            x, _, _ = mamba_forward(cfg, lp, x)
        return x, None

    step = jax.checkpoint(mamba_body) if remat else mamba_body
    for g in range(G):
        x, _ = L.layer_scan(step, x, _group_slice(params["layers"], g, A))
        x = _shared_block_forward(cfg, params["shared"], x, positions)
    x = L.rms_norm(x, params["final_norm"]["w"])
    return L.unembed(params["emb"], x), jnp.zeros((), jnp.float32)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> Dict[str, jax.Array]:
    n_attn = cfg.n_attention_layers
    H, P, N = cfg.n_ssm_heads, cfg.ssm.head_dim, cfg.ssm.state_dim
    ch = cfg.d_inner + 2 * N
    hd = cfg.resolved_head_dim
    n_m = _n_mamba(cfg)
    return {
        "ssm": jnp.zeros((n_m, batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((n_m, batch, cfg.ssm.conv_width - 1, ch), dtype),
        "k": jnp.zeros((n_attn, batch, cache_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((n_attn, batch, cache_len, cfg.n_kv_heads, hd), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
        "slot_pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            cache_len: Optional[int] = None, dtype=None, **_):
    dtype = dtype or jnp.dtype(cfg.dtype)
    B, S = tokens.shape
    window = cfg.sliding_window or 0
    clen = cache_len or (min(S, window) if window else S)
    if cfg.layer_types:
        x, (hs, convs, ks, vs) = _pattern_stack(
            cfg, params, _embed(cfg, params, tokens), dtype)
    else:
        x, (hs, convs, ks, vs) = _zamba_prefill(cfg, params, tokens, dtype)
    k_all, v_all, spos = L.fit_cache(jnp.stack(ks), jnp.stack(vs), S, clen,
                                     window, B)
    cache = {
        "ssm": jnp.concatenate(hs, axis=0),
        "conv": jnp.concatenate(convs, axis=0),
        "k": k_all, "v": v_all,
        "pos": jnp.full((B,), S, jnp.int32),
        "slot_pos": spos,
    }
    if cfg.layer_types:
        return _logits(cfg, params, x[:, -1]), cache
    x = L.rms_norm(x, params["final_norm"]["w"])
    logits = L.unembed(params["emb"], x[:, -1:])
    return logits[:, 0], cache


def _zamba_prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   dtype):
    B, S = tokens.shape
    x = L.embed(params["emb"], tokens)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    G, A = _n_groups(cfg), cfg.attn_every

    def mamba_body(x, lp):
        with jax.named_scope("mamba"):
            x, h, conv = mamba_forward(cfg, lp, x)
        return x, (h, conv.astype(dtype))

    hs, convs, ks, vs = [], [], [], []
    sp = params["shared"]
    for g in range(G):
        x, (h, conv) = L.layer_scan(mamba_body, x,
                                    _group_slice(params["layers"], g, A))
        hs.append(h)
        convs.append(conv)
        with jax.named_scope("attention"):
            hnorm = L.apply_norm(cfg, sp["norm1"], x)
            o, k, v = L.attention_forward(cfg, sp["attn"], hnorm,
                                          positions=positions, return_kv=True)
            x = x + o
        with jax.named_scope("mlp"):
            hnorm = L.apply_norm(cfg, sp["norm2"], x)
            x = x + L.ffn_forward(cfg, sp["ffn"], hnorm)
        ks.append(k.astype(dtype))
        vs.append(v.astype(dtype))
    return x, (hs, convs, ks, vs)


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: Dict[str, jax.Array]):
    B = tokens.shape[0]
    pos = cache["pos"]
    Sc = cache["k"].shape[2]
    slot = pos % Sc if cfg.sliding_window > 0 else pos
    slot_pos = cache["slot_pos"].at[jnp.arange(B), slot].set(pos)
    if cfg.layer_types:
        x, ssm, conv, k, v = _pattern_decode(
            cfg, params, _embed(cfg, params, tokens), cache, pos, slot_pos)
        return _logits(cfg, params, x)[:, 0], dict(
            cache, ssm=ssm, conv=conv, k=k, v=v, pos=pos + 1,
            slot_pos=slot_pos)
    x = L.embed(params["emb"], tokens)
    G, A = _n_groups(cfg), cfg.attn_every
    sp = params["shared"]

    def mamba_body(x, inp):
        lp, h, conv = inp
        with jax.named_scope("mamba"):
            x, h, conv = mamba_decode(cfg, lp, x, h, conv)
        return x, (h, conv)

    hs, convs, ks, vs = [], [], [], []
    for g in range(G):
        grp = (_group_slice(params["layers"], g, A),
               cache["ssm"][g * A:(g + 1) * A],
               cache["conv"][g * A:(g + 1) * A])
        x, (h, conv) = L.layer_scan(mamba_body, x, grp)
        hs.append(h)
        convs.append(conv)
        with jax.named_scope("attention"):
            hnorm = L.apply_norm(cfg, sp["norm1"], x)
            o, kc, vc = L.attention_decode(cfg, sp["attn"], hnorm,
                                           cache["k"][g], cache["v"][g], pos,
                                           slot_pos)
            x = x + o
        with jax.named_scope("mlp"):
            hnorm = L.apply_norm(cfg, sp["norm2"], x)
            x = x + L.ffn_forward(cfg, sp["ffn"], hnorm)
        ks.append(kc)
        vs.append(vc)

    x = L.rms_norm(x, params["final_norm"]["w"])
    logits = L.unembed(params["emb"], x)[:, 0]
    new_cache = dict(cache,
                     ssm=jnp.concatenate(hs, axis=0),
                     conv=jnp.concatenate(convs, axis=0),
                     k=jnp.stack(ks), v=jnp.stack(vs),
                     pos=pos + 1, slot_pos=slot_pos)
    return logits, new_cache
