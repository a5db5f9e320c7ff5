"""Shared building blocks for all architectures.

Pure-JAX (no flax): parameters are nested dicts of jnp arrays created by
``init_*`` functions and consumed by the matching forward functions.

Conventions:
- activations compute in the parameter dtype; softmax / norms in float32.
- attention caches are dicts ``{"k": (B,S,Hkv,D), "v": ..., }`` per layer,
  stacked over layers by the model wrappers; absolute positions live in the
  top-level cache as ``pos (B,)`` and ``slot_pos (B,S)`` (supports both full
  caches and sliding-window ring buffers).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

Params = Dict[str, Any]

# ---------------------------------------------------------------- utilities


def layer_scan(body, carry, xs):
    """lax.scan over stacked layers, honoring the dry-run unroll flag
    (see repro.models.runtime_flags — XLA cost analysis needs unrolled
    loops for correct FLOP/byte counts)."""
    from repro.models import runtime_flags
    return jax.lax.scan(body, carry, xs,
                        unroll=runtime_flags.get_scan_unroll())


def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def dense(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` for a weight that only feeds this product. A bfloat16 ``w``
    under a float32 ``x`` is a weight rounded once for a backend whose
    default-precision float32 dot is one bfloat16 pass (``serving_params``
    of each family): ``x`` is rounded as that dot rounds it and the product
    accumulates and returns in float32. A plain ``x @ w`` would promote
    ``w`` back to a float32 copy."""
    if w.dtype == jnp.bfloat16 and x.dtype == jnp.float32:
        return jnp.matmul(x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)
    return x @ w


# the weights this module feeds only to ``dense``: attention, FFN, untied head
DOT_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "head")


def round_dot_weights(params: Params, names) -> Params:
    """``params`` with every float32 leaf under a key in ``names`` rounded
    to bfloat16; the rest are the same arrays."""
    def walk(p):
        if not isinstance(p, dict):
            return p
        return {k: v.astype(jnp.bfloat16)
                if k in names and getattr(v, "dtype", None) == jnp.float32
                else walk(v) for k, v in p.items()}
    return walk(params)


def rms_norm(x: jax.Array, w: Optional[jax.Array], eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(dt)


def layer_norm(x: jax.Array, w: Optional[jax.Array], b: Optional[jax.Array],
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(dt)


def init_norm(cfg: ModelConfig, key, dtype) -> Params:
    if cfg.norm == "rmsnorm":
        return {"w": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((cfg.d_model,), dtype),
                "b": jnp.zeros((cfg.d_model,), dtype)}
    return {}  # nonparametric (OLMo-style)


def apply_norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["w"])
    if cfg.norm == "layernorm":
        return layer_norm(x, p["w"], p["b"])
    return layer_norm(x, None, None)  # nonparametric LN


# ---------------------------------------------------------------- RoPE


def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin of shape (..., head_dim//2)."""
    half = head_dim // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) *
                    (math.log(theta) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (..., n_heads, head_dim); cos/sin broadcastable to (..., 1, head_dim//2)."""
    dt = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(dt)


# ---------------------------------------------------------------- attention


def init_attention(cfg: ModelConfig, key, dtype, d_model: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": _dense_init(kq, (d, cfg.n_heads * hd), dtype),
        "wk": _dense_init(kk, (d, cfg.n_kv_heads * hd), dtype),
        "wv": _dense_init(kv, (d, cfg.n_kv_heads * hd), dtype),
        "wo": _dense_init(ko, (cfg.n_heads * hd, d), dtype),
    }


def _gqa_scores_full(q, k, n_heads, n_kv):
    """q (B,S,H,D), k (B,T,Hkv,D) -> scores (B,H,S,T) with GQA broadcast."""
    group = n_heads // n_kv
    B, S, _, D = q.shape
    T = k.shape[1]
    qg = q.reshape(B, S, n_kv, group, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                   preferred_element_type=jnp.float32)
    return s.reshape(B, n_kv * group, S, T)


def attention_forward(cfg: ModelConfig, p: Params, x: jax.Array, *,
                      positions: Optional[jax.Array] = None,
                      causal: bool = True,
                      kv_x: Optional[jax.Array] = None,
                      use_rope: bool = True,
                      prefix_len: int = 0,
                      return_kv: bool = False,
                      past_kv: Optional[Tuple[jax.Array, jax.Array]] = None):
    """Full-sequence (self or cross) attention.

    x (B,S,d). kv_x: source of K/V for cross-attention (B,T,d); None = self.
    positions: absolute positions (B,S) for RoPE; default arange. No
    rotation where ``use_rope`` is off or ``cfg.rope_theta`` is 0 (NoPE).
    prefix_len: number of leading tokens (e.g. vision tokens) that every
    query may attend to bidirectionally (VLM prefix attention).
    return_kv: also return the (roped) K and V, e.g. for cache building.
    past_kv: (pk, pv) of shape (B, P, Hkv, D) — already-roped K/V of a
    prefix (chunked prefill / prefix caching); queries sit at absolute
    positions P.. and attend to the past causally.
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    src = x if kv_x is None else kv_x
    past_len = past_kv[0].shape[1] if past_kv is not None else 0
    q = dense(x, p["wq"]).reshape(B, S, H, hd)
    k = dense(src, p["wk"]).reshape(B, src.shape[1], Hkv, hd)
    v = dense(src, p["wv"]).reshape(B, src.shape[1], Hkv, hd)
    if use_rope and cfg.rope_theta and kv_x is None:
        if positions is None:
            positions = jnp.broadcast_to(
                past_len + jnp.arange(S)[None, :], (B, S))
        cos, sin = rope_angles(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    new_k, new_v = k, v
    if past_kv is not None:
        k = jnp.concatenate([past_kv[0].astype(k.dtype), k], axis=1)
        v = jnp.concatenate([past_kv[1].astype(v.dtype), v], axis=1)
    T = k.shape[1]
    if kv_x is None and S >= 2048:
        # long sequences: flash attention, which never materializes the
        # (S, T) score matrix. The Pallas kernel on TPU where it applies (a
        # whole causal prompt), the jnp online-softmax form elsewhere.
        from repro.kernels import ops as _kops
        if causal and past_kv is None and not cfg.sliding_window and \
                not prefix_len and _kops.default_backend() == "tpu":
            o = _kops.flash_prefill(q.transpose(0, 2, 1, 3),
                                    k.transpose(0, 2, 1, 3),
                                    v.transpose(0, 2, 1, 3),
                                    scale=cfg.attn_scale)
            out = o.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
        else:
            out = _flash_attention_ref(q, k, v, causal=causal,
                                       window=cfg.sliding_window,
                                       prefix_len=prefix_len,
                                       n_heads=H, n_kv=Hkv,
                                       scale=cfg.attn_scale,
                                       q_offset=past_len)
        out = dense(out, p["wo"])
        if return_kv:
            return out, new_k, new_v
        return out
    scores = _gqa_scores_full(q, k, H, Hkv) * cfg.attn_scale   # (B,H,S,T)
    if causal and kv_x is None:
        qi = past_len + jnp.arange(S)[:, None]
        ki = jnp.arange(T)[None, :]
        mask = ki <= qi
        if cfg.sliding_window > 0:
            mask &= ki > qi - cfg.sliding_window
        if prefix_len > 0:
            mask |= ki < prefix_len
        scores = jnp.where(mask[None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)       # (B,H,S,T)
    group = H // Hkv
    wv = w.reshape(B, Hkv, group, S, T)
    o = jnp.einsum("bkgst,btkd->bskgd", wv, v).reshape(B, S, H * hd)
    out = dense(o, p["wo"])
    if return_kv:
        return out, new_k, new_v   # new tokens only (past excluded)
    return out


def fit_cache(ks: jax.Array, vs: jax.Array, total: int, clen: int,
              window: int, batch: int):
    """Fit stacked prefill K/V (L,B,total,Hkv,D) into a cache of length
    ``clen``: keep the last clen positions (ring-rolled when windowed) or
    right-pad with empty slots when clen > total. Returns (k, v, slot_pos)."""
    B = batch
    if clen > total:
        pad = clen - total
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        sp = jnp.concatenate([jnp.arange(total), jnp.full((pad,), -1)])
        sp = jnp.broadcast_to(sp[None, :], (B, clen)).astype(jnp.int32)
        return ks, vs, sp
    start = total - clen
    ks = ks[:, :, -clen:]
    vs = vs[:, :, -clen:]
    sp = jnp.broadcast_to(jnp.arange(start, start + clen)[None, :],
                          (B, clen)).astype(jnp.int32)
    if window:
        shift = start % clen
        ks = jnp.roll(ks, shift, axis=2)
        vs = jnp.roll(vs, shift, axis=2)
        sp = jnp.roll(sp, shift, axis=1)
    return ks, vs, sp


def init_kv_cache(cfg: ModelConfig, batch: int, cache_len: int, n_layers: int,
                  dtype) -> Dict[str, jax.Array]:
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((n_layers, batch, cache_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((n_layers, batch, cache_len, cfg.n_kv_heads, hd), dtype),
    }


def attention_decode(cfg: ModelConfig, p: Params, x: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, slot_pos: jax.Array,
                     *, use_rope: bool = True) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against a (possibly ring-buffer) KV cache.

    x (B,1,d); k_cache/v_cache (B,S,Hkv,D); pos (B,) absolute position of the
    new token; slot_pos (B,S) absolute position held by each slot (-1 empty,
    already including this step's write). Returns (out (B,1,d), k, v).
    No rotation where ``use_rope`` is off or ``cfg.rope_theta`` is 0.
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    S = k_cache.shape[1]
    q = dense(x, p["wq"]).reshape(B, 1, H, hd)
    k = dense(x, p["wk"]).reshape(B, 1, Hkv, hd)
    v = dense(x, p["wv"]).reshape(B, 1, Hkv, hd)
    if use_rope and cfg.rope_theta:
        cos, sin = rope_angles(pos[:, None], hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    slot = pos % S if cfg.sliding_window > 0 else pos
    # the new K/V go in by a select over the cache, not a scatter:
    # an undonated step writes the whole cache anew either way, and the
    # select keeps the layout the cache is stored in, where a scatter made
    # XLA copy the cache to another layout and back
    here = (jnp.arange(S)[None, :] == slot[:, None])[:, :, None, None]
    k_cache = jnp.where(here, k, k_cache)
    v_cache = jnp.where(here, v, v_cache)
    # scores over the whole cache, masked by slot validity. f32 via the
    # dot's accumulator (preferred_element_type), NOT by casting inputs:
    # an input cast materializes an f32 copy of the whole K cache per
    # layer, as large as the decode step's other memory traffic.
    group = H // Hkv
    qg = q.reshape(B, Hkv, group, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * cfg.attn_scale
    valid = slot_pos >= 0
    if cfg.sliding_window > 0:
        valid &= slot_pos[:, :] > (pos[:, None] - cfg.sliding_window)
    valid &= slot_pos <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bkgs,bskd->bkgd", w, v_cache).reshape(B, 1, H * hd)
    return dense(o, p["wo"]), k_cache, v_cache


def cross_attention_decode(cfg: ModelConfig, p: Params, x: jax.Array,
                           k_cache: jax.Array, v_cache: jax.Array) -> jax.Array:
    """One-token cross-attention against a fixed encoder KV (B,T,Hkv,D)."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    q = dense(x, p["wq"]).reshape(B, 1, H, hd)
    group = H // Hkv
    qg = q.reshape(B, Hkv, group, hd)
    s = jnp.einsum("bkgd,btkd->bkgt", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / math.sqrt(hd)
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bkgt,btkd->bkgd", w, v_cache).reshape(B, 1, H * hd)
    return dense(o, p["wo"])


def _flash_attention_ref(q, k, v, *, causal: bool, window: int,
                         prefix_len: int, n_heads: int, n_kv: int,
                         scale: float, block: int = 1024,
                         q_offset: int = 0) -> jax.Array:
    """Chunked online-softmax attention (pure JAX; the lax.scan analogue of
    kernels/flash_prefill). Never materializes the (S, T) score matrix in
    HBM: at long sequences that matrix would be most of the memory
    traffic. q (B,S,H,D); k/v (B,T,Hkv,D) (already roped); scores are
    multiplied by ``scale``. Returns (B,S,H*D)."""
    B, S, H, D = q.shape
    T = k.shape[1]
    g = n_heads // n_kv
    block = min(block, T)
    pad = (-T) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nkb = (T + pad) // block
    qg = q.reshape(B, S, n_kv, g, D).transpose(0, 2, 3, 1, 4)  # (B,kv,g,S,D)
    qpos = q_offset + jnp.arange(S)

    def body(carry, j):
        m, l, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(k, j * block, block, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, j * block, block, 1)
        s = jnp.einsum("bkgsd,btkd->bkgst", qg, ks,
                       preferred_element_type=jnp.float32) * scale
        kpos = j * block + jnp.arange(block)
        mask = kpos[None, :] < T
        if causal:
            cm = kpos[None, :] <= qpos[:, None]
            if window > 0:
                cm &= kpos[None, :] > qpos[:, None] - window
            if prefix_len > 0:
                cm |= kpos[None, :] < prefix_len
            mask = mask & cm
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(vs.dtype), vs).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, n_kv, g, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, n_kv, g, S), jnp.float32)
    a0 = jnp.zeros((B, n_kv, g, S, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nkb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H * D).astype(q.dtype)


# ---------------------------------------------------------------- FFN


def init_ffn(cfg: ModelConfig, key, dtype, d_ff: Optional[int] = None,
             d_model: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.ffn == "swiglu":
        return {"w_gate": _dense_init(k1, (d, f), dtype),
                "w_up": _dense_init(k3, (d, f), dtype),
                "w_down": _dense_init(k2, (f, d), dtype)}
    return {"w_up": _dense_init(k1, (d, f), dtype),
            "w_down": _dense_init(k2, (f, d), dtype)}


def ffn_forward(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.ffn == "swiglu":
        return dense(jax.nn.silu(dense(x, p["w_gate"])) * dense(x, p["w_up"]),
                     p["w_down"])
    return dense(jax.nn.gelu(dense(x, p["w_up"])), p["w_down"])


# ---------------------------------------------------------------- embeddings


def init_embeddings(cfg: ModelConfig, key, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"tok": _dense_init(k1, (cfg.vocab_size, cfg.d_model), dtype, scale=0.02)}
    if not cfg.tie_embeddings:
        p["head"] = _dense_init(k2, (cfg.d_model, cfg.vocab_size), dtype)
    return p


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return p["tok"][tokens]


def unembed(p: Params, x: jax.Array) -> jax.Array:
    if "head" in p:
        return dense(x, p["head"])
    return x @ p["tok"].T
