"""Plain reference of OLMo (arXiv:2402.00838) as published for OLMo-1B,
independent of the program.

Block: non-parametric LayerNorm (no weight, no bias) -> causal multi-head
attention with rotary embeddings (rotate-half form, theta ``rope_theta``)
-> residual; non-parametric LayerNorm -> SwiGLU MLP
``down(silu(gate(x)) * up(x))`` -> residual. No biases, no qkv clipping.
Final non-parametric LayerNorm, then the tied embedding as the output head.
Attention is the full (s, s) score matrix, masked causally.

Weights are stacked over layers, ``(num_hidden_layers, ...)``. Everything
is float32 at HIGHEST matmul precision; with ``control`` it is all
bfloat16 (``reference.precision``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import precision

EPS = 1e-5


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return {"d": d, "H": H, "Dh": d // H, "F": conf["intermediate_size"],
            "L": conf["num_hidden_layers"], "V": conf["vocab_size"],
            "theta": conf["rope_theta"]}


def init_weights(conf: dict, key: jax.Array) -> dict:
    """Seeded random weights; call under ``jax.jit`` to make them on the
    device in one program."""
    m = dims(conf)
    d, F, L, V = m["d"], m["F"], m["L"], m["V"]
    ks = iter(jax.random.split(key, 8))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    out = (2 * L) ** -0.5
    return {
        "embedding": normal((V, d), 0.02),
        "layers": {
            "q": normal((L, d, d), d ** -0.5),
            "k": normal((L, d, d), d ** -0.5),
            "v": normal((L, d, d), d ** -0.5),
            "o": normal((L, d, d), d ** -0.5 * out),
            "gate": normal((L, d, F), d ** -0.5),
            "up": normal((L, d, F), d ** -0.5),
            "down": normal((L, F, d), F ** -0.5 * out),
        },
    }


def _layernorm(x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS)


def _rope(x, theta):
    """x (b, s, H, Dh): rotate the two halves of each head."""
    s, Dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang).astype(x.dtype)[None, :, None, :]
    sin = jnp.sin(ang).astype(x.dtype)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m, p, x):
    b, s, d = x.shape
    H, Dh = m["H"], m["Dh"]
    h = _layernorm(x)
    q = _rope((h @ p["q"]).reshape(b, s, H, Dh), m["theta"])
    k = _rope((h @ p["k"]).reshape(b, s, H, Dh), m["theta"])
    v = (h @ p["v"]).reshape(b, s, H, Dh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(Dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, d)
    x = x + o @ p["o"]
    h = _layernorm(x)
    mlp = jax.nn.silu(h @ p["gate"]) * (h @ p["up"])
    return x + mlp @ p["down"]


def hidden(conf: dict, w: dict, tokens: jax.Array, control: bool = False):
    """Final-normed hidden states (b, s, d) of ``tokens`` (b, s)."""
    m = dims(conf)
    w = precision.weights(w, control)
    x = w["embedding"][tokens]

    def body(x, p):
        return _layer(m, p, x), None

    with precision.matmuls(control):
        x, _ = jax.lax.scan(body, x, w["layers"])
    return _layernorm(x)


def logits(conf: dict, w: dict, h: jax.Array, control: bool = False):
    """Tied output head: (..., d) -> (..., V)."""
    return precision.head(h, w["embedding"], control)
