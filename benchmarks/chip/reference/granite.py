"""Plain reference of Granite 4.0-H (``granitemoehybrid``, dense form),
written from the published ``config.json`` and the layer equations of the
Hugging Face ``GraniteMoeHybrid`` model, independent of the program.

Embedding: ``x = embedding[tokens] * embedding_multiplier``. Each layer
``i`` of ``layer_types`` is

    x = x + residual_multiplier * mixer_i(rmsnorm(x))
    x = x + residual_multiplier * mlp_i(rmsnorm(x))

with the mixer a Mamba-2 layer or a GQA attention layer, and the MLP
``down(silu(x W_gate) * (x W_up))``. Final RMSNorm, then the tied
embedding as the output head, the logits divided by ``logits_scaling``.

- Mamba-2 mixer: in_proj to ``[z | x B C | dt]``, causal depthwise conv
  (width ``mamba_d_conv``, with bias) and SiLU on ``x B C``, the SSD
  recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``,
  ``y_t = h_t C_t + D x_t`` (imported from ``reference.mamba2``:
  ``ssd_blocked``, held equal to the sequential recurrence by the CPU
  tests), ``dt = softplus(dt + dt_bias)``, gated RMSNorm
  ``rmsnorm(y * silu(z))``, out_proj. One B/C group.
- Attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` shared K/V heads of ``hidden_size / heads``, no
  positional encoding (``position_embedding_type`` "nope"), scores times
  ``attention_multiplier``, causal, no bias. Computed a block of queries
  at a time, each block with a full softmax over the keys, so that a
  17k-token sequence fits; the sequences of a batch are run one after
  another.

Departures from the published model, none of which changes the numbers:
the MLP's ``input_linear`` ([gate | up], one matrix) is kept as its two
halves; the mixers' weights are stacked by kind and the MLPs' by layer.
The layers are ``layer_types[:num_hidden_layers]``. Weights are random
from the seed: Hugging Face's initial ``A_log = log(1..heads)``, step
sizes drawn as mamba_ssm draws them (``dt`` in [0.001, 0.1], an
assumption: the config gives no range), normals elsewhere.

Everything is float32 at HIGHEST matmul precision; with ``control`` it is
all bfloat16 (``reference.precision``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import precision
from reference.mamba2 import ssd_blocked

Q_BLOCK = 256       # queries per block of the reference's attention
DT_RANGE = (0.001, 0.1)


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    L = conf["num_hidden_layers"]
    types = list(conf["layer_types"][:L])
    di = conf["mamba_expand"] * d
    return {"d": d, "di": di, "N": conf["mamba_d_state"],
            "P": conf["mamba_d_head"], "H": conf["mamba_n_heads"],
            "W": conf["mamba_d_conv"], "L": L, "types": types,
            "n_mamba": types.count("mamba"),
            "n_attn": types.count("attention"),
            "Hq": conf["num_attention_heads"],
            "Hkv": conf["num_key_value_heads"],
            "hd": d // conf["num_attention_heads"],
            "F": conf["shared_intermediate_size"],
            "V": conf["vocab_size"], "eps": conf["rms_norm_eps"],
            "chunk": conf["mamba_chunk_size"]}


def init_weights(conf: dict, key: jax.Array) -> dict:
    """Seeded random weights; call under ``jax.jit`` to make them on the
    device in one program."""
    m = dims(conf)
    d, di, N, H, W, V = (m[k] for k in "d di N H W V".split())
    nm, na, L, F = m["n_mamba"], m["n_attn"], m["L"], m["F"]
    q, kv = m["Hq"] * m["hd"], m["Hkv"] * m["hd"]
    ks = iter(jax.random.split(key, 24))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    dt = jnp.exp(jax.random.uniform(
        next(ks), (nm, H), minval=math.log(DT_RANGE[0]),
        maxval=math.log(DT_RANGE[1])))
    return {
        "embedding": normal((V, d), 0.02),
        "norm_f": 1.0 + normal((d,), 0.1),
        "mamba": {
            "norm": 1.0 + normal((nm, d), 0.1),
            "in_proj": normal((nm, d, 2 * di + 2 * N + H), d ** -0.5),
            "conv_w": normal((nm, W, di + 2 * N), W ** -0.5),
            "conv_b": normal((nm, di + 2 * N), 0.1),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)), (nm, H)),
            "D": 1.0 + normal((nm, H), 0.1),
            "gate_norm": 1.0 + normal((nm, di), 0.1),
            "out_proj": normal((nm, di, d), di ** -0.5),
        },
        "attention": {
            "norm": 1.0 + normal((na, d), 0.1),
            "q": normal((na, d, q), d ** -0.5),
            "k": normal((na, d, kv), d ** -0.5),
            "v": normal((na, d, kv), d ** -0.5),
            "o": normal((na, q, d), q ** -0.5),
        },
        "mlp": {
            "norm": 1.0 + normal((L, d), 0.1),
            "gate": normal((L, d, F), d ** -0.5),
            "up": normal((L, d, F), d ** -0.5),
            "down": normal((L, F, d), F ** -0.5),
        },
    }


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mamba(m, p, x):
    """The Mamba-2 mixer of one sequence: x (s, d) -> (s, d)."""
    s = x.shape[0]
    di, N, H, P, W = m["di"], m["N"], m["H"], m["P"], m["W"]
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:2 * di + 2 * N]
    dt = zxbcdt[:, 2 * di + 2 * N:]
    # causal depthwise conv: out_t = sum_k w_k * xbc_{t - (W-1) + k}
    padded = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    conv = sum(padded[k:k + s] * p["conv_w"][k] for k in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[:, :di].reshape(s, H, P)
    B = xbc[:, di:di + N]
    C = xbc[:, di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                        # (s,H)
    A = -jnp.exp(p["A_log"])                                       # (H,)
    y = ssd_blocked(xs[None], dt[None], A, B[None], C[None])[0]
    y = y + p["D"][:, None] * xs
    y = _rmsnorm(y.reshape(s, di) * jax.nn.silu(z), p["gate_norm"],
                 m["eps"])
    return y @ p["out_proj"]


def _attention(m, p, x, scale):
    """Causal GQA attention of one sequence, no positional encoding:
    x (s, d) -> (s, d), a block of ``Q_BLOCK`` queries at a time."""
    s = x.shape[0]
    Hq, Hkv, hd = m["Hq"], m["Hkv"], m["hd"]
    g = Hq // Hkv
    pad = -s % Q_BLOCK
    q = jnp.pad(x @ p["q"], ((0, pad), (0, 0))).reshape(-1, Q_BLOCK, Hkv, g,
                                                          hd)
    k = (x @ p["k"]).reshape(s, Hkv, hd)
    v = (x @ p["v"]).reshape(s, Hkv, hd)
    keys = jnp.arange(s)

    def block(args):
        i, qb = args                                   # qb (Q, Hkv, g, hd)
        sc = jnp.einsum("qkgd,tkd->kgqt", qb, k) * scale
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(keys[None, :] <= rows[:, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", w, v)

    o = jax.lax.map(block, (jnp.arange(q.shape[0]), q))
    return o.reshape(-1, Hq * hd)[:s] @ p["o"]


def _mlp(p, x):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def _sequence(conf, m, w, tokens):
    """Final-normed hidden states (s, d) of one sequence of tokens (s,)."""
    r, eps = conf["residual_multiplier"], m["eps"]
    x = w["embedding"][tokens] * conf["embedding_multiplier"]
    at = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(m["types"]):
        j = at[kind]
        at[kind] += 1
        p = jax.tree.map(lambda a: a[j], w[kind])
        h = _rmsnorm(x, p["norm"], eps)
        if kind == "mamba":
            x = x + r * _mamba(m, p, h)
        else:
            x = x + r * _attention(m, p, h, conf["attention_multiplier"])
        p = jax.tree.map(lambda a: a[i], w["mlp"])
        x = x + r * _mlp(p, _rmsnorm(x, p["norm"], eps))
    return _rmsnorm(x, w["norm_f"], eps)


def hidden(conf: dict, w: dict, tokens: jax.Array, control: bool = False):
    """Final-normed hidden states (b, s, d) of ``tokens`` (b, s)."""
    if conf["position_embedding_type"] != "nope":
        raise ValueError("the reference computes NoPE attention only")
    m = dims(conf)
    w = precision.weights(w, control)
    with precision.matmuls(control):
        return jax.lax.map(lambda t: _sequence(conf, m, w, t), tokens)


def logits(conf: dict, w: dict, h: jax.Array, control: bool = False):
    """Tied output head, divided by ``logits_scaling``: (..., d) -> (..., V)."""
    return precision.head(h, w["embedding"], control) / conf["logits_scaling"]
