"""Plain reference of Mamba2 (arXiv:2405.21060), written from the paper and
the published ``mamba_ssm`` module defaults, independent of the program.

The SSD layer is the recurrence of the paper's eq. (1):

    h_t = exp(dt_t * A) h_{t-1} + dt_t * x_t B_t^T      (per head, P x N)
    y_t = h_t C_t + D x_t

``ssd_sequential`` runs it one position at a time. ``ssd_blocked`` gives the
same numbers a block of positions at a time (the state carried between
blocks, each block's positions summed in closed form), which the chip runs
in seconds where the sequential loop takes minutes; the CPU tests hold the
two equal.

Block: pre-norm RMSNorm -> in_proj [z | x B C | dt] -> causal depthwise conv
(width d_conv, with bias) and SiLU on x B C -> SSD -> gated RMSNorm
``rmsnorm(y * silu(z))`` -> out_proj, added to the residual. Final RMSNorm,
then the tied embedding as the output head. One B/C group (ngroups 1).

Weights are kept stacked over layers, ``(n_layer, ...)``. Everything is
float32 at HIGHEST matmul precision; with ``control`` it is all bfloat16
(``reference.precision``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import precision


def dims(conf: dict) -> dict:
    s = conf["mamba2_defaults"]
    d = conf["d_model"]
    di = s["expand"] * d
    mult = conf["pad_vocab_size_multiple"]
    return {"d": d, "di": di, "N": s["d_state"], "P": s["headdim"],
            "H": di // s["headdim"], "W": s["d_conv"], "L": conf["n_layer"],
            "V": -(-conf["vocab_size"] // mult) * mult,
            "eps": s["norm_epsilon"], "chunk": s["chunk_size"]}


def init_weights(conf: dict, key: jax.Array) -> dict:
    """Seeded random weights; call under ``jax.jit`` to make them on the
    device in one program."""
    m = dims(conf)
    d, di, N, H, W, L, V = (m[k] for k in "d di N H W L V".split())
    s = conf["mamba2_defaults"]
    ks = iter(jax.random.split(key, 12))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    lo, hi = s["A_init_range"]
    dt = jnp.exp(jax.random.uniform(
        next(ks), (L, H), minval=math.log(s["dt_min"]),
        maxval=math.log(s["dt_max"])))
    return {
        "embedding": normal((V, d), 0.02),
        "norm_f": 1.0 + normal((d,), 0.1),
        "layers": {
            "norm": 1.0 + normal((L, d), 0.1),
            "in_proj": normal((L, d, 2 * di + 2 * N + H), d ** -0.5),
            "conv_w": normal((L, W, di + 2 * N), W ** -0.5),
            "conv_b": normal((L, di + 2 * N), 0.1),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(next(ks), (L, H),
                                                minval=lo, maxval=hi)),
            "D": 1.0 + normal((L, H), 0.1),
            "gate_norm": 1.0 + normal((L, di), 0.1),
            "out_proj": normal((L, di, d), (di * 2 * L) ** -0.5),
        },
    }


def ssd_sequential(x, dt, A, B, C):
    """x (b,s,H,P), dt (b,s,H), A (H,), B and C (b,s,N) -> y (b,s,H,P),
    one position at a time, from a zero state."""
    b, _, H, P = x.shape

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        h = jnp.exp(dt_t * A)[:, :, None, None] * h + \
            (dt_t[:, :, None] * x_t)[..., None] * B_t[:, None, None, :]
        return h, jnp.einsum("bhpn,bn->bhp", h, C_t)

    h0 = jnp.zeros((b, H, P, B.shape[-1]), jnp.float32)
    _, ys = jax.lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0)
                                         for a in (x, dt, B, C)))
    return jnp.moveaxis(ys, 0, 1)


def ssd_blocked(x, dt, A, B, C, block: int = 64):
    """The same recurrence, ``block`` positions at a time. Inside a block,
    with l_t the running sum of dt * A from the block's start,

        y_t = exp(l_t) C_t h_in
              + sum_{u <= t} exp(l_t - l_u) dt_u (C_t . B_u) x_u
        h_out = exp(l_T) h_in + sum_u exp(l_T - l_u) dt_u x_u B_u^T

    and h_out enters the next block. The sequence is padded at its end
    with dt = 0, which changes no earlier position."""
    b, s, H, P = x.shape
    N = B.shape[-1]
    pad = -s % block
    x, dt, B, C = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                   for a in (x, dt, B, C))
    nb = (s + pad) // block
    blocks = tuple(jnp.moveaxis(a.reshape(b, nb, block, *a.shape[2:]), 1, 0)
                   for a in (x, dt, B, C))
    causal = jnp.tril(jnp.ones((block, block), bool))

    def step(h, inp):
        x_k, dt_k, B_k, C_k = inp              # (b,T,H,P) (b,T,H) (b,T,N)
        l = jnp.cumsum(dt_k * A, axis=1)       # (b,T,H)
        diff = l[:, :, None, :] - l[:, None, :, :]          # (b,t,u,H)
        w = jnp.exp(jnp.where(causal[None, :, :, None], diff, -jnp.inf))
        cb = jnp.einsum("btn,bun->btu", C_k, B_k)
        mix = cb[..., None] * w * dt_k[:, None]              # (b,t,u,H)
        y = jnp.einsum("btuh,buhp->bthp", mix, x_k)
        y += jnp.exp(l)[..., None] * jnp.einsum("btn,bhpn->bthp", C_k, h)
        last = l[:, -1:, :]                                  # (b,1,H)
        u = (jnp.exp(last - l) * dt_k)[..., None] * x_k      # (b,u,H,P)
        h = jnp.exp(last[:, 0])[:, :, None, None] * h + \
            jnp.einsum("buhp,bun->bhpn", u, B_k)
        return h, y

    h0 = jnp.zeros((b, H, P, N), x.dtype)
    _, ys = jax.lax.scan(step, h0, blocks)
    return jnp.moveaxis(ys, 0, 1).reshape(b, nb * block, H, P)[:, :s]


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _layer(m, p, x):
    """x (b, s, d) -> x + block(x)."""
    b, s, _ = x.shape
    di, N, H, P, W = m["di"], m["N"], m["H"], m["P"], m["W"]
    zxbcdt = _rmsnorm(x, p["norm"], m["eps"]) @ p["in_proj"]
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * N]
    dt = zxbcdt[..., 2 * di + 2 * N:]
    # causal depthwise conv: out_t = sum_k w_k * xbc_{t - (W-1) + k}
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + s] * p["conv_w"][k] for k in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(b, s, H, P)
    B = xbc[..., di:di + N]
    C = xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                        # (b,s,H)
    A = -jnp.exp(p["A_log"])                                       # (H,)
    y = ssd_blocked(xs, dt, A, B, C) + p["D"][:, None] * xs
    y = _rmsnorm(y.reshape(b, s, di) * jax.nn.silu(z), p["gate_norm"],
                 m["eps"])
    return x + y @ p["out_proj"]


def hidden(conf: dict, w: dict, tokens: jax.Array, control: bool = False):
    """Final-normed hidden states (b, s, d) of ``tokens`` (b, s)."""
    m = dims(conf)
    w = precision.weights(w, control)
    x = w["embedding"][tokens]

    def body(x, p):
        return _layer(m, p, x), None

    with precision.matmuls(control):
        x, _ = jax.lax.scan(body, x, w["layers"])
    return _rmsnorm(x, w["norm_f"], m["eps"])


def logits(conf: dict, w: dict, h: jax.Array, control: bool = False):
    """Tied output head: (..., d) -> (..., V)."""
    return precision.head(h, w["embedding"], control)
