"""Mamba2 (SSD — state-space duality) layers. [arXiv:2405.21060]

Implements the chunked SSD algorithm in pure jnp (this is also the oracle
the Pallas ``ssd_scan`` kernel is validated against), a recurrent one-token
decode step, and the full block (in_proj -> conv -> SSD -> gated norm ->
out_proj) used by the ``ssm`` and ``hybrid`` architectures.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.layers import _dense_init, rms_norm

Params = Dict[str, Any]


# ------------------------------------------------------------- SSD core


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, chunk: int,
                h0: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Chunked state-space-duality scan (Mamba2 Listing 1, jnp).

    x  (b, s, h, p)   per-head inputs
    dt (b, s, h)      softplus'd step sizes
    A  (h,)           negative decay rates
    B  (b, s, n)      input projections (single group, broadcast over heads)
    C  (b, s, n)      output projections
    h0 optional initial state (b, h, p, n)

    Returns (y (b,s,h,p), final_state (b,h,p,n)).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    if s % chunk:
        # pad to a chunk multiple: dt=0 makes padded steps identity
        # (decay exp(0)=1, zero input), so the final state is unaffected.
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        y, h_final = ssd_chunked(x, dt, A, B, C, chunk, h0=h0)
        return y[:, :s], h_final
    nc = s // chunk

    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = B.reshape(b, nc, chunk, n)
    Cc = C.reshape(b, nc, chunk, n)

    dA = dtc * A  # (b,nc,cs,h), negative
    dA_cum = jnp.cumsum(dA, axis=2)

    # --- intra-chunk (quadratic attention-like term)
    # L[i,j] = exp(dA_cum[i] - dA_cum[j]) for i >= j else 0
    li = dA_cum[:, :, :, None, :]      # (b,nc,cs,1,h)
    lj = dA_cum[:, :, None, :, :]      # (b,nc,1,cs,h)
    mask = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
    # mask the EXPONENT, not the result: for i<j the exponent is positive
    # and exp overflows to inf, which poisons gradients through where().
    arg = jnp.where(mask[None, None, :, :, None], li - lj, -jnp.inf)
    Lmat = jnp.exp(arg)
    scores = jnp.einsum("bzin,bzjn->bzij", Cc.astype(jnp.float32),
                        Bc.astype(jnp.float32))
    # (b,nc,i,j) x (b,nc,i,j,h) x dt_j -> weight per head
    w = scores[..., None] * Lmat * dtc[:, :, None, :, :]
    y_diag = jnp.einsum("bzijh,bzjhp->bzihp", w, xc.astype(jnp.float32))

    # --- per-chunk final states
    decay_to_end = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)   # (b,nc,cs,h)
    states = jnp.einsum("bzjn,bzjh,bzjhp->bzhpn",
                        Bc.astype(jnp.float32),
                        (decay_to_end * dtc).astype(jnp.float32),
                        xc.astype(jnp.float32))             # (b,nc,h,p,n)

    # --- inter-chunk recurrence: h_{z} entering chunk z
    chunk_decay = jnp.exp(dA_cum[:, :, -1, :])               # (b,nc,h)
    h_init = jnp.zeros((b, h, p, n), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def scan_fn(h_prev, inp):
        st, dec = inp  # (b,h,p,n), (b,h)
        h_new = dec[:, :, None, None] * h_prev + st
        return h_new, h_prev

    states_t = jnp.moveaxis(states, 1, 0)        # (nc,b,h,p,n)
    decay_t = jnp.moveaxis(chunk_decay, 1, 0)    # (nc,b,h)
    h_final, h_entering = jax.lax.scan(scan_fn, h_init, (states_t, decay_t))
    h_entering = jnp.moveaxis(h_entering, 0, 1)  # (b,nc,h,p,n)

    # --- inter-chunk output: decayed initial state of each chunk
    y_off = jnp.einsum("bzin,bzih,bzhpn->bzihp",
                       Cc.astype(jnp.float32),
                       jnp.exp(dA_cum),
                       h_entering)

    y = (y_diag + y_off).reshape(b, s, h, p).astype(x.dtype)
    return y, h_final


def ssd_decode_step(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                    C: jax.Array, h: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One recurrent step. x (b,h,p), dt (b,h), B/C (b,n), h (b,h,p,n)."""
    dA = jnp.exp(dt * A)                                     # (b,h)
    hf = h.astype(jnp.float32)
    upd = (dt[:, :, None] * x.astype(jnp.float32))[..., None] * \
        B.astype(jnp.float32)[:, None, None, :]              # (b,h,p,n)
    h_new = dA[:, :, None, None] * hf + upd
    y = jnp.einsum("bhpn,bn->bhp", h_new, C.astype(jnp.float32))
    return y.astype(x.dtype), h_new


# ------------------------------------------------------------- Mamba2 block


# the block's weights fed only to ``layers.dense``
DOT_WEIGHTS = ("w_in", "w_out")


def init_mamba_layer(cfg: ModelConfig, key, dtype) -> Params:
    d = cfg.d_model
    di = cfg.d_inner
    N = cfg.ssm.state_dim
    H = cfg.n_ssm_heads
    conv_ch = di + 2 * N
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        # projects to [z (di), x (di), B (N), C (N), dt (H)]
        "w_in": _dense_init(k1, (d, 2 * di + 2 * N + H), dtype),
        "conv_w": _dense_init(k2, (cfg.ssm.conv_width, conv_ch), dtype,
                              scale=1.0 / math.sqrt(cfg.ssm.conv_width)),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm_w": jnp.ones((di,), dtype),
        "w_out": _dense_init(k3, (di, d), dtype),
        "rms_w": jnp.ones((d,), dtype),   # pre-norm
    }


def _split_proj(cfg: ModelConfig, proj: jax.Array):
    di, N, H = cfg.d_inner, cfg.ssm.state_dim, cfg.n_ssm_heads
    z = proj[..., :di]
    xBC = proj[..., di:di + di + 2 * N]
    dt = proj[..., di + di + 2 * N:]
    return z, xBC, dt


def _causal_conv(xBC: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv1d. xBC (b,s,ch), w (width,ch)."""
    width = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1], :] * w[i] for i in range(width))
    return jax.nn.silu(out + b)


def _residual(cfg: ModelConfig, out: jax.Array) -> jax.Array:
    """The block's output as added to the residual stream: scaled by the
    muP ``residual_multiplier`` where the config has one."""
    if cfg.residual_multiplier != 1.0:
        return out * cfg.residual_multiplier
    return out


def mamba_forward(cfg: ModelConfig, p: Params, x: jax.Array,
                  h0: Optional[jax.Array] = None,
                  conv0: Optional[jax.Array] = None):
    """Full-sequence Mamba2 block (pre-norm, mixer, residual add).
    x (b,s,d) -> (y, final_ssm_state, conv_state)."""
    b, s, d = x.shape
    di, N, H = cfg.d_inner, cfg.ssm.state_dim, cfg.n_ssm_heads
    P = cfg.ssm.head_dim
    hid = rms_norm(x, p["rms_w"])
    proj = L.dense(hid, p["w_in"])
    z, xBC, dt_raw = _split_proj(cfg, proj)
    if conv0 is not None:
        xBC_ext = jnp.concatenate([conv0.astype(xBC.dtype), xBC], axis=1)
        conv_out = _causal_conv(xBC_ext, p["conv_w"], p["conv_b"])[:, conv0.shape[1]:]
    else:
        conv_out = _causal_conv(xBC, p["conv_w"], p["conv_b"])
    xs = conv_out[..., :di].reshape(b, s, H, P)
    B = conv_out[..., di:di + N]
    C = conv_out[..., di + N:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    # routed through kernels.ops: Pallas ssd_scan on TPU, the jnp oracle
    # (ssd_chunked below) elsewhere
    from repro.kernels import ops as _kops
    y, h_final = _kops.ssd_scan(xs, dt, A, B, C, h0,
                                chunk=cfg.ssm.chunk_size)
    y = y + p["D"][None, None, :, None].astype(y.dtype) * xs
    y = y.reshape(b, s, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm_w"])
    out = _residual(cfg, L.dense(y, p["w_out"]))
    conv_state = xBC[:, -(cfg.ssm.conv_width - 1):, :]
    return x + out, h_final, conv_state


def mamba_decode(cfg: ModelConfig, p: Params, x: jax.Array,
                 h: jax.Array, conv_state: jax.Array):
    """One-token step. x (b,1,d); h (b,H,P,N); conv_state (b,width-1,ch)."""
    b = x.shape[0]
    di, N, H = cfg.d_inner, cfg.ssm.state_dim, cfg.n_ssm_heads
    P = cfg.ssm.head_dim
    hid = rms_norm(x, p["rms_w"])
    proj = L.dense(hid, p["w_in"])
    z, xBC, dt_raw = _split_proj(cfg, proj)
    window = jnp.concatenate([conv_state.astype(xBC.dtype), xBC], axis=1)
    w = p["conv_w"]
    conv_out = jax.nn.silu(
        jnp.einsum("bwc,wc->bc", window, w) + p["conv_b"])[:, None, :]
    xs = conv_out[..., :di].reshape(b, H, P)
    B = conv_out[:, 0, di:di + N]
    C = conv_out[:, 0, di + N:]
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, h_new = ssd_decode_step(xs, dt, A, B, C, h)
    y = y + p["D"][None, :, None].astype(y.dtype) * xs
    y = y.reshape(b, 1, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm_w"])
    out = _residual(cfg, L.dense(y, p["w_out"]))
    new_conv = window[:, 1:, :]
    return x + out, h_new, new_conv
