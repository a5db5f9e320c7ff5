"""Pure-jnp oracles for every kernel in this package.

These are the correctness references the Pallas kernels are validated
against (interpret=True on CPU), and the jittable fallback path ``ops.py``
uses on hosts without a TPU.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.ssm import ssd_chunked as _ssd_chunked_model

_NEG_INF = -1e30


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        block_tables: jax.Array, lengths: jax.Array) -> jax.Array:
    """Oracle for kernels.paged_attention.paged_attention.

    q (B, n_kv, group, D); pools (P, page, n_kv, D); block_tables (B, max_pages);
    lengths (B,). Returns (B, n_kv, group, D).
    """
    B, n_kv, group, D = q.shape
    page = k_pool.shape[1]
    max_pages = block_tables.shape[1]
    S = max_pages * page
    # gather pages -> (B, S, n_kv, D)
    k = k_pool[block_tables].reshape(B, S, n_kv, D)
    v = v_pool[block_tables].reshape(B, S, n_kv, D)
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", w, v.astype(jnp.float32))
    return o.astype(q.dtype)


def flash_prefill_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True,
                      scale: float | None = None) -> jax.Array:
    """Oracle for kernels.flash_prefill. q (B,H,S,D); k/v (B,Hkv,S,D);
    scores times ``scale`` (None: 1/sqrt(D))."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    qg = q.reshape(B, Hkv, group, S, D)
    s = jnp.einsum("bkgsd,bktd->bkgst", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None, None], s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,bktd->bkgsd", w, v.astype(jnp.float32))
    return o.reshape(B, H, S, D).astype(q.dtype)


def ssd_scan_ref(x, dt, A, B, C, h0=None, *, chunk: int = 256):
    """Oracle for kernels.ssd_scan — reuses the model-layer SSD (itself
    validated against the sequential recurrence in tests)."""
    return _ssd_chunked_model(x, dt, A, B, C, chunk, h0=h0)


def ssd_sequential_ref(x, dt, A, B, C):
    """Fully sequential SSM recurrence — ground truth for both the chunked
    model implementation and the Pallas kernel.

    x (b,s,h,p); dt (b,s,h); A (h,); B (b,s,n); C (b,s,n).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]

    def step(hstate, inp):
        xt, dtt, Bt, Ct = inp  # (b,h,p),(b,h),(b,n),(b,n)
        dA = jnp.exp(dtt * A)  # (b,h)
        upd = (dtt[:, :, None] * xt)[..., None] * Bt[:, None, None, :]
        hstate = dA[:, :, None, None] * hstate + upd
        y = jnp.einsum("bhpn,bn->bhp", hstate, Ct)
        return hstate, y

    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(B, 1, 0).astype(jnp.float32),
          jnp.moveaxis(C, 1, 0).astype(jnp.float32))
    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    h_final, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h_final
