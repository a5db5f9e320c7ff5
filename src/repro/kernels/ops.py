"""Jit'd public wrappers around the Pallas kernels.

On TPU the real kernels run; on CPU hosts (this container) callers either
use ``backend="ref"`` (pure-jnp oracle, fast under jit) or
``backend="interpret"`` (executes the actual kernel body in the Pallas
interpreter — used by the correctness tests).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax

from repro.kernels import ref as _ref
from repro.kernels.flash_prefill import flash_prefill as _flash_kernel
from repro.kernels.paged_attention import paged_attention as _paged_kernel
from repro.kernels.ssd_scan import ssd_scan as _ssd_kernel

Backend = Literal["tpu", "interpret", "ref"]


def default_backend() -> Backend:
    return "tpu" if jax.default_backend() == "tpu" else "ref"


def paged_attention(q, k_pool, v_pool, block_tables, lengths, *,
                    page_size: int = 16, backend: Backend | None = None):
    backend = backend or default_backend()
    if backend == "ref":
        return jax.jit(_ref.paged_attention_ref)(q, k_pool, v_pool,
                                                 block_tables, lengths)
    return _paged_kernel(q, k_pool, v_pool, block_tables, lengths,
                         page_size=page_size,
                         interpret=(backend == "interpret"))


def flash_prefill(q, k, v, *, causal: bool = True, block_q: int = 256,
                  block_k: int = 256, scale: float | None = None,
                  backend: Backend | None = None):
    """q (B,H,S,D); k/v (B,Hkv,S,D) -> (B,H,S,D); scores times ``scale``
    (None: 1/sqrt(D))."""
    backend = backend or default_backend()
    if backend == "ref":
        return jax.jit(functools.partial(_ref.flash_prefill_ref,
                                         causal=causal, scale=scale))(q, k, v)
    s = q.shape[2]
    pad = -s % min(max(block_q, block_k), s)
    if causal and pad:
        # pad the sequence to whole blocks: a padded key lies after every
        # real query, so the causal mask hides it; padded rows are dropped
        import jax.numpy as jnp
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        return flash_prefill(jnp.pad(q, widths), jnp.pad(k, widths),
                             jnp.pad(v, widths), causal=True,
                             block_q=block_q, block_k=block_k, scale=scale,
                             backend=backend)[:, :, :s]
    return _flash_kernel(q, k, v, causal=causal, block_q=block_q,
                         block_k=block_k, scale=scale,
                         interpret=(backend == "interpret"))


def ssd_scan(x, dt, A, B, C, h0=None, *, chunk: int = 256,
             backend: Backend | None = None):
    backend = backend or default_backend()
    if backend == "ref":
        return jax.jit(functools.partial(_ref.ssd_scan_ref, chunk=chunk))(
            x, dt, A, B, C, h0)
    s = x.shape[1]
    if s % chunk:
        # pad to a chunk multiple (dt=0 padded steps are identity; the
        # final state is unaffected — see models/ssm.ssd_chunked)
        import jax.numpy as jnp
        pad = chunk - s % chunk
        y, h = ssd_scan(jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))),
                        jnp.pad(dt, ((0, 0), (0, pad), (0, 0))), A,
                        jnp.pad(B, ((0, 0), (0, pad), (0, 0))),
                        jnp.pad(C, ((0, 0), (0, pad), (0, 0))),
                        h0, chunk=chunk, backend=backend)
        return y[:, :s], h
    return _ssd_kernel(x, dt, A, B, C, h0, chunk=chunk,
                       interpret=(backend == "interpret"))
