"""Paged-attention decode kernel (TPU Pallas).

TPU-native adaptation of vLLM's paged attention (DESIGN.md §3): the KV pool
is a dense HBM array ``(num_pages, page_size, n_kv_heads, head_dim)``; the
grid iterates ``(batch, kv_head, page)`` and the BlockSpec index_map reads
the per-sequence block table (scalar-prefetched) to DMA exactly one page's
K/V tile into VMEM per step. A flash-style online-softmax accumulator lives
in VMEM scratch; the output is written on the final page iteration.

Page tiles are (page_size, head_dim) = multiples of the (8,128) TPU tile as
long as page_size % 8 == 0 and head_dim % 128 == 0 (we use 16/128 defaults).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_PAGE_SIZE = 16
_NEG_INF = -1e30


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_s, l_s, acc_s, *, page_size: int, scale: float):
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    length = len_ref[b]
    base = p * page_size

    @pl.when(base < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (group, hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)      # (page, hd)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        idx = base + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        s = jnp.where(idx < length, s, _NEG_INF)       # (group, page)

        m_prev = m_s[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)
        l_new = alpha * l_s[:, :1] + jnp.sum(pexp, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(p == n_pages - 1)
    def _finalize():
        l = jnp.maximum(l_s[:, :1], 1e-30)
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array,
                    *, page_size: int = DEFAULT_PAGE_SIZE,
                    interpret: bool = False) -> jax.Array:
    """Decode attention over paged KV.

    q            (B, n_kv, group, head_dim)  — one query token per sequence
    k_pool/v_pool(num_pages, page_size, n_kv, head_dim)
    block_tables (B, max_pages) int32        — page ids per sequence
    lengths      (B,) int32                  — tokens in each sequence's KV
    returns      (B, n_kv, group, head_dim)
    """
    B, n_kv, group, hd = q.shape
    max_pages = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_kv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), lambda b, h, p, bt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, page_size, 1, hd),
                         lambda b, h, p, bt, ln: (bt[b, p], 0, h, 0)),
            pl.BlockSpec((1, page_size, 1, hd),
                         lambda b, h, p, bt, ln: (bt[b, p], 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd),
                               lambda b, h, p, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, lengths, q, k_pool, v_pool)
