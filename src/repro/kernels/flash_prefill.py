"""Causal flash-attention prefill kernel (TPU Pallas).

The attention of a whole prompt, on the served prefill path
(``models.layers.attention_forward`` on TPU): grid (batch, q_head, q_block,
kv_block) with the kv_block axis sequential ("arbitrary") so a flash
online-softmax accumulator can live in VMEM scratch. Blocks above the
causal diagonal are skipped with ``pl.when`` — both the DMA cost model and
the FLOP count see only the lower triangle. GQA is handled by indexing the
KV head as q_head // group in the BlockSpec index_map. Its custom call is
named ``flash_prefill`` in the compiled program and the profiler's trace.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s,
            *, block_q: int, block_k: int, scale: float, causal: bool):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # skip fully-masked blocks above the causal diagonal
    run = (j * block_k <= i * block_q + block_q - 1) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qi = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            ki = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qi >= ki, s, _NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)
        l_s[...] = jnp.broadcast_to(
            alpha * l_s[:, :1] + jnp.sum(pexp, axis=1, keepdims=True), l_s.shape)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_s[:, :1], 1e-30)
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "causal",
                                             "scale", "interpret"))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  block_q: int = 256, block_k: int = 256,
                  causal: bool = True, scale: float | None = None,
                  interpret: bool = False) -> jax.Array:
    """Flash attention. q (B,H,S,D); k/v (B,Hkv,S,D); returns (B,H,S,D).
    Scores are multiplied by ``scale`` (None: 1/sqrt(D))."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    grid = (B, H, S // block_q, S // block_k)

    return pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_k=block_k,
                          scale=scale, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_prefill",
    )(q, k, v)
