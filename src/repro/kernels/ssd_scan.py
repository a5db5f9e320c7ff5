"""Mamba2 SSD chunked-scan kernel (TPU Pallas).

The chunk axis is sequential ("arbitrary") and carries the SSM state
(P, N) in VMEM scratch; per chunk the kernel computes the intra-chunk
quadratic (attention-like) term on the MXU plus the inter-chunk
contribution of the carried state, then updates the state — the same
dataflow as ``repro.models.ssm.ssd_chunked`` (the oracle), but with one
HBM->VMEM DMA per (x, dt, B, C) chunk tile and no (b, nc, cs, cs, h)
intermediate materialized in HBM.

Layout: the kernel runs head-major. ``x`` is tiled as ``(chunk, P)`` blocks
of a ``(b, h, s, P)`` array and ``dt`` as ``(chunk, 1)`` columns of
``(b, h, s, 1)``, so the last two block dimensions always tile (a size-1
head block on a ``(b, s, h, P)`` array does not). ``A`` is a scalar per
head, read from SMEM through scalar prefetch. Mosaic lowers neither
``cumsum`` nor a (1, 1) -> 2-D broadcast, so prefix sums are masked
reductions over a (chunk, chunk) tile and the chunk-total decay is reduced
straight into the column shape it multiplies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(A_ref, x_ref, dt_ref, B_ref, C_ref, h0_ref, y_ref, state_ref,
            h_s, *, chunk: int):
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        h_s[...] = h0_ref[...].astype(jnp.float32)

    A = A_ref[pl.program_id(1)]                      # scalar (SMEM)
    x = x_ref[...].astype(jnp.float32)               # (cs, P)
    dt_col = dt_ref[...].astype(jnp.float32)         # (cs, 1)
    Bm = B_ref[...].astype(jnp.float32)              # (cs, N)
    Cm = C_ref[...].astype(jnp.float32)              # (cs, N)
    P = x.shape[1]

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # the same step sizes as a row: pick the diagonal of the lane broadcast
    dt_row = jnp.sum(jnp.where(ii == jj, dt_col, 0.0), axis=0,
                     keepdims=True)                  # (1, cs)
    dA_col = dt_col * A                              # (cs, 1)
    dA_row = dt_row * A                              # (1, cs)
    # inclusive prefix sums, as a column and as a row
    cum_col = jnp.sum(jnp.where(jj <= ii, dA_row, 0.0), axis=1,
                      keepdims=True)                 # (cs, 1)
    cum_row = jnp.sum(jnp.where(ii <= jj, dA_col, 0.0), axis=0,
                      keepdims=True)                 # (1, cs)

    # intra-chunk: y_diag = ((C B^T) ∘ L ∘ dt_j) x
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    L = jnp.where(ii >= jj, jnp.exp(cum_col - cum_row), 0.0)
    w = scores * L * dt_row
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y_off = exp(dA_cum) * (C h^T);  h (P,N)
    y += jnp.exp(cum_col) * jax.lax.dot_general(
        Cm, h_s[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: h' = exp(dA_total) h + x^T (decay_to_end * dt * B)
    total_col = jnp.sum(jnp.broadcast_to(dA_row, (chunk, chunk)), axis=1,
                        keepdims=True)               # (cs, 1), every row
    total_p = jnp.sum(jnp.broadcast_to(dA_row, (P, chunk)), axis=1,
                      keepdims=True)                 # (P, 1), every row
    decay = jnp.exp(total_col - cum_col)             # (cs, 1)
    h_s[...] = jnp.exp(total_p) * h_s[...] + jax.lax.dot_general(
        x, Bm * (decay * dt_col), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(c == nc - 1)
    def _finalize():
        state_ref[...] = h_s[...].astype(state_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, h0: jax.Array = None, *, chunk: int = 256,
             interpret: bool = False):
    """Chunked SSD scan.

    x (b,s,h,p); dt (b,s,h); A (h,); B (b,s,n); C (b,s,n);
    h0 optional initial state (b,h,p,n)
    -> (y (b,s,h,p), final_state (b,h,p,n))
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)
    sq = pl.squeezed
    xh = jnp.transpose(x, (0, 2, 1, 3))              # (b,h,s,p)
    dth = jnp.transpose(dt, (0, 2, 1))[..., None]    # (b,h,s,1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((sq, sq, chunk, p), lambda bi, hi, ci, a: (bi, hi, ci, 0)),
            pl.BlockSpec((sq, sq, chunk, 1), lambda bi, hi, ci, a: (bi, hi, ci, 0)),
            pl.BlockSpec((sq, chunk, n), lambda bi, hi, ci, a: (bi, ci, 0)),
            pl.BlockSpec((sq, chunk, n), lambda bi, hi, ci, a: (bi, ci, 0)),
            pl.BlockSpec((sq, sq, p, n), lambda bi, hi, ci, a: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((sq, sq, chunk, p), lambda bi, hi, ci, a: (bi, hi, ci, 0)),
            pl.BlockSpec((sq, sq, p, n), lambda bi, hi, ci, a: (bi, hi, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
    )
    yh, state = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), xh, dth, B, C, h0)
    return jnp.transpose(yh, (0, 2, 1, 3)), state
