"""The precision of the references, and of their control.

The configurations state float32. The reference computes float32 with
every matmul at HIGHEST precision (on the TPU, float32 accuracy from
several MXU passes). The control is the reference one step below what the
configuration states: bfloat16 throughout, weights and activations alike,
its matmuls in the chip's native single pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def weights(w, control: bool):
    """The weights as the reference (float32) or the control (bfloat16)
    computes with them."""
    if not control:
        return w
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16), w)


def matmuls(control: bool):
    """The matmul precision in force inside the reference or the control."""
    return jax.default_matmul_precision("default" if control else "highest")


def head(h, embedding, control: bool):
    """Tied output head (..., d) -> (..., V) logits, in float32."""
    emb = weights(embedding, control)
    with matmuls(control):
        return (h.astype(emb.dtype) @ emb.T).astype(jnp.float32)
