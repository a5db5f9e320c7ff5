"""Unified model API over all architecture families.

``Model(cfg)`` exposes:
  init(key, dtype)                 -> params
  forward(params, batch)           -> (logits, aux_loss)
  loss(params, batch)              -> scalar causal-LM loss (+ MoE aux)
  prefill(params, batch)           -> (last_logits, cache)
  decode_step(params, tokens, cache)-> (logits, cache)
  init_cache(batch, cache_len)     -> zeroed cache pytree
  serving_params(params, bf16_dots)-> params as the serving programs take them
  example_batch(batch, seq, key)   -> random batch with the right modalities

``batch`` is a dict: always ``tokens (B,S) int32``; plus ``frames`` for audio
(stub frame embeddings) and ``vision`` for VLM (stub patch embeddings).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, hybrid, mamba_model, transformer

Params = Dict[str, Any]
Batch = Dict[str, jax.Array]

_FAMILY = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "ssm": mamba_model,
    "hybrid": hybrid,
    "audio": encdec,
}


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self._m = _FAMILY[cfg.arch_type]

    # ------------------------------------------------------------ params
    def init(self, key, dtype=None) -> Params:
        return self._m.init_params(self.cfg, key, dtype=dtype)

    # ------------------------------------------------------------ forward
    def forward(self, params: Params, batch: Batch, *, remat: bool = False):
        kw = {}
        if self.cfg.arch_type == "vlm":
            kw["vision_embeds"] = batch["vision"]
        if self.cfg.arch_type == "audio":
            kw["frames"] = batch["frames"]
        return self._m.forward(self.cfg, params, batch["tokens"],
                               remat=remat, **kw)

    def loss(self, params: Params, batch: Batch, *, remat: bool = False) -> jax.Array:
        logits, aux = self.forward(params, batch, remat=remat)
        tokens = batch["tokens"]
        tgt = tokens[:, 1:]
        lg = logits[:, :-1].astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        true = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask")
        nll = lse - true
        if mask is not None:
            m = mask[:, 1:].astype(jnp.float32)
            return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0) + aux
        return jnp.mean(nll) + aux

    # ------------------------------------------------------------ serving
    def init_cache(self, batch: int, cache_len: int, dtype=None):
        dtype = dtype or jnp.dtype(self.cfg.dtype)
        return self._m.init_cache(self.cfg, batch, cache_len, dtype)

    def prefill(self, params: Params, batch: Batch, *,
                cache_len: Optional[int] = None, dtype=None,
                past_cache=None):
        kw = {}
        if self.cfg.arch_type == "vlm":
            kw["vision_embeds"] = batch["vision"]
        if self.cfg.arch_type == "audio":
            kw["frames"] = batch["frames"]
        if past_cache is not None:
            if self.cfg.arch_type not in ("dense", "moe", "vlm"):
                raise NotImplementedError(
                    "chunked prefill: transformer family only")
            kw["past_cache"] = past_cache
        return self._m.prefill(self.cfg, params, batch["tokens"],
                               cache_len=cache_len, dtype=dtype, **kw)

    def decode_step(self, params: Params, tokens: jax.Array, cache):
        return self._m.decode_step(self.cfg, params, tokens, cache)

    def serving_params(self, params: Params, bf16_dots: bool) -> Params:
        """``params`` as the serving programs take them. With ``bf16_dots``
        (the backend computes a float32 dot at default precision as one
        bfloat16 pass) the family rounds the weights it feeds only to such
        dots to bfloat16 here, once, rather than inside every call. Without
        it, or for a family that names no such weights, ``params`` itself."""
        prepare = getattr(self._m, "serving_params", None) if bf16_dots \
            else None
        return prepare(self.cfg, params) if prepare else params

    # ------------------------------------------------------------ inputs
    def example_batch(self, batch: int, seq: int, key=None,
                      dtype=None) -> Batch:
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.dtype)
        key = key if key is not None else jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(key)
        out: Batch = {"tokens": jax.random.randint(
            k1, (batch, seq), 0, cfg.vocab_size, dtype=jnp.int32)}
        if cfg.arch_type == "audio":
            out["frames"] = jax.random.normal(
                k2, (batch, cfg.enc_seq, cfg.d_model)).astype(dtype)
        if cfg.arch_type == "vlm":
            out["vision"] = jax.random.normal(
                k2, (batch, cfg.n_vision_tokens, cfg.d_model)).astype(dtype)
        return out


def get_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
