"""Chunked prefill + prefix reuse: prefilling a prompt in pieces through
``past_cache`` must be equivalent to one-shot prefill (and to forward)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import get_model

B, S = 2, 48


@pytest.mark.parametrize("arch", ["granite-8b", "qwen2-moe-a2.7b"])
def test_chunked_prefill_matches_oneshot(arch):
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    batch = model.example_batch(B, S, jax.random.PRNGKey(1),
                                dtype=jnp.float32)
    toks = batch["tokens"]

    one_logits, one_cache = model.prefill(params, batch, dtype=jnp.float32)

    # prefill in three chunks: 16 + 16 + 16
    cache = None
    for lo in range(0, S, 16):
        chunk = {"tokens": toks[:, lo:lo + 16]}
        logits, cache = model.prefill(params, chunk, dtype=jnp.float32,
                                      past_cache=cache)

    np.testing.assert_allclose(np.asarray(logits), np.asarray(one_logits),
                               atol=3e-3, rtol=3e-3)
    np.testing.assert_allclose(np.asarray(cache["k"]),
                               np.asarray(one_cache["k"]),
                               atol=3e-3, rtol=3e-3)
    assert int(cache["pos"][0]) == S


def test_prefix_reuse_then_decode():
    """Reuse a cached shared prefix, prefill only the suffix, then decode —
    results must match the from-scratch path (prefix caching semantics)."""
    cfg = get_smoke_config("granite-8b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    prefix = jax.random.randint(key, (1, 24), 0, cfg.vocab_size,
                                dtype=jnp.int32)
    sufa = jax.random.randint(jax.random.PRNGKey(4), (1, 8), 0,
                              cfg.vocab_size, dtype=jnp.int32)

    # cache the prefix once
    _, pcache = model.prefill(params, {"tokens": prefix},
                              dtype=jnp.float32)
    # continue with the suffix from the cached prefix
    la, ca = model.prefill(params, {"tokens": sufa}, dtype=jnp.float32,
                           past_cache=pcache)
    # from-scratch reference
    full = jnp.concatenate([prefix, sufa], axis=1)
    lr, cr = model.prefill(params, {"tokens": full}, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lr),
                               atol=3e-3, rtol=3e-3)

    # decode a few tokens from both caches: must agree
    # (grow room: pad both caches via cache_len on a fresh prefill)
    la2, ca = model.prefill(params, {"tokens": sufa}, dtype=jnp.float32,
                            past_cache=pcache, cache_len=40)
    lr2, cr = model.prefill(params, {"tokens": full}, dtype=jnp.float32,
                            cache_len=40)
    tok = jnp.argmax(la2, -1)[:, None].astype(jnp.int32)
    for _ in range(4):
        da, ca = model.decode_step(params, tok, ca)
        dr, cr = model.decode_step(params, tok, cr)
        np.testing.assert_allclose(np.asarray(da), np.asarray(dr),
                                   atol=5e-3, rtol=5e-3)
        tok = jnp.argmax(da, -1)[:, None].astype(jnp.int32)


@pytest.mark.parametrize("arch", ["granite-8b", "qwen2-moe-a2.7b"])
def test_engine_chunked_prefill_serves_the_eager_tokens(arch, served_tokens):
    """An engine prefilling in chunks through its jitted program (one per
    chunk and past length) serves the tokens it served with an eager
    ``Model.prefill`` per chunk."""
    from repro.serving.engine import Engine
    from repro.serving.request import make_interactive
    cfg = get_smoke_config(arch)
    params = get_model(cfg).init(jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in (29, 21)]   # chunks 8+8+8+5 and 8+8+5

    def serve(eager):
        eng = Engine(cfg, params=params, max_slots=2, max_len=64,
                     dtype=jnp.float32, prefill_chunk=8)
        reqs = [make_interactive(len(p), 6) for p in prompts]
        for r, p in zip(reqs, prompts):
            r.prompt_tokens = p
        return served_tokens(eng, reqs, eager=eager)

    jitted = serve(eager=False)
    assert [len(t) for t in jitted] == [6, 6]
    assert jitted == serve(eager=True)
