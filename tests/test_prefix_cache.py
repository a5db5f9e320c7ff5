"""Engine-level prefix caching + chunked prefill (Fig. 11 knobs, real)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.serving.engine import Engine
from repro.serving.prefix_cache import PrefixCache
from repro.serving.request import RequestState, make_interactive


def test_prefix_cache_lookup_longest():
    pc = PrefixCache(max_entries=4)
    pc.store([1, 2, 3], "c3")
    pc.store([1, 2, 3, 4, 5], "c5")
    cache, n = pc.lookup([1, 2, 3, 4, 5, 6, 7])
    assert cache == "c5" and n == 5
    cache, n = pc.lookup([1, 2, 3, 4])        # c3 is the longest STRICT prefix
    assert cache == "c3" and n == 3
    cache, n = pc.lookup([9, 9])
    assert cache is None and n == 0
    assert pc.hits == 2 and pc.misses == 1


def test_prefix_cache_lru_eviction():
    pc = PrefixCache(max_entries=2)
    pc.store([1], "a")
    pc.store([2], "b")
    pc.store([3], "c")
    assert len(pc) == 2
    assert pc.lookup([1, 0])[0] is None       # evicted
    assert pc.lookup([3, 0])[0] == "c"


def _run_engine(eng, reqs, max_steps=200):
    for r in reqs:
        eng.submit(r)
    steps = 0
    while (eng.waiting or eng.n_active) and steps < max_steps:
        eng.step()
        steps += 1
    return steps


def test_engine_prefix_hit_and_correctness():
    cfg = get_smoke_config("granite-8b")
    shared = np.arange(10, 26, dtype=np.int32) % cfg.vocab_size

    def mk(extra):
        r = make_interactive(16 + len(extra), 6)
        r.prompt_tokens = np.concatenate([shared, np.asarray(extra, np.int32)])
        return r

    # engine WITH prefix caching
    eng = Engine(cfg, max_slots=2, max_len=64, dtype=jnp.float32,
                 prefix_cache_entries=8)
    reqs = [mk([1, 2, 3]), mk([4, 5, 6]), mk([7, 8, 9])]
    # serialize so the first prompt is cached before the others arrive
    _run_engine(eng, reqs[:1])
    _run_engine(eng, reqs[1:])
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert eng.prefix_cache.hits >= 1

    # identical workload WITHOUT caching must produce the same tokens
    eng2 = Engine(cfg, max_slots=2, max_len=64, dtype=jnp.float32)
    reqs2 = [mk([1, 2, 3]), mk([4, 5, 6]), mk([7, 8, 9])]
    _run_engine(eng2, reqs2[:1])
    _run_engine(eng2, reqs2[1:])
    for a, b in zip(reqs, reqs2):
        assert a.tokens_generated == b.tokens_generated


def test_engine_chunked_prefill():
    cfg = get_smoke_config("granite-8b")
    eng = Engine(cfg, max_slots=2, max_len=96, dtype=jnp.float32,
                 prefill_chunk=8)
    r = make_interactive(29, 5)   # 29 tokens -> chunks 8+8+8+5
    _run_engine(eng, [r])
    assert r.state == RequestState.FINISHED
    assert r.tokens_generated >= r.output_len


def test_engine_prefix_cache_serves_the_eager_tokens(served_tokens):
    """With the prefix cache on, the jitted prefill (a past cache sliced
    to the shared prefix, then the suffix) serves the tokens the eager
    ``Model.prefill`` served, and the cache is hit."""
    cfg = get_smoke_config("granite-8b")
    shared = np.arange(10, 26, dtype=np.int32) % cfg.vocab_size
    extras = ([1, 2, 3], [4, 5, 6, 7], [8, 9])

    def serve(eager):
        eng = Engine(cfg, max_slots=2, max_len=64, dtype=jnp.float32,
                     prefix_cache_entries=8)
        reqs = []
        for extra in extras:
            r = make_interactive(16 + len(extra), 5)
            r.prompt_tokens = np.concatenate(
                [shared, np.asarray(extra, np.int32)])
            reqs.append(r)
        tokens = served_tokens(eng, reqs, eager=eager)
        assert eng.prefix_cache.hits == 2
        return tokens

    jitted = serve(eager=False)
    assert all(len(t) == 5 for t in jitted)
    assert jitted == serve(eager=True)
