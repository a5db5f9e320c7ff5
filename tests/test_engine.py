"""Real continuous-batching engine: e2e serving, preemption, KV restore."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.serving.engine import Engine
from repro.serving.request import (RequestState, RequestType, make_batch,
                                   make_interactive)


@pytest.fixture(scope="module")
def engine_cfg():
    return get_smoke_config("granite-8b")


def _drain(eng, reqs, max_steps=300):
    steps = 0
    while (eng.waiting or eng.n_active) and steps < max_steps:
        eng.step()
        steps += 1
    return steps


def test_serves_all_requests(engine_cfg):
    eng = Engine(engine_cfg, max_slots=4, max_len=96, dtype=jnp.float32)
    reqs = [make_interactive(8 + i, 6 + i) for i in range(6)]
    for r in reqs:
        eng.submit(r)
    _drain(eng, reqs)
    for r in reqs:
        assert r.state == RequestState.FINISHED
        assert r.tokens_generated >= r.output_len
        assert r.first_token_time is not None
        assert r.finish_time >= r.first_token_time


def test_max_batch_size_respected(engine_cfg):
    eng = Engine(engine_cfg, max_slots=4, max_len=64, max_batch_size=2,
                 dtype=jnp.float32)
    for i in range(4):
        eng.submit(make_interactive(8, 30))
    eng.step()
    assert eng.n_active <= 2


def test_interactive_preempts_batch(engine_cfg):
    eng = Engine(engine_cfg, max_slots=2, max_len=96, dtype=jnp.float32)
    b1 = make_batch(8, 60)
    b2 = make_batch(8, 60)
    eng.submit(b1)
    eng.submit(b2)
    eng.step()
    assert eng.n_active == 2
    inter = make_interactive(8, 4)
    eng.submit(inter)
    stats = eng.step()
    assert len(stats.preempted) == 1
    victim = stats.preempted[0]
    assert victim.state == RequestState.PREEMPTED
    assert victim.saved_kv is not None
    assert inter.state in (RequestState.RUNNING, RequestState.FINISHED)
    # resubmit the victim: must resume from saved KV (no re-prefill -> its
    # first_token_time is preserved and generation continues)
    tokens_before = victim.tokens_generated
    eng.submit(victim)
    _drain(eng, [victim])
    assert victim.state == RequestState.FINISHED
    assert victim.tokens_generated >= victim.output_len
    assert victim.tokens_generated >= tokens_before
    assert victim.saved_kv is None


def test_throughput_metric_positive(engine_cfg):
    eng = Engine(engine_cfg, max_slots=4, max_len=64, dtype=jnp.float32)
    for i in range(3):
        eng.submit(make_interactive(8, 20))
    for _ in range(10):
        eng.step()
    assert eng.throughput() > 0


FAMILIES = {"ssm": "mamba2-1.3b", "dense": "granite-8b",
            "moe": "qwen2-moe-a2.7b", "hybrid": "zamba2-2.7b",
            "audio": "whisper-base", "vlm": "internvl2-2b"}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_engine_prefill_matches_eager_model_prefill(family):
    """The engine's jitted prefill computes what an eager ``Model.prefill``
    does on the same params and prompt: the logits and every cache leaf,
    within float32 rounding."""
    cfg = get_smoke_config(FAMILIES[family])
    assert cfg.arch_type == family
    eng = Engine(cfg, max_slots=2, max_len=64, dtype=jnp.float32)
    req = make_interactive(24, 4)
    req.prompt_tokens = np.random.default_rng(11).integers(
        0, cfg.vocab_size, 24, dtype=np.int32)
    logits, cache = eng._prefill(req)
    want_logits, want_cache = eng.model.prefill(
        eng.params, eng._prompt_batch(req), dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               rtol=1e-5, atol=1e-5)
    assert sorted(cache) == sorted(want_cache)
    for k in cache:
        np.testing.assert_allclose(np.asarray(cache[k]),
                                   np.asarray(want_cache[k]),
                                   rtol=1e-5, atol=1e-5, err_msg=k)


def test_each_slot_decodes_its_own_greedy_token(engine_cfg):
    """Every active slot's next decode input is the argmax of its own row of
    the step before, whichever slots are busy and whoever left."""
    eng = Engine(engine_cfg, max_slots=4, max_len=96, dtype=jnp.float32)
    seen = []
    decode = eng._decode

    def recording(params, tokens, pool):
        slots = [s.request for s in eng.slots]
        logits, pool = decode(params, tokens, pool)
        seen.append((slots, np.asarray(tokens)[:, 0], np.asarray(logits)))
        return logits, pool

    eng._decode = recording
    for i in range(5):
        eng.submit(make_interactive(8 + 3 * i, 4 + 5 * i))
    _drain(eng, [])
    checked = 0
    for (before, _, logits), (after, toks, _) in zip(seen, seen[1:]):
        for j, (a, b) in enumerate(zip(before, after)):
            if a is not None and a is b:
                assert toks[j] == logits[j].argmax(), (j, a.req_id)
                checked += 1
    assert checked > 20


def test_request_stops_where_the_pool_ends(engine_cfg):
    """A request that asks for more tokens than its slot holds finishes
    when its cache reaches ``max_len``."""
    eng = Engine(engine_cfg, max_slots=2, max_len=32, dtype=jnp.float32)
    long, short = make_interactive(20, 100), make_interactive(8, 3)
    eng.submit(long)
    eng.submit(short)
    _drain(eng, [long, short])
    assert long.state == short.state == RequestState.FINISHED
    assert short.tokens_generated == 3
    assert long.tokens_generated == 32 - 1 - 20 + 1
