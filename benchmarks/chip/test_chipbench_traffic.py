"""The traffic generator: the same requests for the same seed, the same
work in another order for another seed, and every traffic file within its
own limits."""
import json

import numpy as np
import pytest

from chipbench import traffic
from chipbench.runner import BENCH_DIR

FILES = sorted(p.stem for p in (BENCH_DIR / "traffic").glob("*.json"))
BIG_SEED = 2 ** 33 + 5


def _load(name):
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def _key(reqs):
    return [(a.due, a.prompt_len, a.output_len, a.interactive,
             a.tokens.tobytes()) for a in reqs]


@pytest.mark.parametrize("name", FILES)
def test_same_seed_same_requests(name):
    t = _load(name)
    assert _key(traffic.generate(t, BIG_SEED, 30, 50000)) == \
        _key(traffic.generate(t, BIG_SEED, 30, 50000))


@pytest.mark.parametrize("name", FILES)
def test_other_seed_same_work_in_another_order(name):
    t = _load(name)
    a = traffic.generate(t, 1, 30, 50000)
    b = traffic.generate(t, 2, 30, 50000)
    assert _key(a) != _key(b)
    for cls in (True, False):
        assert sorted((r.prompt_len, r.output_len) for r in a
                      if r.interactive == cls) == \
            sorted((r.prompt_len, r.output_len) for r in b
                   if r.interactive == cls)
        gaps = [np.diff([0.0] + [r.due for r in x if r.interactive])
                for x in (a, b)]
        assert sorted(gaps[0]) == pytest.approx(sorted(gaps[1]))


@pytest.mark.parametrize("name", FILES)
def test_lengths_within_the_file_and_warmed(name):
    t = _load(name)
    warm = set(traffic.prompt_lengths(t))
    for r in traffic.generate(t, 3, 51, 50000):
        s = next(s for s in t["streams"]
                 if (s["class"] == "interactive") == r.interactive)
        p, o = s["prompt"], s["output"]
        assert r.prompt_len in warm
        assert p["min"] <= r.prompt_len <= p["max"]
        assert r.prompt_len % p.get("round_up", 1) == 0
        assert o["min"] <= r.output_len <= o["max"]
        assert r.prompt_len + r.output_len <= o.get("max_total", 1 << 30)
        assert len(r.tokens) == r.prompt_len
        assert r.tokens.dtype == np.int32 and r.tokens.max() < 50000
        if "backlog" in s:
            assert r.due == 0.0
        else:
            assert 0.0 < r.due


def test_rate_sets_the_number_of_arrivals():
    t = _load("long_prompt")
    rate = t["streams"][0]["rate"]
    n = len(traffic.generate(t, 4, 40, 50000))
    assert n == int(np.ceil(rate * 40))
    assert len(traffic.generate(t, 4, 40, 50000, rate_scale=2.0)) == \
        int(np.ceil(2 * rate * 40))


@pytest.mark.parametrize("seed", [1, BIG_SEED])
def test_blocks_give_each_part_of_the_window_the_same_mix(seed):
    """With k blocks, each run of n/k consecutive arrivals holds one
    request and one gap from each group of k neighbours in rank."""
    t = _load("long_prompt")
    s = t["streams"][0]
    k = s["blocks"]
    n = 6 * k
    reqs = traffic.generate(t, seed, n / s["rate"], 50000)
    assert len(reqs) == n
    pairs = sorted((r.prompt_len, r.output_len) for r in reqs)
    gaps = np.diff([0.0] + [r.due for r in reqs])
    by_gap = np.sort(gaps)
    for b in range(k):
        block = slice(b * n // k, (b + 1) * n // k)
        mine = sorted((r.prompt_len, r.output_len) for r in reqs[block])
        for j, p in enumerate(mine):
            assert pairs[j * k] <= p <= pairs[j * k + k - 1]
        for j, g in enumerate(np.sort(gaps[block])):
            assert by_gap[j * k] <= g <= by_gap[j * k + k - 1]
