"""One general generator for every traffic file under ``traffic/``.

A traffic file is JSON with a list of ``streams``. Each stream is one class
of requests (``interactive`` or ``batch``) and either an open-loop arrival
process (``rate`` in requests/s, ``process`` "poisson" or "gamma" with
``cv``) or a ``backlog`` of requests all due at t=0. Prompt and output
lengths are lognormal (``mu``, ``sigma`` of the log, as in
``repro.sim.workload``: INPUT 4.6/1.0, OUTPUT 5.2/0.9), then rounded up to
a multiple of ``round_up`` and clipped to ``[min, max]``; an output is also
cut so that prompt + output stays under ``max_total``.

The distributions and arrival processes are those of ``repro.sim.workload``
(``_token_lengths``, ``_interarrival``), copied here so that the benchmark
owns its yardstick. One change: draws are stratified. Every seed gets the
same multiset of gaps and of lengths (the distribution's quantiles at
``(i + 0.5) / n``), prompt and output lengths paired alike, in an order
drawn from the seed, so seeds change which request is long and when, not
how much work there is. A stream with ``blocks`` k > 1 orders its requests
and its gaps in k consecutive blocks of the same mix (``_order``), so that
each part of the window, its end too, gets about the same work whatever the
seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


@dataclass
class Arrival:
    due: float              # seconds after the window opens
    prompt_len: int
    output_len: int
    interactive: bool
    tokens: np.ndarray      # prompt token ids (int32)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _order(n: int, blocks: int, rng: np.random.Generator) -> np.ndarray:
    """A seeded order of the ranks 0..n-1. With one block, any order. With
    k blocks, the ranks fall into groups of k neighbours, each group gives
    one rank to each block (which one is drawn from the seed), and the
    blocks follow each other, each in an order drawn from the seed."""
    k = max(1, min(int(blocks), n))
    if k == 1:
        return rng.permutation(n)
    member = np.empty(n, np.int64)
    for lo in range(0, n, k):
        member[lo:lo + k] = rng.permutation(k)[:min(k, n - lo)]
    return np.concatenate([rng.permutation(np.flatnonzero(member == b))
                           for b in range(k)])


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    z = np.array([_NORMAL.inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(spec["mu"] + spec["sigma"] * z)
    step = spec.get("round_up", 1)
    x = np.ceil(x / step) * step
    x = np.clip(x, spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(x)


def _gaps(stream: dict, n: int) -> np.ndarray:
    """The gaps' quantiles, in ascending order."""
    mean = 1.0 / stream["rate"]
    q = _quantiles(n)
    if stream.get("process", "poisson") == "poisson":
        g = -np.log1p(-q) * mean
    else:
        # Gamma with coefficient of variation cv: shape 1/cv^2
        from scipy.stats import gamma
        cv = stream["cv"]
        g = gamma.ppf(q, 1.0 / cv ** 2, scale=mean * cv ** 2)
    return np.asarray(g)


def prompt_lengths(traffic: dict) -> list:
    """Every prompt length the traffic can send (the shapes to warm)."""
    out = set()
    for s in traffic["streams"]:
        p = s["prompt"]
        step = p.get("round_up", 1)
        lo = int(math.ceil(p["min"] / step) * step)
        out.update(range(lo, p["max"] + 1, step))
        out.add(p["min"])
        out.add(p["max"])
    return sorted(out)


def classes(traffic: dict) -> set:
    return {s["class"] for s in traffic["streams"]}


def generate(traffic: dict, seed: int, seconds: float, vocab: int,
             rate_scale: float = 1.0) -> list:
    """Requests due in a window of ``seconds``, sorted by due time.

    ``rate_scale`` multiplies every stream's rate (the knee sweep uses it).
    """
    rng = np.random.default_rng(seed)
    out = []
    for s in traffic["streams"]:
        blocks = s.get("blocks", 1)
        if "backlog" in s:
            n = int(s["backlog"])
            due = np.zeros(n)
        else:
            rate = s["rate"] * rate_scale
            n = max(1, int(math.ceil(rate * seconds)))
            gaps = _gaps(dict(s, rate=rate), n)
            due = np.cumsum(gaps[_order(n, blocks, rng)])
        # prompts and outputs are paired once, the same for every seed, so
        # that the cap on their sum cuts the same outputs; the seed orders
        # the pairs
        fixed = np.random.default_rng(0)
        prompts = _lengths(s["prompt"], n, fixed)
        outputs = _lengths(s["output"], n, fixed)
        cap = s["output"].get("max_total")
        if cap is not None:
            outputs = np.minimum(outputs, cap - prompts)
        rank = np.lexsort((outputs, prompts))
        order = rank[_order(n, blocks, rng)]
        prompts, outputs = prompts[order], outputs[order]
        inter = s["class"] == "interactive"
        for t, p, o in zip(due, prompts, outputs):
            out.append(Arrival(float(t), int(p), int(o), inter,
                               rng.integers(0, vocab, int(p),
                                            dtype=np.int32)))
    out.sort(key=lambda a: a.due)
    return out
