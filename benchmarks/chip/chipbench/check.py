"""Whether what the timed path served is the model's.

After the window closes, a sample of the requests it finished (``sample``)
is run through the plain float32 reference as prompt + served tokens, and
two numbers are read on it:

- ``logit_err``: at every position where the program produced logits for
  a sampled request (the prefill's last position, then each decode step
  through the slot pool), the distance between the program's logits and
  the reference's, relative to the reference's (L2 over the vocabulary);
  the run's number is the worst position. The float32 engine, whose
  matmuls take bfloat16 operands at JAX's default precision, reads a few
  percent; the control, the reference computed in bfloat16
  (``reference/precision.py``), reads it at the same positions
  (``calibrate.py``).
- ``token_mismatches``: served tokens that are not the argmax of the
  program's own logits at the position before (greedy serving reads 0).
  Together with ``logit_err`` it holds the served stream to greedy
  decoding of logits that lie near the reference's.

The served tokens are the decode step's inputs, and the program's logits
the outputs of its prefill and decode steps, recorded by the benchmark's
wrappers: a request of n output tokens shows n - 1 served tokens (the
last one produced is never fed back) and n rows of logits.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 512          # logits are taken this many positions at a time
PAD_LEN = 1024      # the reference's batch is padded to this many positions
PAD_BATCH = 4       # and to this many sequences, so few programs are built


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also past 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def sample(finished: list, served_on: dict, slot_of: dict, seed: int,
           max_n: int, want_tokens: int) -> list:
    """Finished requests to compare: the one with the most served tokens,
    one that was preempted and restored (where any finished), the one that
    sat in the highest slot, one from each engine that finished any, then
    others drawn from the seed until ``want_tokens`` served tokens or
    ``max_n`` requests."""
    pool = sorted((r for r in finished if r.output_len >= 2),
                  key=lambda r: r.req_id)
    if not pool:
        return []
    rng = np.random.default_rng([seed, 17])
    order = [pool[i] for i in rng.permutation(len(pool))]
    picked = [max(pool, key=lambda r: (r.output_len, -r.req_id))]
    restored = [r for r in order if r.preemptions and r not in picked]
    picked += restored[:1]
    high = max(order, key=lambda r: slot_of.get(r.req_id, -1))
    if high not in picked:
        picked.append(high)
    for eng in sorted({served_on.get(r.req_id) for r in pool}
                      - {None}):
        if not any(served_on.get(r.req_id) == eng for r in picked):
            picked.append(next(r for r in order
                               if served_on.get(r.req_id) == eng))
    for r in order:
        if len(picked) >= max_n or \
                sum(p.output_len - 1 for p in picked) >= want_tokens:
            break
        if r not in picked:
            picked.append(r)
    return picked


def highest_slots(decode_inputs: list) -> dict:
    """req_id -> the highest slot index the request was decoded in."""
    out = {}
    for _, slots, _, _ in decode_inputs:
        for j, r in enumerate(slots):
            if r is not None and out.get(r.req_id, -1) < j:
                out[r.req_id] = j
    return out


def served(decode_inputs: list, prefill_logits: dict, reqs: list) -> dict:
    """req_id -> (the decode inputs fed for the request, in order; the
    program's logits rows for it: its prefill's, then one per decode step)
    on the host."""
    want = {id(r): r for r in reqs}
    toks, rows = {r.req_id: [] for r in reqs}, {}
    for r in reqs:
        lg = prefill_logits.get(r.req_id)
        rows[r.req_id] = [] if lg is None else [lg[0]]
    for _, slots, tok, lg in decode_inputs:
        for j, s in enumerate(slots):
            if id(s) in want:
                toks[s.req_id].append(tok[j, 0])
                rows[s.req_id].append(lg[j])
    host = jax.device_get((toks, rows))
    return {rid: (np.asarray(host[0][rid], np.int32).reshape(-1),
                  np.stack(host[1][rid]).astype(np.float32)
                  if host[1][rid] else np.zeros((0, 0), np.float32))
            for rid in toks}


@functools.lru_cache(maxsize=8)
def _hidden(ref, conf_json: str, control: bool):
    return jax.jit(functools.partial(ref.hidden, json.loads(conf_json),
                                     control=control))


def _batch(seqs):
    """Pad (prompt, served) pairs into one (b, s) token batch. Padding
    follows each sequence, so a causal model's earlier positions do not
    see it."""
    lens = [len(p) + len(t) for p, t, _ in seqs]
    s = -(-max(lens) // PAD_LEN) * PAD_LEN
    b = -(-len(seqs) // PAD_BATCH) * PAD_BATCH
    toks = np.zeros((b, s), np.int32)
    for i, (p, t, _) in enumerate(seqs):
        full = np.concatenate([p, t])
        toks[i, :len(full)] = full
    return toks


def _rel_err(got, want):
    """Per row: |got - want| / |want|, L2 over the last axis."""
    return jnp.linalg.norm(got - want, axis=-1) / \
        jnp.linalg.norm(want, axis=-1)


def readings(ref, conf: dict, weights, seqs: list,
             control: bool = False) -> dict:
    """The check's numbers over ``seqs``, each (prompt, served tokens,
    program logits rows) with one row more than served tokens: the worst
    relative logit error, the served tokens that are not the argmax of
    the program's row before them, the positions compared, and, with
    ``control``, the control's worst relative logit error at the same
    positions."""
    seqs = [(p, t, lg) for p, t, lg in seqs if len(lg) == len(t) + 1]
    key = json.dumps(conf, sort_keys=True)
    toks = jnp.asarray(_batch(seqs))
    rows, cols = [], []
    for i, (p, t, _) in enumerate(seqs):
        rows += [i] * (len(t) + 1)
        cols += list(range(len(p) - 1, len(p) + len(t)))
    at = (np.asarray(rows), np.asarray(cols))
    prog = np.concatenate([lg for _, _, lg in seqs])
    mismatches = sum(int(np.sum(np.argmax(lg[:-1], -1) != t))
                     for _, t, lg in seqs)
    h = _hidden(ref, key, False)(weights, toks)[at]
    hc = _hidden(ref, key, True)(weights, toks)[at] if control else None
    err, err_c = [], []
    for lo in range(0, len(prog), ROWS):
        want = ref.logits(conf, weights, h[lo:lo + ROWS])
        err.append(_rel_err(jnp.asarray(prog[lo:lo + ROWS]), want))
        if control:
            err_c.append(_rel_err(
                ref.logits(conf, weights, hc[lo:lo + ROWS], control=True),
                want))
    out = {"logit_err": float(jnp.max(jnp.concatenate(err))),
           "token_mismatches": mismatches, "positions": int(len(prog))}
    if control:
        out["control_logit_err"] = float(jnp.max(jnp.concatenate(err_c)))
    return out
