"""Each plain reference against the program's own prefill and decode step,
at the program's smoke sizes on the CPU, where both compute float32 in
full: they must agree to rounding. The control (the reference in bfloat16)
must not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, runner, smoke
from repro.models import Model


@pytest.mark.parametrize("config", ["mamba2-1.3b", "olmo-1b"])
def test_reference_matches_program_prefill_and_decode(config):
    cell = smoke.cell(config, "long_prompt")
    ref, adapter = runner.family(cell)
    conf = cell.conf
    w = runner.make_weights(ref, conf, 3)
    model = Model(adapter.model_config(config, conf))
    params = adapter.program_params(w)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 400, (1, 70)),
                       jnp.int32)
    want = ref.logits(conf, w, ref.hidden(conf, w, toks))[0]
    scale = float(jnp.max(jnp.abs(want)))
    lg, cache = model.prefill(params, {"tokens": toks[:, :50]},
                              dtype=jnp.float32)
    errs = [float(jnp.max(jnp.abs(lg[0] - want[49])))]
    pool = model.init_cache(1, 96, dtype=jnp.float32)
    for k, v in cache.items():      # into the pool as the engine lays it
        if k == "slot_pos":
            v = jnp.pad(v, ((0, 0), (0, 96 - v.shape[1])),
                        constant_values=-1)
        elif v.ndim >= 3 and v.shape[2] != pool[k].shape[2]:
            v = pool[k].at[:, :, :v.shape[2]].set(v)
        pool[k] = v
    for t in range(50, 70):
        lg, pool = model.decode_step(params, toks[:, t:t + 1], pool)
        errs.append(float(jnp.max(jnp.abs(lg[0] - want[t]))))
    assert max(errs) <= 1e-5 * scale

    ctl = ref.logits(conf, w, ref.hidden(conf, w, toks, control=True),
                     control=True)
    assert float(jnp.max(check._rel_err(ctl[0], want))) > \
        cell.limits["logit_err"]["limit"]


@pytest.mark.parametrize("config", ["mamba2-1.3b", "olmo-1b"])
def test_logit_readings_of_greedy_tokens_and_of_the_control(config):
    """Greedy tokens with the float32 reference's own logits read no error
    and no mismatch; the control reads above the smoke cells' limit; a
    served token off the argmax is a mismatch, and a row of logits off
    the reference's is an error."""
    cell = smoke.cell(config, "long_prompt")
    ref, _ = runner.family(cell)
    w = runner.make_weights(ref, cell.conf, 5)
    rng = np.random.default_rng(1)
    limit = cell.limits["logit_err"]["limit"]
    # one program for every step: a causal model's position t does not see
    # the zeros after it
    hidden = jax.jit(lambda w, t: ref.hidden(cell.conf, w, t))
    seqs = []
    for n in (40, 70):
        prompt = rng.integers(0, 400, n).astype(np.int32)
        toks = np.zeros((1, 136), np.int32)
        toks[0, :n] = prompt
        rows = []
        for t in range(n, n + 61):
            h = hidden(w, jnp.asarray(toks))[:, t - 1]
            rows.append(np.asarray(ref.logits(cell.conf, w, h)[0]))
            toks[0, t] = int(np.argmax(rows[-1]))
        seqs.append((prompt, toks[0, n:n + 60].copy(), np.stack(rows)))
    got = check.readings(ref, cell.conf, w, seqs, control=True)
    assert got["positions"] == 122
    assert got["logit_err"] <= 1e-5
    assert got["token_mismatches"] == 0
    assert got["control_logit_err"] > limit

    prompt, served, rows = seqs[0]
    served = served.copy()
    served[7] = (served[7] + 1) % 400
    got = check.readings(ref, cell.conf, w, [(prompt, served, rows)])
    assert got["token_mismatches"] >= 1
    rows = rows.copy()
    rows[3] = np.roll(rows[3], 1)
    got = check.readings(ref, cell.conf, w, [seqs[1][:2] + (rows,)])
    assert got["logit_err"] > limit


def test_mamba2_blocked_recurrence_equals_the_sequential_one():
    from reference import mamba2
    ks = jax.random.split(jax.random.key(0), 5)
    b, s, H, P, N = 2, 150, 3, 4, 8       # several blocks and a padded tail
    x = jax.random.normal(ks[0], (b, s, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, H)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (b, s, N))
    C = jax.random.normal(ks[4], (b, s, N))
    with jax.default_matmul_precision("highest"):
        want = mamba2.ssd_sequential(x, dt, A, B, C)
        got = mamba2.ssd_blocked(x, dt, A, B, C, block=32)
    assert float(jnp.max(jnp.abs(got - want))) <= \
        1e-5 * float(jnp.max(jnp.abs(want)))
