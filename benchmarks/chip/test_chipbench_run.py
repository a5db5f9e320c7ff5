"""Whole runs of tiny cells on the CPU, past the harness's look for a chip:
a sound run is correct, and a run with the timed path broken underneath is
not, for each fault a serving cell can have. And the command itself, which
refuses to print a result without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from chipbench import runner, smoke
from repro.models import Model
from repro.serving import engine as engine_mod

ROOT = runner.ROOT
SECONDS = 8.0
CACHE_KEYS = ("jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(scope="module", autouse=True)
def compile_cache(tmp_path_factory):
    """As ``run.py`` does: every program in a persistent cache, so that a
    prefill in the window loads its program instead of compiling it (each
    eager prefill re-lowers its layer scan)."""
    before = {k: getattr(jax.config, k) for k in CACHE_KEYS}
    for k, v in zip(CACHE_KEYS, (str(tmp_path_factory.mktemp("jc")), 0, 0)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _run(cell, seed=11):
    return runner.run(cell, seed, SECONDS, False, t_start=time.monotonic(),
                      require_tpu=False)


@pytest.mark.parametrize("config", ["mamba2-1.3b", "olmo-1b"])
def test_sound_run_is_correct(config):
    out = _run(smoke.cell(config, "long_prompt"))
    assert out["correct"], out
    assert out["checks"]["positions_compared"]["value"] >= 20
    assert out["checks"]["token_mismatches"]["value"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"itl_p50_ms", "itl_p98_ms", "setup_s"}


def _state_unchanged(step):
    def broken(self, params, tokens, cache):
        logits, _ = step(self, params, tokens, cache)
        return logits, cache
    return broken


def _half_batch(step):
    def broken(self, params, tokens, cache):
        logits, cache = step(self, params, tokens, cache)
        half = logits.shape[0] // 2
        return jnp.concatenate([logits[:half], logits[:half]]), cache
    return broken


def _token_altered(step):
    def broken(self, params, tokens, cache):
        logits, cache = step(self, params, tokens, cache)
        return jnp.roll(logits, 1, axis=-1), cache
    return broken


class _ArgmaxOff:
    """``jnp`` as the engine sees it, with every token it picks off by
    one: the logits are the model's, the served tokens are not."""

    def __getattr__(self, name):
        return getattr(jnp, name)

    @staticmethod
    def argmax(x, *a, **k):
        return (jnp.argmax(x, *a, **k) + 1) % x.shape[-1]


def _failed_checks(out):
    c = out["checks"]
    return [n for n in ("logit_err", "token_mismatches")
            if c[n]["value"] > c[n]["limit"]]


# the exchange between chips has no fault here: the replicas of a cell
# share nothing, each serves its own requests on its own chip
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered, None])
def test_broken_decode_step_is_not_correct(fault, monkeypatch):
    if fault is None:       # the token altered after the model, where picked
        monkeypatch.setattr(engine_mod, "jnp", _ArgmaxOff())
    else:
        monkeypatch.setattr(Model, "decode_step", fault(Model.decode_step))
    # arrivals fast enough to fill every slot
    out = _run(smoke.cell("mamba2-1.3b", "long_prompt", rate=6.0))
    assert not out["correct"], out["checks"]
    assert out["checks"]["positions_compared"]["value"] >= 20
    assert _failed_checks(out) == (["token_mismatches"] if fault is None
                                   else ["logit_err"]), out["checks"]


def test_preempted_requests_are_checked():
    """The engine feeds a restored slot the token 0 in place of the one it
    produced last: the check reads that request as wrong (a served token
    off the program's own argmax), and a request that was never preempted
    as right."""
    import numpy as np

    from chipbench import check, serve
    from repro.serving.engine import Engine
    from repro.serving.request import make_batch, make_interactive

    cell = smoke.cell("mamba2-1.3b", "chat_mixed")
    ref, adapter = runner.family(cell)
    w = runner.make_weights(ref, cell.conf, 3)
    eng = Engine(adapter.model_config(cell.config, cell.conf),
                 params=adapter.program_params(w), max_slots=2, max_len=256,
                 dtype=jnp.float32)
    rec = serve.Recorder()
    serve.instrument(eng, 0, rec)
    rng = np.random.default_rng(0)
    reqs = [make_batch(64, 24), make_batch(64, 24), make_interactive(64, 4)]
    for r in reqs:
        r.prompt_tokens = rng.integers(0, 500, 64, dtype=np.int32)
    eng.submit(reqs[0])
    eng.submit(reqs[1])
    eng.step()
    eng.submit(reqs[2])          # both slots busy: a batch request goes
    for _ in range(60):
        for victim in eng.step().preempted:
            eng.submit(victim)
    kept, restored = sorted(reqs[:2], key=lambda r: r.preemptions)
    assert (kept.preemptions, restored.preemptions) == (0, 1)
    assert all(r.tokens_generated >= r.output_len for r in reqs)
    served = check.served(rec.decode_inputs, rec.prefill_logits,
                          [kept, restored])
    limit = cell.limits["logit_err"]["limit"]
    for r, wrong in ((kept, False), (restored, True)):
        toks, rows = served[r.req_id]
        got = check.readings(ref, cell.conf, w, [(r.prompt_tokens, toks,
                                                  rows)])
        assert got["positions"] == r.output_len
        assert got["logit_err"] <= limit
        assert (got["token_mismatches"] > 0) == wrong, got


def _command(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mamba2-1.3b.long_prompt", "--seed", "1", "--seconds", "1",
         "--trace", "0", *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=240)


def test_command_without_a_tpu_prints_no_result():
    p = _command(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_command_without_the_program_prints_no_result(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
