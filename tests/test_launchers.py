"""CLI launcher smoke tests (subprocess): train.py and serve.py run
end-to-end on reduced configs."""
import importlib.util
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(args, timeout=420):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-m", *args], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


def test_train_cli():
    out = _run(["repro.launch.train", "--arch", "olmo-1b", "--steps", "6",
                "--batch", "2", "--seq", "32", "--log-every", "5"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "loss" in out.stdout
    # first-vs-last line present
    assert "->" in out.stdout


def test_serve_cli():
    out = _run(["repro.launch.serve", "--arch", "olmo-1b", "--requests",
                "6", "--max-slots", "4", "--max-len", "96"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "served 6/6 requests" in out.stdout


def test_dryrun_cli_smoke():
    """One small dry-run pair through the CLI (512 fake devices)."""
    out = _run(["repro.launch.dryrun", "--arch", "olmo-1b", "--shape",
                "decode_32k", "--no-unroll"], timeout=580)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "1/1 pairs lowered+compiled" in out.stdout


_CACHE_DIR = ("import jax; from repro.launch.compile_cache import "
              "use_compile_cache; print(use_compile_cache()); "
              "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_location(tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the
    cache sits at a fixed path inside the checkout."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", _CACHE_DIR], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [str(tmp_path)] * 2
    env.pop("JAX_COMPILATION_CACHE_DIR")
    out = subprocess.run([sys.executable, "-c", _CACHE_DIR], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    repo_cache = os.path.join(os.path.realpath(ROOT), ".jax_cache")
    assert out.stdout.split() == [repo_cache] * 2


def test_benchmark_runner_fails_when_a_module_raises(monkeypatch, capsys):
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmarks", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    def boom():
        raise RuntimeError("boom")

    monkeypatch.setitem(sys.modules, "benchmarks.boom",
                        types.SimpleNamespace(run=boom))
    monkeypatch.setattr(run, "MODULES", ["boom"])
    monkeypatch.setattr(sys, "argv", ["run"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (0, None)
    assert "boom/ERROR,0,RuntimeError=boom" in capsys.readouterr().out
