"""The diffusion serve CLI reports failure through its exit code: a bucket
whose requests end not-ok, or a sharded run with nothing to shard over,
exits non-zero instead of printing and returning 0."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.launch import serve as serve_cli

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(args, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one CPU device
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--mode", "diffusion",
         "--smoke", "--requests", "2", "--nfe", "4", "--seq", "8",
         "--bucket-sizes", "2", *args],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO)


@pytest.mark.parametrize("cond_value,ok", [(0.1, True), (np.nan, False)])
def test_failing_bucket_exits_nonzero(tmp_path, cond_value, ok):
    """A NaN conditioning prompt poisons every lane of the bucket; the
    guard fails its requests and the CLI exits non-zero. The same run
    with a finite prompt exits 0."""
    cond = tmp_path / "cond.npy"
    np.save(cond, np.full((8,), cond_value, np.float32))
    r = run_cli(["--cond-file", str(cond), "--guidance-scale", "2.0",
                 "--guard-interval", "1"], tmp_path)
    if ok:
        assert r.returncode == 0, r.stdout + r.stderr
    else:
        assert r.returncode != 0, r.stdout
        assert "failed_numerics" in r.stdout
        assert "requests ended not-ok" in r.stderr


def test_sharded_on_one_device_is_an_error(tmp_path):
    r = run_cli(["--sharded"], tmp_path)
    assert r.returncode != 0
    assert "needs >= 2 devices" in r.stderr


def test_compile_cache_path_follows_env(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is configured;
    without it the cache sits at the fixed ``<repo>/.jax_cache``."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert serve_cli.use_compile_cache() == "/elsewhere"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert serve_cli.use_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
