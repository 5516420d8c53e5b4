"""The pieces that bring the encrypted round up on a chip, checked on the
CPU: the persistent compile cache location, the device-worker spawn guard,
the trainer's full-width default, and chip_smoke.py's round function at
SMOKE width (its device check must refuse the CPU)."""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

from repro import configs
from repro.core.ckks import params as ckks_params
from repro.launch import compile_cache, train

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory (and drop the initialized cache)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    cc.reset_cache()


def test_compile_cache_honours_env(monkeypatch, cache_config):
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch,
                                                   cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.enable() == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        ROOT, ".jax_cache")
    # the same path on every call: never a temporary name
    assert compile_cache.enable() == os.path.join(ROOT, ".jax_cache")


def test_sharded_workers_refuse_to_spawn_after_jax_import(tmp_path):
    """This process has imported JAX on the CPU: spawning device-count
    workers from it must be refused, and nothing written."""
    from benchmarks import run

    assert "jax" in sys.modules
    with pytest.raises(RuntimeError, match="already imported JAX"):
        run._run_sharded_workers("benchmarks.agg_sharded", "agg_sharded",
                                 str(tmp_path / "out.json"))
    assert not (tmp_path / "out.json").exists()


def test_trainer_builds_full_config_by_default():
    assert train.parser().parse_args([]).smoke is False
    assert train.parser().parse_args(["--smoke"]).smoke is True
    assert train.parser().parse_args(["--no-smoke"]).smoke is False


def test_chip_smoke_round_at_smoke_width():
    cs = _chip_smoke()
    lines = []
    out = cs.run_round(configs.get_config("qwen1.5-0.5b", smoke=True),
                       ckks_params.make_context(n_poly=256, n_limbs=2,
                                                delta_bits=20),
                       batch=2, seq=16, backend="pallas", say=lines.append)
    assert out["max_err"] <= cs.CKKS_ATOL
    assert out["n_ciphertexts"] > 0 and out["uplink_bytes"] > 0
    assert all(len(v) == 2 for v in out["losses"].values())
    assert any("bit-exact: True" in line for line in lines)


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
