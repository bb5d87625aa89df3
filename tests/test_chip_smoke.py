"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, its
dispatch matrices are the documented ones, and its phases pass end to
end on 4 forced CPU devices with the kernels interpreted (subprocess).
Also the one compile-cache switch every entry point calls."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
CHILD = os.path.join(REPO, "tests", "multidevice", "child_chip_smoke.py")


def _cpu_env(child_env, **extra):
    env = dict(child_env, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return dict(env, **extra)


def _assert_refused(res):
    assert res.returncode != 0, res.stdout
    assert '"ok"' not in res.stdout, res.stdout


def test_refuses_without_tpu(child_env):
    res = subprocess.run([sys.executable, SMOKE], env=_cpu_env(child_env),
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    _assert_refused(res)
    assert "no TPU" in res.stderr


def test_refuses_outside_a_checkout(child_env, tmp_path):
    shutil.copy(SMOKE, tmp_path)
    env = _cpu_env(child_env)
    env.pop("PYTHONPATH", None)
    res = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    _assert_refused(res)


@pytest.mark.parametrize("model,top_k,p", [("mixtral-8x7b", 2, 4),
                                           ("deepseek-moe-16b", 6, 4)])
def test_dispatch_matrix(model, top_k, p):
    S = chip_smoke.dispatch_matrix(model, p, 4096)
    assert S.shape == (p, p)
    assert (S.sum(axis=1) == 4096 * top_k).all()
    assert (S == chip_smoke.dispatch_matrix(model, p, 4096)).all()
    assert len(set(S.sum(axis=0).tolist())) == p  # skewed expert loads


def test_rehearsal_on_four_cpu_devices(child_env):
    res = subprocess.run([sys.executable, CHILD], env=_cpu_env(child_env),
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    assert "ALL CHIP-SMOKE REHEARSAL CHECKS PASSED" in res.stdout
    assert "interpreted, all bitwise equal" in res.stdout


_CACHE_PROBE = """
import json, os, jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
path = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir,
                  "files": sorted(os.listdir(path))}))
"""


def test_compile_cache_uses_the_given_directory(child_env, tmp_path):
    cache = tmp_path / "cache"
    res = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE],
        env=_cpu_env(child_env, JAX_COMPILATION_CACHE_DIR=str(cache)),
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["path"] == out["config"] == str(cache)
    assert out["files"], "nothing was cached in JAX_COMPILATION_CACHE_DIR"
    assert sorted(os.listdir(tmp_path)) == ["cache"]


def test_compile_cache_defaults_to_the_checkout(child_env):
    from repro.compile_cache import DEFAULT_DIR

    probe = ("import jax\n"
             "from repro.compile_cache import enable_compile_cache\n"
             "print(enable_compile_cache(), "
             "jax.config.jax_compilation_cache_dir)\n")
    res = subprocess.run([sys.executable, "-c", probe],
                         env=_cpu_env(child_env), capture_output=True,
                         text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    path, config = res.stdout.split()[-2:]
    assert path == config == os.path.join(REPO, ".jax_cache")
    assert str(DEFAULT_DIR) == path
