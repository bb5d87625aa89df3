"""The command refuses, with no result line, where it cannot measure."""
import os
import shutil
import subprocess
import sys

from chipbench import run

ARGS = ["chipbench/run.py", "--workload", "mixtral-8x7b-ep4.slab",
        "--seed", "1", "--seconds", "1", "--trace", "0"]


def call(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    out = call(run.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = call(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_refuses_an_unknown_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chipbench/run.py",
                          "--workload", "no-such-cell", "--seed", "1",
                          "--seconds", "1"], cwd=run.ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
