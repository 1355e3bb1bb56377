"""run.py refuses any device but a TPU, and a checkout without the
system, printing no result."""
import os
import shutil
import subprocess
import sys

from conftest import ROOT

CMD = [sys.executable, "benchmarks/chip/run.py", "--workload",
       "deepseek67b.decode", "--seed", str(2**31 + 1), "--seconds", "10",
       "--trace", "0"]


def _env():
    return dict(os.environ, JAX_PLATFORMS="cpu")


def test_refuses_the_cpu():
    p = subprocess.run(CMD, cwd=ROOT, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_fails_without_the_system(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip")
    env = dict(_env(), PYTHONPATH="")
    p = subprocess.run(CMD, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
