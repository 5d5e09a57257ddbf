"""The command's refusals: no TPU, or a checkout without the program."""
import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "set_uniform_r90",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            assert "metrics" not in json.loads(line)
        except ValueError:
            pass


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr
    assert "TPU" in p.stderr
    _no_result(p)


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    _no_result(p)
