"""The command refuses to report where it cannot measure."""
import os
import shutil
import subprocess
import sys

from benchmark import run


def _cmd(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "cockroach-register.one-test", "--seed", str(2**31 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    p = _cmd(run.ROOT)
    assert p.returncode == 3 and p.stdout.strip() == "", p.stderr


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    p = _cmd(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == "", p.stderr
