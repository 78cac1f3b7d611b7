"""``bench/run.py`` refuses to run without a TPU and prints no result."""
import os
import shutil
import subprocess
import sys

from bench_helpers import BENCH, ROOT


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-10k.steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_exits_nonzero_with_only_the_benchmarks_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
