"""``bench/run.py`` prints no result and exits non-zero without a TPU, and
in a directory that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys

from bench.lib import registry

ARGS = ["--workload", "snb_s1.read_point", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    proc = _run(str(registry.REPO))
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(registry.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(registry.REPO / "BENCHMARK.json", tmp_path)
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
