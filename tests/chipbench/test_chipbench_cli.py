"""The command refuses to run, and prints no result, without a TPU or
without the program beside the benchmark's files."""
import os
import shutil
import subprocess
import sys

from benchmarks.chip.harness import ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "dgemm.square16k", "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_with_no_result(tmp_path):
    r = _run(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_exit_nonzero_with_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in ("benchmarks/chip", "tests/chipbench"):
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
