"""Without a TPU, or without the program, a run prints no result and
exits non-zero."""
import os
import shutil
import subprocess
import sys

from conftest import BENCH

CMD = [sys.executable, "bench/run.py", "--workload", "pen-8x2.gate.open",
       "--seed", str(2**31 + 17), "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(BENCH.parent)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_are_refused(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".run", ".jax_cache"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
