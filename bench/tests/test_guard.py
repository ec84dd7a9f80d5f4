"""The run refuses to measure anywhere but on a chip, and refuses to run
without the program beside it."""
import os
import shutil
import subprocess
import sys

from harness import spec

ROOT = spec.ROOT
ARGS = ["--workload", "qwen3-0.6b.chat", "--seed", "0", "--seconds", "10",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_chip_exits_nonzero_and_names_it():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU found" in r.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
