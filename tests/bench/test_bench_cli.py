"""``bench/run.py`` refuses to measure where it cannot: it exits non-zero
and prints nothing on standard output on a machine without a TPU, and in a
directory that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys

import benchtiny

ARGS = ["--workload", "phi3.fed4.dp", "--seed", "4294967303",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    p = _run(benchtiny.ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(benchtiny.ROOT / "BENCHMARK.json", tmp_path)
    for d in ("bench", "tests/bench"):
        shutil.copytree(benchtiny.ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
