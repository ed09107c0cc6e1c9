"""The command itself: it refuses a CPU, and refuses to run without the
program beside it, with nothing on standard output either way."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, REPO

ARGS = ["--workload", "window1k.replay", "--seed", "3000000000",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, script] + ARGS, cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_the_command_refuses_a_cpu():
    p = _run(REPO, os.path.join("benchmark", "run.py"))
    assert p.returncode == 2
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_the_command_fails_without_the_program(tmp_path):
    shutil.copytree(
        BENCH, tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path), os.path.join("benchmark", "run.py"))
    assert p.returncode not in (0, 2)
    assert p.stdout == ""


def test_benchmark_json_names_the_command():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark"]
