"""The command refuses to run without a chip, and without the program."""

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)     # the command finds the program itself
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            assert "correct" not in json.loads(line)
        except ValueError:
            pass


def test_the_command_refuses_the_cpu():
    proc = _run(ROOT, "--workload", "yi6b.mixed.poisson", "--seed",
                str(2**31 + 3), "--seconds", "5", "--trace", "0")
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    _no_result(proc)


def test_the_command_fails_without_the_program(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, "--workload", "yi6b.mixed.poisson", "--seed", "1",
                "--seconds", "5", "--trace", "0")
    assert proc.returncode != 0
    _no_result(proc)
