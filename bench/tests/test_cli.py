"""The command refuses to run without a TPU, and without the program."""
import json
import os
import shutil
import subprocess
import sys

import run

ARGS = ["--workload", "gotoh.batch", "--seed", "5", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_refuses_the_cpu():
    p = _run(run.ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
