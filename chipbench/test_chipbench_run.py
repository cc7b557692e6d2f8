"""The command refuses to run off the chip, or without the program."""

import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
ARGS = ["--workload", "smollm-serial.oasst-poisson", "--seed",
        "3000000001", "--seconds", "5", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py"] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_with_a_reason_when_the_device_is_not_a_tpu():
    p = run(ROOT)
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
