"""``bench/run.py`` as the driver runs it: no result without the chip."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]


def run(cwd: Path, *args, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=120)


ARGS = ("--workload", "products-sage.full", "--seed", str(2**31 + 9), "--seconds", "1",
        "--trace", "0")


def test_refuses_a_cpu_naming_the_platform():
    p = run(REPO, *ARGS)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert p.stdout == ""


@pytest.mark.parametrize("knob", ["AUTOSAGE_HW_PROFILE", "AUTOSAGE_REPLAY_ONLY"])
def test_refuses_an_identity_knob(knob):
    p = run(REPO, *ARGS, **{knob: "1"})
    assert p.returncode != 0 and knob in p.stderr and p.stdout == ""


def test_refuses_an_unknown_workload():
    p = run(REPO, "--workload", "no-such.cell", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and "no workload" in p.stderr and p.stdout == ""


def test_no_result_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for d in ("bench", "tests/bench"):
        shutil.copytree(REPO / d, tmp_path / d, ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path, *ARGS)
    assert p.returncode != 0 and p.stdout == ""
