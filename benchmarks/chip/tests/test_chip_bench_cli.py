"""The measurement command refuses to measure without a TPU, outside a
checkout of the program, and for a cell BENCHMARK.json does not name: a
nonzero exit and no result line."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

CHIP_DIR = Path(__file__).resolve().parents[1]
ROOT = CHIP_DIR.parents[1]


def _run(cwd, *args, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


ARGS = ["--workload", "s01-reducto.fcc", "--seed", str(2 ** 31 + 3),
        "--seconds", "1", "--trace", "0"]


def test_no_tpu_no_result():
    p = _run(ROOT, *ARGS, env_extra={"BENCH_RUN": "1"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_unknown_workload_no_result():
    p = _run(ROOT, "--workload", "nope.soak", "--seed", "1", "--seconds",
             "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "unknown workload" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    p = _run(tmp_path, *ARGS)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no program" in p.stderr


@pytest.mark.parametrize("bad", [["--trace", "2"], ["--seed", "x"]])
def test_bad_arguments_no_result(bad):
    args = list(ARGS)
    i = args.index(bad[0])
    args[i + 1] = bad[1]
    p = _run(ROOT, *args)
    assert p.returncode != 0 and p.stdout.strip() == ""
