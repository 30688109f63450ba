"""The S04 cell served over a four-chip camera mesh, on four CPU devices,
cut to eight cameras (two per device): the camera mesh as served comes out correct, and with the
exchange between chips left out (each device's all-gather returns its own
cameras, the others' rows zero) it comes out not correct.  Each case runs
in a process of its own, which sees four host devices."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CHIP_DIR = Path(__file__).resolve().parents[1]
ROOT = CHIP_DIR.parents[1]

DRIVE = r"""
import dataclasses, json, sys, time
sys.path[:0] = [sys.argv[2], sys.argv[3]]
import jax
import jax.numpy as jnp
from chipbench import spec
from chipbench.harness import run_cell
from chipbench.serve import CompileClock

if sys.argv[1] == "no_exchange":
    def local_only(x, axis_name, *, axis=0, tiled=False, **kw):
        # what the other chips hold never arrives: their rows stay zero
        n = x.shape[0]
        full = jnp.zeros((n * jax.device_count(),) + x.shape[1:], x.dtype)
        return jax.lax.dynamic_update_slice_in_dim(
            full, x, jax.lax.axis_index(axis_name) * n, 0)
    jax.lax.all_gather = local_only
assert jax.device_count() == 4
# every camera is live: seven of them on other chips than camera 0
SEED = 2 ** 31 + 16759
cell = spec.find_cell("s04-deepstream.fcc", spec.load_benchmark())
cell = dataclasses.replace(cell, chips=4,
                           config={**cell.config, "num_cameras": 8})
result, _ = run_cell(cell, seed=SEED, seconds=0.5, trace=False,
                     clock=CompileClock(), t_start=time.perf_counter())
print(json.dumps(result))
"""


def _drive(case):
    # single-threaded XLA, so the child leaves the other tests' workers
    # their cores
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
                         "--xla_cpu_multi_thread_eigen=false")
    p = subprocess.run([sys.executable, "-c", DRIVE, case, str(ROOT / "src"),
                        str(CHIP_DIR)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case,correct", [("sound", True),
                                          ("no_exchange", False)])
def test_camera_mesh(case, correct):
    result = _drive(case)
    assert result["device"]["count"] == 4
    assert result["correct"] is correct, result["checks"]
