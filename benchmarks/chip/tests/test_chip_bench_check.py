"""The correctness check, on the CPU at two cameras: a run of the harness
with the program as it is comes out correct; the control (the plain
reference with its detectors in bfloat16) and runs with the timed path
broken underneath come out not correct.  The look for a chip is skipped;
the rest of a run is driven as ``run.py`` drives it."""
import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

CHIP_DIR = Path(__file__).resolve().parents[1]
ROOT = CHIP_DIR.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(CHIP_DIR))

from chipbench import reference, spec  # noqa: E402
from chipbench.harness import (Weights, cell_config, compare,  # noqa: E402
                               load_limits, run_cell)
from chipbench.serve import CompileClock, build_runner  # noqa: E402
from chipbench.traffic import Stream  # noqa: E402

BENCH = spec.load_benchmark(ROOT)
CAMS = 2
SEED = 2 ** 31 + 172       # soak: camera 1 is live through the first 120 slots
CELLS = ["s01-deepstream.fcc", "s01-reducto.fcc", "s01-deepstream.soak"]


def small(name):
    """The cell as it stands, cut to two cameras."""
    cell = spec.find_cell(name, BENCH)
    return dataclasses.replace(cell, config={**cell.config,
                                             "num_cameras": CAMS})


def serve(name, build=build_runner, seconds=0.5):
    result, checks = run_cell(small(name), seed=SEED, seconds=seconds,
                              trace=False, clock=CompileClock(),
                              t_start=time.perf_counter(), build=build)
    return result, checks


def broken(fault):
    def build(cfg, seed, weights, ckpt):
        runner = build_runner(cfg, seed, weights, ckpt)
        fault(runner)
        return runner
    return build


def stale_state(runner):
    """The step hands on its state unchanged: every window starts from the
    initial carry at the stream position where the run began."""
    run = runner.system.run_episode

    def step(scene, *args, carry=None, **kw):
        t = scene._t
        out = run(scene, *args, carry=None, **kw)
        scene._t = t
        return out
    runner.system.run_episode = step


def half_the_cameras(runner):
    """The second half of the fleet is left out of every slot, which is
    scored over the rest."""
    offer = runner.offer

    def take(trace, faults=None):
        live = np.array(faults, bool)
        live[:, (live.shape[1] + 1) // 2:] = False
        return offer(trace, faults=live)
    runner.offer = take


def altered_answer(runner):
    """The last slot of every window logs a utility 0.05 off."""
    run = runner.system.run_episode

    def step(*args, **kw):
        logs = dict(run(*args, **kw))
        u = np.array(logs["utility"], np.float64)
        u[-1] += 0.05
        logs["utility"] = u
        return logs
    runner.system.run_episode = step


def no_checkpoint(runner):
    """The checkpoint after each window is skipped."""
    runner.checkpointer.maybe_save = lambda step: False


def late_checkpoint(runner):
    """Each window's checkpoint is put off by one window: the newest
    committed generation is always one behind."""
    save = runner.checkpointer.maybe_save
    runner.checkpointer.maybe_save = lambda step: step > 1 and save(step - 1)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    result, checks = serve(name)
    assert result["correct"] is True, checks
    assert checks["rows_off_pct"]["value"] == 0.0
    if "ckpt_windows_behind" in checks:
        assert checks["ckpt_windows_behind"]["value"] == 0
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"segments_per_s", "decision_p95_ms",
                                      "setup_s"}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [stale_state, half_the_cameras,
                                   altered_answer])
def test_broken_run_is_not_correct(name, fault):
    result, checks = serve(name, build=broken(fault))
    assert result["correct"] is False, checks
    assert checks["rows_off_pct"]["value"] > checks["rows_off_pct"]["limit"]


@pytest.mark.parametrize("fault", [no_checkpoint, late_checkpoint])
def test_skipped_checkpoint_is_not_correct(fault):
    result, checks = serve("s01-deepstream.fcc", build=broken(fault))
    assert result["correct"] is False, checks
    assert checks["ckpt_windows_behind"]["value"] >= 1
    assert checks["rows_off_pct"]["value"] == 0.0


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_control_is_not_correct(name):
    """The bfloat16 control against the float32 reference, at the cell's
    limit, over the stream's first 48 slots."""
    cell = small(name)
    cfg = cell_config(cell)
    w = Weights(cfg).ref()
    st = Stream(cell.mix, CAMS, SEED)
    bw, live = st.slots(0, 48)
    want = reference.run(cfg, w, SEED, bw, live)
    got = reference.run(cfg, w, SEED, bw, live, detector_dtype="bfloat16")
    reading = compare({k: list(v) for k, v in got.items()}, want)
    assert reading["rows_off_pct"] > load_limits(name)["rows_off_pct"]
