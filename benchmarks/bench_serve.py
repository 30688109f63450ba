"""Continuous-serving SLO bench: window turnaround over a diurnal soak.

Drives ``serve.stream.StreamingFleetRunner`` over the 1000-slot diurnal
soak stream (``data.scenarios.make_soak_stream``; reduced in ``--quick``)
THROUGH the hardened ingest stage (``serve.ingest.StreamIngestor`` over a
line-protocol replay source — the bench now measures the same parse ->
quarantine -> sequence path a real deployment serves), and reports the
serving SLO summary: p50/p99 window turnaround, sustained slots/sec, plus
the always-on invariants — ZERO episode recompiles after the warmup window
and exactly 2 'harvest' D2H fetches per window (the cost per window is
flat no matter how long the stream runs) — and the robustness counters
(load-shed ``dropped_slots``, quarantined / gap-filled / duplicate /
out-of-order slots; all zero on the clean soak, and part of the trajectory
so an accounting regression is visible across PRs).  The headline and a
trajectory entry land in ``artifacts/bench/BENCH_trajectory.json`` so
serving-throughput regressions are visible across PRs.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import detectors
from repro.core import fleet as fleet_mod
from repro.core import scheduler as sched_mod

WINDOW_SLOTS = 8
W_CAP_KBPS = 8000.0   # the harness-wide pinned DP capacity


def _build_runner(method: str):
    from repro.core import utility as util_mod
    from repro.core.scheduler import DeepStreamSystem, SystemConfig
    from repro.data.synthetic import DeviceScene, SceneConfig
    from repro.serve.stream import StreamConfig, StreamingFleetRunner

    light, server = detectors()
    scene_cfg = SceneConfig(seed=33)
    cfg = SystemConfig(scene=scene_cfg, episode=True, eval_frames=3,
                       w_cap_kbps=W_CAP_KBPS)
    system = DeepStreamSystem(cfg, light, server)
    system.mlp = util_mod.init_utility_mlp(jax.random.PRNGKey(0))
    system.tau_wl, system.tau_wh = 10.0, 50.0
    system.jcab_table = np.linspace(0.2, 0.8, 18).reshape(6, 3).astype(
        np.float32)
    runner = StreamingFleetRunner(
        system, DeviceScene(scene_cfg), method=method,
        cfg=StreamConfig(window_slots=WINDOW_SLOTS, queue_slots=WINDOW_SLOTS,
                         degrade=False))
    return runner, scene_cfg


def run(quick: bool = False) -> dict:
    from repro.data.scenarios import SOAK_SLOTS, make_soak_stream
    from repro.serve.ingest import (ListSource, StreamIngestor,
                                    format_record)

    slots = 96 if quick else SOAK_SLOTS
    method = "deepstream"
    runner, scene_cfg = _build_runner(method)
    trace, live = make_soak_stream(slots, num_cams=scene_cfg.num_cameras)

    # the soak stream as line-protocol records: the bench serves through
    # the full hardened ingest path, not the trusted in-process offer()
    lines = [format_record(t, trace[t], live[t]) for t in range(slots)]
    ingestor = StreamIngestor(runner,
                              ListSource(lines, batch=WINDOW_SLOTS))

    # warmup window: compiles the (method, bucket) episode executable
    ingestor.pump(until_t=WINDOW_SLOTS)
    n_compiles0 = fleet_mod.episode_compile_count()
    d0 = sched_mod.d2h_fetch_counts()
    warmup_windows = runner.window

    ingestor.pump(until_t=slots, flush=True)

    d1 = sched_mod.d2h_fetch_counts()
    timed_windows = runner.window - warmup_windows
    recompiles = fleet_mod.episode_compile_count() - n_compiles0
    harvest_per_window = ((d1["harvest"] - d0["harvest"]) / timed_windows
                          if timed_windows else 0.0)

    # SLO stats over the post-warmup windows only (the warmup window's
    # turnaround is compile time, not serving time)
    walls = np.asarray(list(runner.window_walls)[warmup_windows:], float)
    served = len(runner.logs["W"]) - warmup_windows * WINDOW_SLOTS
    p50 = float(np.percentile(walls, 50)) if walls.size else 0.0
    p99 = float(np.percentile(walls, 99)) if walls.size else 0.0
    slots_per_s = served / float(walls.sum()) if walls.sum() > 0 else 0.0

    result = {
        "method": method,
        "slots": slots,
        "window_slots": WINDOW_SLOTS,
        "windows": int(runner.window),
        "dropped_slots": int(runner.dropped_slots),
        "quarantined_slots": int(runner.quarantined_slots),
        "quarantined": dict(runner.quarantined),
        "gap_filled_slots": int(runner.gap_filled_slots),
        "duplicates": int(runner.duplicates),
        "out_of_order": int(runner.out_of_order),
        "p50_window_s": p50,
        "p99_window_s": p99,
        "slots_per_s": slots_per_s,
        "recompiles_after_warmup": int(recompiles),
        "harvest_fetches_per_window": harvest_per_window,
        "keep_fetches": d1["keep"] - d0["keep"],
        "control_fetches": d1["control"] - d0["control"],
        "headline": (f"{slots_per_s:.2f} slots/s "
                     f"p50={p50 * 1e3:.0f}ms p99={p99 * 1e3:.0f}ms "
                     f"recompiles={recompiles}"),
    }
    result["trajectory"] = {
        "bench": "bench_serve",
        "serve_soak": {
            "slots": slots,
            "window_slots": WINDOW_SLOTS,
            "p50_window_s": p50,
            "p99_window_s": p99,
            "slots_per_s": slots_per_s,
            "recompiles_after_warmup": int(recompiles),
            "harvest_fetches_per_window": harvest_per_window,
            "dropped_slots": int(runner.dropped_slots),
            "quarantined_slots": int(runner.quarantined_slots),
            "gap_filled_slots": int(runner.gap_filled_slots),
        },
    }
    return result
