#!/usr/bin/env python3
"""Chip smoke: serve the fleet stream on a TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the camera mesh over four chips

One chip (the default): the served deployment of ``launch/serve.py
--fleet-stream`` (``build_fleet_runner``: SceneConfig defaults, 5
cameras of 96x160 at 10 fps, the committed detector checkpoints, 8-slot
windows) serves the 64-slot soak stream (diurnal bandwidth + camera
churn) for deepstream and reducto on ``jax.devices()[0]``, with compiled
``edge_motion``/``tx_codec`` kernels and checkpoints at every window.  The
same stream then runs through the kernel-free reference body on the same
chip, and the logs must agree at the repo's 1e-5 gate.  The served run
must stay on the ``episode`` rung, compile no episode after its first
window, fetch exactly two harvests per window and log only finite values.

``--chips 4``: the camera-mesh path (``shard="auto"``: 5 cameras padded
to 8 over four devices) serves the same stream, compared with the same
stream on one device in this process at the same gate.  No other phase.

Everything runs in this one process (a chip belongs to one process).  The
script fails — nonzero exit, no result line — when JAX finds no TPU, and
when it runs outside this repository.  The last line of stdout is one
JSON object naming the device.  JAX's persistent compile cache sits where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CKPT_ROOT = ROOT / ".smoke_ckpt"
STREAM_SLOTS = 64
WINDOW_SLOTS = 8
METHODS = ("deepstream", "reducto")
GATE = 1e-5


class CompileClock:
    """Sums JAX's own compile-phase durations (trace, lowering, backend
    compile, persistent-cache retrieval) as they are reported."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self, monitoring):
        self.secs = defaultdict(float)
        monitoring.register_event_duration_secs_listener(self._record)

    def _record(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.secs[event] += duration

    def snapshot(self) -> dict:
        return dict(self.secs)

    def since(self, snap: dict) -> dict:
        return {e.rsplit("/", 1)[-1]: self.secs[e] - snap.get(e, 0.0)
                for e in self.EVENTS}


def serve(method: str, *, shard: str, use_kernels: bool = True,
          episode_pipelined: bool = True, ckpt: bool = False):
    """Serve the soak stream through ``build_fleet_runner`` from a fresh
    start.  Returns (runner, logs, probes): a probe of (episode traces,
    harvest fetches) is taken before each window and after the last."""
    import numpy as np

    from repro.core import fleet as fleet_mod
    from repro.core import scheduler as sched_mod
    from repro.launch.serve import build_fleet_runner, serve_soak_stream

    ckpt_dir = None
    if ckpt:
        ckpt_dir = CKPT_ROOT / method
        shutil.rmtree(ckpt_dir, ignore_errors=True)  # start fresh every run
    runner = build_fleet_runner(
        method, window_slots=WINDOW_SLOTS,
        ckpt_dir=None if ckpt_dir is None else str(ckpt_dir), ckpt_keep=2,
        shard=shard, use_kernels=use_kernels,
        episode_pipelined=episode_pipelined)
    probes = []

    def probe(**_):
        probes.append((fleet_mod.episode_compile_count(),
                       sched_mod.d2h_fetch_counts()["harvest"]))

    runner.fault_hook = probe
    with runner:
        serve_soak_stream(runner, STREAM_SLOTS)
    probe()
    logs = {k: np.asarray(v, np.float64) for k, v in runner.logs.items()}
    return runner, logs, probes


def compare(name: str, got: dict, ref: dict) -> None:
    """The repo's log gate: |got - ref| <= 1e-5 * max(1, max|ref|) per
    log key (tests/harness.py ``assert_logs_match``)."""
    import numpy as np

    for k in ref:
        scale = max(1.0, float(np.max(np.abs(ref[k]))))
        diff = float(np.max(np.abs(got[k] - ref[k])))
        print(f"# {name} {k}: max |diff| {diff!r} (gate {GATE * scale!r})")
        assert got[k].shape == ref[k].shape, (name, k)
        assert diff <= GATE * scale, f"{name} {k}: {diff} > {GATE * scale}"


def check_served(method: str, runner, logs: dict, probes) -> None:
    import numpy as np

    degrades = [e for e in runner.events if e["kind"] == "degrade"]
    print(f"# {method}: rung {runner.stats()['rung']}, "
          f"degrade events {len(degrades)}")
    assert runner.stats()["rung"] == "episode" and not degrades
    compiles = [c for c, _ in probes]
    print(f"# {method}: episode traces before each window and at the end "
          f"{compiles}")
    assert len(set(compiles[1:])) == 1, "an episode recompiled after window 0"
    fetches = np.diff([h for _, h in probes]).tolist()
    print(f"# {method}: harvest fetches per window {fetches}")
    assert fetches == [2] * runner.window
    assert runner.window == STREAM_SLOTS // WINDOW_SLOTS
    finite = all(bool(np.all(np.isfinite(v))) for v in logs.values())
    print(f"# {method}: {len(logs['utility'])} slots logged, all finite "
          f"{finite}, mean utility {float(np.mean(logs['utility']))!r}")
    assert finite and len(logs["utility"]) == STREAM_SLOTS


def one_chip(clock) -> None:
    import numpy as np

    for method in METHODS:
        snap = clock.snapshot()
        runner, logs, probes = serve(method, shard="off", ckpt=True)
        spent = clock.since(snap)
        walls = list(runner.window_walls)
        print(f"# {method}: compile s {sum(spent.values())!r} {spent}")
        print(f"# {method}: first window s {walls[0]!r}, warm window s "
              f"(median of {len(walls) - 1}) {float(np.median(walls[1:]))!r}")
        check_served(method, runner, logs, probes)
        _, ref, _ = serve(method, shard="off", use_kernels=False,
                          episode_pipelined=False)
        compare(f"{method} kernels vs reference body", logs, ref)


def kernel_launches(runner, trace_dir: Path) -> dict:
    """Profile one more warm window of ``runner`` and count, per TPU, the
    Mosaic kernel launches (``tpu_custom_call`` ops) it ran — where the
    per-camera ``edge_motion``/``tx_codec`` work landed."""
    import jax

    from repro.data.scenarios import make_soak_stream

    shutil.rmtree(trace_dir, ignore_errors=True)
    t = runner.t_next
    trace, live = make_soak_stream(
        t + WINDOW_SLOTS, num_cams=runner.system.cfg.scene.num_cameras)
    with jax.profiler.trace(str(trace_dir)):
        runner.offer(trace[t:], faults=live[t:])
        runner.serve()
    xplane = next(trace_dir.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(xplane))
    return {plane.name: sum("tpu_custom_call" in event.name
                            for line in plane.lines if line.name == "XLA Ops"
                            for event in line.events)
            for plane in data.planes if plane.name.startswith("/device:TPU")}


def four_chips() -> None:
    from repro.sharding import rules

    for method in METHODS:
        runner, logs, probes = serve(method, shard="auto")
        mesh = runner.system.mesh
        assert mesh is not None and mesh.size == 4
        check_served(method, runner, logs, probes)
        _, one, _ = serve(method, shard="off")
        compare(f"{method} 4-chip mesh vs one device", logs, one)
        C = runner.system.cfg.scene.num_cameras
        est_devs = sorted(d.id for d in runner.carry.est.a_ema.devices())
        print(f"# {method}: camera mesh over devices "
              f"{[d.id for d in mesh.devices.flat]}, cameras {C} padded to "
              f"{rules.pad_cameras(C, mesh)}; elastic (control) carry on "
              f"devices {est_devs}")
        launches = kernel_launches(runner, ROOT / ".smoke_trace" / method)
        print(f"# {method}: kernel launches per device in one traced warm "
              f"window of {WINDOW_SLOTS} slots {launches}")
        assert len(launches) == 4 and all(launches.values()), launches


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}")

    import jax
    import jax.monitoring

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"# devices: {len(devices)} x {dev.device_kind}")

    from repro.ckpt import checkpoint as ckpt
    from repro.train.detector_train import ARTIFACTS
    for variant in ("light", "server"):
        path = ARTIFACTS / f"detector_{variant}"
        how = "restored" if ckpt.is_committed(path) else "trained"
        print(f"# detector {variant}: {how} ({path.relative_to(ROOT)})")

    clock = CompileClock(jax.monitoring)
    if args.chips == 4:
        four_chips()
    else:
        one_chip(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
