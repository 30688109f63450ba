"""The system under test, built from the program's public constructors, and
the loop that drives it.

The fleet is built as the program's served deployment builds it: an
episode-mode ``DeepStreamSystem`` (compiled kernels, pipelined episode
body) over a ``DeviceScene``, wrapped in a windowed
``StreamingFleetRunner`` — with every number taken from the configuration
file and the weights from the benchmark's own copies.
"""
from __future__ import annotations

import shutil
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from chipbench.spec import CHIP_DIR
from chipbench.traffic import Stream

SCRATCH = CHIP_DIR / ".scratch"


class CompileClock:
    """Sums JAX's own compile-phase durations as they are reported."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.secs: Dict[str, float] = defaultdict(float)
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._record)

    def _record(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.secs[event.rsplit("/", 1)[-1]] += duration
            self.count += 1


def build_runner(cfg: Dict, seed: int, weights, ckpt_dir: Optional[Path]):
    """A fresh ``StreamingFleetRunner`` for configuration ``cfg``."""
    import jax

    from repro.core.codec import CodecConfig
    from repro.core.elastic import ElasticConfig
    from repro.core.scheduler import DeepStreamSystem, SystemConfig
    from repro.data.synthetic import DeviceScene, SceneConfig
    from repro.serve.stream import StreamConfig, StreamingFleetRunner

    sc = dict(cfg["scene"])
    sc["obj_size_range"] = tuple(sc["obj_size_range"])
    scene_cfg = SceneConfig(num_cameras=cfg["num_cameras"], seed=seed, **sc)
    cc = dict(cfg["codec"])
    cc["bitrates_kbps"] = tuple(cc["bitrates_kbps"])
    cc["resolutions"] = tuple(cc["resolutions"])
    s = cfg["system"]
    sys_cfg = SystemConfig(
        scene=scene_cfg, codec=CodecConfig(**cc),
        elastic=ElasticConfig(**cfg["elastic"]),
        block_size=s["block_size"], eval_frames=s["eval_frames"],
        use_kernels=s["use_kernels"], episode=s["episode"],
        episode_pipelined=s["episode_pipelined"],
        w_cap_kbps=float(s["w_cap_kbps"]), shard=s["shard"])
    system = DeepStreamSystem(sys_cfg, weights.light, weights.server)
    system.mlp = weights.mlp
    system.tau_wl = float(cfg["control"]["tau_wl"])
    system.tau_wh = float(cfg["control"]["tau_wh"])
    system._key = jax.random.PRNGKey(s["codec_key"])
    sv = cfg["serving"]
    if ckpt_dir is not None:
        shutil.rmtree(ckpt_dir, ignore_errors=True)   # fresh every run
    return StreamingFleetRunner(
        system, DeviceScene(scene_cfg), method=cfg["method"],
        cfg=StreamConfig(window_slots=sv["window_slots"],
                         queue_slots=sv["queue_slots"],
                         ckpt_dir=None if ckpt_dir is None else str(ckpt_dir),
                         ckpt_keep=sv["ckpt_keep"], degrade=sv["degrade"],
                         install_signal=ckpt_dir is not None))


@dataclass
class Loop:
    """The feeder, as the mix's ``loop`` says.  Closed: offer one window,
    serve it, repeat.  Open: offer each slot at its arrival time, at a
    fixed rate, and serve whenever a window's worth is queued.

    Per accepted slot it records when the slot arrived and when its log
    row was on the host (``serve()`` returned); slots are served in the
    order they were accepted."""
    runner: object
    stream: Stream
    t_next: int = 0
    arrive_at: List[float] = field(default_factory=list)   # per slot
    done_at: List[float] = field(default_factory=list)
    accepted: List[int] = field(default_factory=list)      # stream index
    dropped: int = 0

    @property
    def kind(self) -> str:
        return self.stream.mix["loop"]["kind"]

    def _offer(self, arrivals: List[float]) -> None:
        n = len(arrivals)
        bw, live = self.stream.slots(self.t_next, self.t_next + n)
        took = self.runner.offer(bw, faults=live)
        self.arrive_at += arrivals[:took]
        self.accepted += range(self.t_next, self.t_next + took)
        self.dropped += n - took
        self.t_next += n

    def _serve(self, flush: bool = False) -> None:
        from jax.profiler import TraceAnnotation

        before = len(self.runner.logs["utility"])
        with TraceAnnotation("bench.serve"):
            self.runner.serve(flush=flush)
        t = time.perf_counter()
        self.done_at += [t] * (len(self.runner.logs["utility"]) - before)

    def window(self) -> None:
        """Offer one window of slots and serve it."""
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            with TraceAnnotation("bench.offer"):
                self._offer([t0] * self.stream.window_slots)
            self._serve()

    def run_for(self, seconds: float) -> None:
        """Drive the loop for ``seconds``.  An open loop offers the slots
        that arrive in that time, a whole number of windows, and then
        serves what is still queued."""
        from jax.profiler import TraceAnnotation

        if self.kind == "closed":
            t_end = time.perf_counter() + seconds
            while time.perf_counter() < t_end:
                self.window()
            return
        rate = float(self.stream.mix["loop"]["slots_per_s"])
        w = self.stream.window_slots
        total = max(1, int(seconds * rate) // w) * w
        t0, i = time.perf_counter(), 0
        while i < total:
            due = min(total, int((time.perf_counter() - t0) * rate) + 1)
            if due > i:
                self._offer([t0 + j / rate for j in range(i, due)])
                i = due
            if len(self.arrive_at) - len(self.done_at) >= w:
                with TraceAnnotation("bench.window"):
                    self._serve()
            else:
                time.sleep(max(0.0, t0 + i / rate - time.perf_counter()))
        self._serve(flush=True)

    def run_windows(self, windows: int) -> None:
        """Drive the loop for about ``windows`` windows of slots."""
        if self.kind == "closed":
            for _ in range(windows):
                self.window()
        else:
            w = self.stream.window_slots
            self.run_for(windows * w / float(
                self.stream.mix["loop"]["slots_per_s"]))


def probe():
    """(episode compiles so far, harvest fetches so far)."""
    from repro.core import fleet, scheduler
    return fleet.episode_compile_count(), scheduler.d2h_fetch_counts()["harvest"]
