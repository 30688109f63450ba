"""Crash-safe continuous serving: the always-on windowed stream runner.

Production traffic is an unbounded bandwidth/scene stream, not a fixed-T
batch trace.  ``StreamingFleetRunner`` converts the repo's strongest asset
— the compiled, zero-transfer (method, bucket) episode executables — into
the shape a real fleet service runs:

  * **Windows.**  Incoming slots queue in a BOUNDED ingest buffer
    (``StreamConfig.queue_slots``; overflow is dropped and counted in
    ``dropped_slots`` — an oversubscribed service sheds load explicitly,
    it does not grow without bound).  Whenever a full window
    (``window_slots``, sized to an episode bucket) is queued, it is
    dispatched through the EXISTING compiled episode executable — serving
    re-traces nothing, ever.

  * **Carry.**  The full device-resident episode carry (``ElasticStateJax``,
    reducto reference frames, previous liveness row — see
    ``scheduler.EpisodeCarry``) hands across window boundaries, so the
    windowed stream is slot-for-slot IDENTICAL (<= 1e-5) to one
    uninterrupted episode over the concatenated trace.  Codec keys are a
    pure per-(slot, camera) fold of the run key and the scene is pure in
    (seed, cursor), so both continue across windows — and across process
    restarts — for free.

  * **Checkpoints.**  At each window boundary the carry pytree + the run
    key + host counters checkpoint via ``ckpt.AsyncSaver`` (atomic commit:
    a crash mid-save can only ever leave an uncommitted directory behind;
    every leaf carries a content checksum, and ``restore`` falls back
    through generation history past corrupt generations to the newest one
    that VERIFIES — see ``ckpt.checkpoint``; ``ckpt_keep`` bounds retention
    without ever deleting the newest valid generation).  A
    ``ft.PreemptionCheckpointer`` turns SIGTERM/SIGINT into save-now +
    clean exit.  The kill-and-resume differential
    (tests/test_serve_stream.py): interrupt mid-stream, restart, restore,
    re-offer the stream from ``t_next`` — concatenated logs equal an
    uninterrupted run's, all methods and fault families, with ZERO episode
    recompiles after restore.

  * **SLO supervision.**  An ``ft.Watchdog`` over window turnaround times
    drives a degraded-mode ladder — full-bucket episode windows ->
    smaller-bucket episode chunks -> the pipelined per-slot loop — and
    climbs back up after ``recover_after`` consecutive healthy windows.
    Every rung serves THE SAME carry chain (the smaller rungs are exact,
    not approximations), so degradation changes latency shape only, never
    numerics; the watchdog re-baselines on every rung change
    (``Watchdog.rebaseline``) so the old rung's timing distribution never
    mis-gates the new one.

Window lifecycle (the serving contract)::

    offer(slots) -> [ingest queue] -> serve():
        per window:  dispatch(rung, carry)     # compiled episode / chunks
                     carry  = system.last_carry
                     logs  += window logs
                     verdict = watchdog.record(wall)   # ladder up/down
                     checkpointer.maybe_save(window)   # atomic, async
    crash / SIGTERM anywhere -> restore():
        latest_committed -> carry + key + counters + logs
        scene cursor = t_next; caller re-offers the stream from t_next
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as ckpt
from repro.common import obs
from repro.core import elastic as elastic_mod
from repro.core import fleet as fleet_mod
from repro.core.scheduler import DeepStreamSystem, EpisodeCarry
from repro.data.synthetic import DeviceScene
from repro.ft.watchdog import (PreemptionCheckpointer, Watchdog,
                               WatchdogConfig)

LOG_KEYS = ("utility", "mean_f1", "bytes", "W", "extra", "area",
            "alloc_kbps")

# the degraded-mode ladder: every rung serves the same carry chain exactly
# (see _dispatch_window), so a rung change is a latency decision only
LADDER = ("episode", "episode_small", "pipelined")

# window turnarounds and events a runner keeps: an always-on stream must not
# grow per window
HISTORY = 10_000


@dataclass
class StreamConfig:
    """Serving-policy knobs for ``StreamingFleetRunner``.

    ``window_slots`` should be an episode bucket size (it is bucketed up
    otherwise — correct, but pads every window); ``queue_slots`` bounds the
    ingest buffer (overflow drops, counted); ``ckpt_dir=None`` disables
    checkpointing (pure in-memory serving); ``ckpt_every`` is in windows;
    ``ckpt_keep`` bounds generation retention (keep-last-N, never deleting
    the newest VALID generation — see ``ckpt.gc_generations``; None keeps
    all); ``install_signal`` wires SIGTERM/SIGINT into save-now-and-exit
    (``ft.PreemptionCheckpointer``); ``recover_after`` healthy windows
    climb one ladder rung back up."""
    window_slots: int = 8
    queue_slots: int = 64
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 1
    ckpt_keep: Optional[int] = None
    degrade: bool = True
    recover_after: int = 3
    install_signal: bool = False
    watchdog: WatchdogConfig = field(default_factory=WatchdogConfig)


class StreamingFleetRunner:
    """Always-on windowed serving over a ``DeepStreamSystem``'s compiled
    episode executables — see the module docstring for the contract.

    ``wall_hook(window, wall_s) -> wall_s`` post-processes the measured
    window turnaround before the watchdog sees it (tests inject straggler
    windows); ``fault_hook(window=, rung=)`` runs right before each window
    dispatch and may raise (tests inject mid-stream crashes); ``chaos`` is
    an optional ``ft.chaos.ChaosEngine`` — its ``pre_window`` fires before
    each window (exception / SIGTERM sites) and its checkpoint sites thread
    into the saver (save latency, post-commit corruption)."""

    def __init__(self, system: DeepStreamSystem, scene: DeviceScene,
                 method: str = "deepstream", cfg: Optional[StreamConfig] = None,
                 use_elastic: Optional[bool] = None,
                 wall_hook: Optional[Callable[[int, float], float]] = None,
                 fault_hook: Optional[Callable[..., None]] = None,
                 chaos: Optional[Any] = None):
        cfg = cfg if cfg is not None else StreamConfig()
        if not system.cfg.episode:
            raise ValueError("StreamingFleetRunner needs an episode-mode "
                             "system (SystemConfig.episode=True)")
        if system.cfg.w_cap_kbps is None:
            # w_cap is a jit STATIC: deriving it per window from each
            # window's max would re-trace the control/episode programs on
            # every bandwidth swing — the opposite of serving
            raise ValueError("streaming requires SystemConfig.w_cap_kbps "
                             "pinned (per-window capacities would recompile "
                             "the episode executables)")
        if not isinstance(scene, DeviceScene):
            raise TypeError("streaming serves a DeviceScene (device-side "
                            f"segment generation), got {type(scene)!r}")
        self.system = system
        self.scene = scene
        self.method = method
        self.cfg = cfg
        self.use_elastic = (method == "deepstream" if use_elastic is None
                            else use_elastic)
        self.wall_hook = wall_hook
        self.fault_hook = fault_hook
        self.chaos = chaos
        C = system.cfg.scene.num_cameras
        self._C = C
        self.carry: Optional[EpisodeCarry] = None
        self.window = 0                      # completed windows
        self.dropped_slots = 0               # ingest-queue overflow
        self.rung = 0                        # ladder position
        self.ok_streak = 0                   # consecutive healthy windows
        # ingest-hardening counters (fed by serve.ingest via note_ingest);
        # checkpointed with the carry so accounting survives restarts
        self.quarantined: Dict[str, int] = {}
        self.quarantined_slots = 0
        self.gap_filled_slots = 0
        self.duplicates = 0
        self.out_of_order = 0
        self.logs: Dict[str, List[float]] = {k: [] for k in LOG_KEYS}
        # turnaround of the most recent windows, and their count and sum
        self.window_walls: Deque[float] = deque(maxlen=HISTORY)
        self.windows_served = 0
        self.wall_s_total = 0.0
        self.events: Deque[Dict[str, Any]] = deque(maxlen=HISTORY)
        self._queue: Deque[Tuple[float, np.ndarray]] = deque()
        self.watchdog = Watchdog(cfg.watchdog)
        self.saver = ckpt.AsyncSaver(keep=cfg.ckpt_keep, chaos=chaos)
        self.checkpointer = PreemptionCheckpointer(
            self._checkpoint, every=max(1, cfg.ckpt_every),
            install_signal=cfg.install_signal)

    # -- ingest ----------------------------------------------------------------

    @property
    def t_next(self) -> int:
        """The next global slot this runner will serve — the stream offset
        a restarted feeder resumes from."""
        return self.scene._t

    def queued_slots(self) -> int:
        return len(self._queue)

    def note_ingest(self, kind: str, **info: Any) -> None:
        """Ingest-stage accounting hook (``serve.ingest`` calls this for
        every quarantine / dedupe / reorder / gap-fill decision): bumps the
        counters and appends an event — the runner's event log is the
        single serving record."""
        if kind == "quarantine":
            reason = str(info.get("reason", "unknown"))
            self.quarantined[reason] = self.quarantined.get(reason, 0) + 1
            self.quarantined_slots += 1
        elif kind == "gap_fill":
            self.gap_filled_slots += 1
        elif kind == "duplicate":
            self.duplicates += 1
        elif kind == "out_of_order":
            self.out_of_order += 1
        self.events.append({"kind": kind, **info})

    def offer(self, trace_kbps: np.ndarray,
              faults: Optional[np.ndarray] = None) -> int:
        """Enqueue incoming slots; returns how many were ACCEPTED.  Slots
        beyond the bounded queue's free space are dropped and counted in
        ``dropped_slots`` — explicit load shedding, the always-on service's
        answer to input outpacing service rate.  Rejects non-finite or
        negative bandwidth outright (ValueError): the hardened path is
        ``serve.ingest`` (which quarantines and gap-fills); a direct
        in-process feeder handing over garbage is a caller bug, and nothing
        non-finite may ever reach the device carry."""
        trace = np.asarray(trace_kbps, np.float64).reshape(-1)
        if trace.size and (not np.all(np.isfinite(trace))
                           or np.any(trace < 0.0)):
            raise ValueError("offer() requires finite, non-negative "
                             "bandwidth; route untrusted input through "
                             "serve.ingest.StreamIngestor")
        T = len(trace)
        if faults is None:
            live = np.ones((T, self._C), bool)
        else:
            live = np.asarray(faults, bool)
            if live.shape != (T, self._C):
                raise ValueError(f"faults mask must be (T={T}, C={self._C}),"
                                 f" got {live.shape}")
        room = max(0, self.cfg.queue_slots - len(self._queue))
        take = min(room, T)
        for i in range(take):
            self._queue.append((float(trace[i]), live[i]))
        if take < T:
            self.dropped_slots += T - take
            self.events.append({"kind": "drop", "slots": T - take,
                                "queued": len(self._queue)})
        return take

    # -- serving ---------------------------------------------------------------

    def serve(self, flush: bool = False) -> int:
        """Serve every FULL window currently queued (plus, with ``flush``,
        one final partial window — same bucket executable, shorter active
        prefix).  Returns the number of windows served.  May raise
        ``SystemExit`` after a preemption-triggered save
        (``install_signal``) or whatever ``fault_hook`` raises — the
        checkpoint chain makes either recoverable via ``restore``."""
        served = 0
        while len(self._queue) >= self.cfg.window_slots:
            self._serve_window(self.cfg.window_slots)
            served += 1
        if flush and self._queue:
            self._serve_window(len(self._queue))
            served += 1
        return served

    def _take(self, n: int) -> Tuple[np.ndarray, np.ndarray]:
        W = np.empty(n, np.float64)
        live = np.empty((n, self._C), bool)
        for i in range(n):
            W[i], live[i] = self._queue.popleft()
        return W, live

    def _serve_window(self, n: int) -> None:
        W, live = self._take(n)
        t0 = time.perf_counter()
        if self.fault_hook is not None:
            self.fault_hook(window=self.window, rung=self.rung)
        if self.chaos is not None:
            # serve.exception / serve.sigterm sites; consumed-once, so a
            # recovered runner re-serving this window does not re-crash
            self.chaos.pre_window(self.window)
        logs = self._dispatch_window(W, live)
        wall = time.perf_counter() - t0
        if self.wall_hook is not None:
            wall = self.wall_hook(self.window, wall)
        self.carry = self.system.last_carry
        with obs.span("serve.logs"):
            for k in LOG_KEYS:
                self.logs[k].extend(float(v) for v in logs[k])
        self.window += 1
        self.window_walls.append(wall)
        self.windows_served += 1
        self.wall_s_total += wall
        with obs.span("serve.supervise"):
            self._supervise(wall)
        if self.cfg.ckpt_dir is not None:
            with obs.span("serve.checkpoint"):
                self.checkpointer.maybe_save(self.window)

    def _dispatch_window(self, W: np.ndarray, live: np.ndarray
                         ) -> Dict[str, np.ndarray]:
        """One window at the current ladder rung.  Every rung threads the
        SAME carry chain — ``episode_small`` chains the carry through each
        smaller-bucket chunk and ``pipelined`` seeds the per-slot loop from
        it — so rung changes are numerically invisible."""
        mode = LADDER[self.rung]
        if mode == "pipelined":
            return self.system._run_batched(
                self.scene, W, self.method, self.use_elastic, faults=live,
                carry=self.carry)
        step = len(W) if mode == "episode" else self._small_len()
        parts = []
        for i0 in range(0, len(W), step):
            i1 = min(i0 + step, len(W))
            parts.append(self.system.run_episode(
                self.scene, W[i0:i1], self.method, self.use_elastic,
                faults=live[i0:i1], carry=self.carry))
            self.carry = self.system.last_carry
        if len(parts) == 1:
            return parts[0]
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

    def _small_len(self) -> int:
        """The degraded chunk size: the episode bucket BELOW the window's
        (already compiled by the bucket ladder), floored at the smallest."""
        buckets = sorted(self.system.cfg.episode_buckets or
                         (self.cfg.window_slots,))
        wb = fleet_mod.bucket_len(self.cfg.window_slots, buckets)
        below = [b for b in buckets if b < wb]
        return below[-1] if below else buckets[0]

    def _supervise(self, wall: float) -> None:
        """The SLO ladder: a 'replace' verdict (sustained straggling)
        degrades one rung, ``recover_after`` consecutive 'ok' windows climb
        one back; both re-baseline the watchdog (the new rung's timing
        distribution is a different population)."""
        verdict = self.watchdog.record(self.window, wall)
        self.events.append({"kind": "window", "window": self.window,
                            "rung": LADDER[self.rung], "wall_s": wall,
                            "verdict": verdict})
        if (verdict == "replace" and self.cfg.degrade
                and self.rung + 1 < len(LADDER)):
            self.rung += 1
            self.ok_streak = 0
            self.watchdog.rebaseline()
            self.events.append({"kind": "degrade", "to": LADDER[self.rung],
                                "window": self.window})
        elif verdict == "ok" and self.rung > 0:
            self.ok_streak += 1
            if self.ok_streak >= self.cfg.recover_after:
                self.rung -= 1
                self.ok_streak = 0
                self.watchdog.rebaseline()
                self.events.append({"kind": "recover",
                                    "to": LADDER[self.rung],
                                    "window": self.window})
        elif verdict != "ok":
            self.ok_streak = 0

    # -- checkpoint / restore --------------------------------------------------

    def _carry_tree(self) -> Dict[str, Any]:
        """The checkpointed pytree: the device carry + the codec run key.
        Everything else a restart needs is host metadata (below) or pure
        (the scene, the key fold)."""
        c = self.carry
        return {"est": c.est, "ref": jnp.asarray(c.ref, jnp.float32),
                "live_prev": jnp.asarray(c.live_prev, bool),
                "key": self.system._key}

    def _carry_target(self) -> Dict[str, Any]:
        """A zero carry with the exact structure/shapes ``ckpt.restore``
        validates against."""
        scfg = self.system.cfg.scene
        return {"est": elastic_mod.init_state_jax(),
                "ref": jnp.zeros((self._C, scfg.height, scfg.width),
                                 jnp.float32),
                "live_prev": jnp.ones((self._C,), bool),
                "key": jnp.zeros_like(self.system._key)}

    def _ckpt_path(self, window: int) -> Path:
        return Path(self.cfg.ckpt_dir) / f"window_{window:08d}"

    def _checkpoint(self, window: int) -> None:
        """Atomic carry checkpoint at a window boundary.  Async by default
        (the next window overlaps the compression/IO); BLOCKING when
        preempted — the process is about to exit, and the daemon writer
        thread dying mid-write must only ever cost us the LAST checkpoint,
        never corrupt one (uncommitted directories are never restored)."""
        if self.carry is None:
            return
        with obs.span("ckpt.meta"):
            meta = {"window": window, "t_next": int(self.t_next),
                    "t_first": int(self.carry.t_first), "rung": self.rung,
                    "ok_streak": self.ok_streak,
                    "dropped_slots": self.dropped_slots,
                    "method": self.method,
                    "quarantined": dict(self.quarantined),
                    "quarantined_slots": self.quarantined_slots,
                    "gap_filled_slots": self.gap_filled_slots,
                    "duplicates": self.duplicates,
                    "out_of_order": self.out_of_order,
                    "logs": {k: list(v) for k, v in self.logs.items()}}
        self.saver.save(self._carry_tree(), self._ckpt_path(window),
                        step=window, metadata=meta,
                        blocking=self.checkpointer.preempted)

    def restore(self) -> bool:
        """Restore from the newest VALID committed checkpoint under
        ``ckpt_dir`` (False if there is none — fresh start).  Self-healing:
        every leaf is checksum-verified on read, and a corrupt latest
        generation (bit-flip, truncation, torn manifest) is SKIPPED — with
        a ``restore_skip`` event naming what failed — falling back through
        generation history to the newest checkpoint that verifies.
        Rebuilds the full serving state: device carry, codec run key, scene
        cursor (the scene is pure in (seed, t) — no frames are stored),
        accumulated logs and counters, ladder rung.  The caller then
        re-offers the stream from ``t_next``; zero recompiles — the
        restored carry re-enters the exact executables the pre-crash
        process compiled."""
        if self.cfg.ckpt_dir is None:
            return False
        tree = meta = path = None
        for cand in reversed(ckpt.generations(self.cfg.ckpt_dir)):
            try:
                tree, meta = ckpt.restore(cand, self._carry_target())
                path = cand
                break
            except ckpt.CheckpointCorruptError as e:
                self.events.append({"kind": "restore_skip",
                                    "path": str(cand), "error": str(e)})
        if path is None:
            return False
        self.system._key = tree["key"]
        self.carry = EpisodeCarry(
            est=tree["est"], ref=tree["ref"],
            live_prev=np.asarray(tree["live_prev"], bool),
            t_first=int(meta["t_first"]))
        self.scene._t = int(meta["t_next"])
        self.window = int(meta["window"])
        self.rung = int(meta["rung"])
        self.ok_streak = int(meta["ok_streak"])
        self.dropped_slots = int(meta["dropped_slots"])
        self.quarantined = {str(k): int(v) for k, v in
                            meta.get("quarantined", {}).items()}
        self.quarantined_slots = int(meta.get("quarantined_slots", 0))
        self.gap_filled_slots = int(meta.get("gap_filled_slots", 0))
        self.duplicates = int(meta.get("duplicates", 0))
        self.out_of_order = int(meta.get("out_of_order", 0))
        self.logs = {k: [float(v) for v in meta["logs"].get(k, [])]
                     for k in LOG_KEYS}
        self.checkpointer.last_saved = self.window
        self.events.append({"kind": "restore", "path": str(path),
                            "window": self.window, "t_next": self.t_next})
        return True

    # -- stats / teardown ------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Serving SLO summary over the windows served so far (the
        percentiles over the most recent ``HISTORY``); ``host_ms``: the
        median milliseconds of each serving span over its most recent
        ``obs.RECENT`` (where a window's host time goes); ``h2d_puts`` and
        ``operand_cache``: the process's host-to-device puts per category
        (one 'episode' put per window) and the episode operand cache's
        builds and reuses (``repro.common.obs``)."""
        walls = np.asarray(self.window_walls, float)
        slots = len(self.logs["W"])
        total = self.wall_s_total
        return {
            "windows": self.windows_served,
            "slots": slots,
            "dropped_slots": self.dropped_slots,
            "quarantined_slots": self.quarantined_slots,
            "gap_filled_slots": self.gap_filled_slots,
            "duplicates": self.duplicates,
            "out_of_order": self.out_of_order,
            "p50_window_s": float(np.percentile(walls, 50)) if walls.size else 0.0,
            "p99_window_s": float(np.percentile(walls, 99)) if walls.size else 0.0,
            "slots_per_s": slots / total if total > 0 else 0.0,
            "rung": LADDER[self.rung],
            "host_ms": {n: 1e3 * float(np.median(d)) for n in obs.SERVE_SPANS
                        if (d := obs.recent(n))},
            "h2d_puts": obs.h2d_put_counts(),
            "operand_cache": obs.operand_cache_counts(),
        }

    def close(self) -> None:
        """Flush the in-flight checkpoint write and restore the process's
        signal handlers."""
        self.saver.wait()
        self.checkpointer.close()

    def __enter__(self) -> "StreamingFleetRunner":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
