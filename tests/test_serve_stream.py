"""Crash-safe continuous serving: ``serve.stream`` differentials.

The headline is the KILL-AND-RESUME differential: interrupt a windowed
stream mid-trace (an injected exception, or a real SIGTERM through the
``ft.PreemptionCheckpointer``), restart from scratch, restore the latest
committed checkpoint, re-offer the stream from ``t_next`` — and the
concatenated logs must match an UNINTERRUPTED episode run over the same
trace to <= 1e-5, for every method and fault family, with ZERO episode
recompiles after restore (the restored carry re-enters the executables the
pre-crash process compiled) and the episode-mode D2H contract intact
(exactly the 2 'harvest' fetches per episode dispatch, nothing else).

Also here: windowed == continuous (no crash at all), a warm window's
operands arriving in one put with the cached invariants (and the cache
rebuilt when a threshold changes), the SLO watchdog
ladder (degrade under injected stragglers -> pipelined, recover, logs STILL
exact — every rung serves the same carry chain), bounded-queue load
shedding with drop accounting that survives restore, a small soak, and the
ServeEngine drain-budget starvation regression.
"""
import contextlib
import os
import signal

import jax
import numpy as np
import pytest

import harness
from repro.common import obs
from repro.core import elastic as elastic_mod
from repro.core import fleet as fleet_mod
from repro.core import scheduler as sched_mod
from repro.data import synthetic as synth_mod
from repro.sharding import rules as shard_rules
from repro.data.scenarios import make_faults, make_scene, make_soak_stream, \
    make_trace
from repro.data.synthetic import DeviceScene
from repro.ft.watchdog import WatchdogConfig
from repro.serve.stream import LADDER, StreamConfig, StreamingFleetRunner

SCENE = ("urban_mid", 33)
STREAM_KEYS = ("utility", "mean_f1", "bytes", "alloc_kbps", "extra", "area")


def _scene_cfg():
    fam, seed = SCENE
    return make_scene(fam, seed)


def _stream_inputs(T, fault_family, *, trace_seed=8, fault_seed=3):
    scfg = _scene_cfg()
    trace = make_trace("fcc_medium", T, seed=trace_seed,
                       num_cams=scfg.num_cameras)
    faults = make_faults(fault_family, T, scfg.num_cameras, seed=fault_seed)
    return scfg, trace, faults


def _continuous_reference(detectors, scfg, trace, faults, method):
    """One uninterrupted episode-mode run over the whole trace."""
    s = harness.build_system(detectors, "episode", scfg)
    s._key = jax.random.PRNGKey(1234)
    return s.run(DeviceScene(scfg), trace, method=method, faults=faults)


def _runner(detectors, scfg, method, cfg, **kw):
    s = harness.build_system(detectors, "episode", scfg)
    s._key = jax.random.PRNGKey(1234)
    return StreamingFleetRunner(s, DeviceScene(scfg), method=method,
                                cfg=cfg, **kw)


def _logs(runner):
    return {k: np.asarray(v) for k, v in runner.logs.items()}


# -- windowed == continuous ----------------------------------------------------

@pytest.mark.parametrize("method", harness.METHODS)
def test_windowed_matches_continuous(detectors, method):
    """Carry handoff across window boundaries makes the windowed stream
    slot-for-slot identical to one uninterrupted episode — including a
    final partial (flushed) window through the same bucket executable."""
    scfg, trace, faults = _stream_inputs(12, "camera_flap")
    ref = _continuous_reference(detectors, scfg, trace, faults, method)

    runner = _runner(detectors, scfg, method, StreamConfig(window_slots=8))
    assert runner.offer(trace, faults=faults) == len(trace)
    served = runner.serve(flush=True)        # one full + one partial window
    assert served == 2 and runner.t_next == len(trace)
    harness.assert_logs_match(ref, _logs(runner), keys=STREAM_KEYS,
                              ctx=f"stream {method}")


# -- episode operands: one put per window, cached invariants ------------------

# device-array constructors a warm window's prep must not call: its host
# values arrive in ONE jax.device_put and everything else is cached
_CONSTRUCTORS = frozenset({
    "array", "asarray", "zeros", "ones", "full", "empty", "arange",
    "zeros_like", "ones_like", "full_like", "concatenate", "stack",
    "float32", "int32", "bool_", "uint32"})
# the modules whose `jnp` the episode prep path reaches
_PREP_MODULES = (sched_mod, fleet_mod, elastic_mod, synth_mod, shard_rules)


class _NoConstructors:
    """`jax.numpy` with its array constructors made to raise."""

    def __init__(self, jnp):
        self._jnp = jnp

    def __getattr__(self, name):
        if name in _CONSTRUCTORS:
            raise AssertionError(f"warm prep called jnp.{name}")
        return getattr(self._jnp, name)


@contextlib.contextmanager
def _guarded_prep(puts):
    """Inside, every ``serve.prep`` span runs with the array constructors
    of ``_PREP_MODULES`` and ``init_state_jax`` raising and implicit
    transfers refused, and counts its ``jax.device_put`` calls into
    ``puts``."""
    span = obs.span

    @contextlib.contextmanager
    def guarded(name):
        if name != "serve.prep":
            with span(name):
                yield
            return
        put = jax.device_put

        def counted_put(*a, **k):
            puts.append(1)
            return put(*a, **k)

        with span(name), pytest.MonkeyPatch.context() as m:
            for mod in _PREP_MODULES:
                m.setattr(mod, "jnp", _NoConstructors(mod.jnp))
            m.setattr(elastic_mod, "init_state_jax", None)
            m.setattr(jax, "device_put", counted_put)
            with jax.transfer_guard("disallow"):
                yield

    with pytest.MonkeyPatch.context() as m:
        m.setattr(obs, "span", guarded)
        yield


@pytest.mark.parametrize("method", harness.METHODS)
def test_warm_window_prep_is_one_put(detectors, method):
    """Each window's operands reach the device in ONE put: the put counter
    reads one episode put per window, the invariants are built on the
    first window and reused on every later one, and once the first window
    is warm, prep calls no array constructor, makes no implicit transfer
    and calls ``jax.device_put`` exactly once — on full windows and a
    flushed partial one.  The logs still equal one uninterrupted episode."""
    scfg, trace, faults = _stream_inputs(29, "camera_flap")
    ref = _continuous_reference(detectors, scfg, trace, faults, method)
    runner = _runner(detectors, scfg, method, StreamConfig(window_slots=8))
    puts0, cache0 = obs.h2d_put_counts(), obs.operand_cache_counts()
    runner.offer(trace[:8], faults=faults[:8])
    assert runner.serve() == 1
    puts = []
    with _guarded_prep(puts):
        runner.offer(trace[8:], faults=faults[8:])
        assert runner.serve(flush=True) == 3      # two full + one partial
    assert len(puts) == 3
    st = runner.stats()
    assert {k: st["h2d_puts"][k] - puts0[k] for k in puts0} == {
        "episode": 4, "invariants": 1}
    assert {k: st["operand_cache"][k] - cache0[k] for k in cache0} == {
        "built": 1, "reused": 3}
    harness.assert_logs_match(ref, _logs(runner), keys=STREAM_KEYS,
                              ctx=f"one-put stream {method}")


def test_tau_wl_change_rebuilds_invariants(detectors):
    """A re-profiled ``tau_wl`` between windows rebuilds the cached
    invariants, and the next window serves exactly what a fresh system
    with that threshold serves from the same carry.  The new ``tau_wl``
    lies above the whole trace, so elastic borrowing starts: the cache
    cannot be stale without the logs showing it."""
    scfg, trace, faults = _stream_inputs(16, "camera_flap")
    tau_wl = 3000.0
    runner = _runner(detectors, scfg, "deepstream",
                     StreamConfig(window_slots=8))
    cache0 = obs.operand_cache_counts()
    runner.offer(trace[:8], faults=faults[:8])
    runner.serve()
    carry = runner.carry
    runner.system.tau_wl = tau_wl
    runner.offer(trace[8:], faults=faults[8:])
    runner.serve()
    cache1 = obs.operand_cache_counts()
    assert cache1["built"] - cache0["built"] == 2
    assert cache1["reused"] == cache0["reused"]

    first = harness.build_system(detectors, "episode", scfg)
    first._key = jax.random.PRNGKey(1234)
    want0 = first.run_episode(synth_mod.DeviceScene(scfg), trace[:8],
                              "deepstream", faults=faults[:8])
    fresh = harness.build_system(detectors, "episode", scfg)
    fresh.tau_wl = tau_wl
    fresh._key = jax.random.PRNGKey(1234)
    scene = synth_mod.DeviceScene(scfg)
    scene._t = 8
    want1 = fresh.run_episode(scene, trace[8:], "deepstream",
                              faults=faults[8:], carry=carry)
    got = _logs(runner)
    for k in STREAM_KEYS:
        np.testing.assert_array_equal(
            got[k], np.concatenate([want0[k], want1[k]]), err_msg=k)
    assert not got["extra"][:8].any() and got["extra"][8:].any()


# -- kill-and-resume -----------------------------------------------------------

class _InjectedCrash(Exception):
    pass


def _interrupt_hook(kind):
    """Interrupt the stream mid-trace: an injected exception right before
    window 2 dispatches, or a real SIGTERM right before window 1 (the
    handler sets ``preempted``; window 1 still serves, then the
    checkpointer saves BLOCKING at its boundary and exits 143).  Either
    way windows 0-1 are committed and window 2 remains to resume."""
    def hook(window, rung):
        if kind == "exception" and window == 2:
            raise _InjectedCrash(f"window {window}")
        if kind == "sigterm" and window == 1:
            signal.raise_signal(signal.SIGTERM)
    return hook


# every method under BOTH fault families, each (interrupt kind) covered
# for every method across the grid
KILL_GRID = [(m, fam, kind)
             for m, kind in zip(harness.METHODS,
                                ["exception", "sigterm"] * 2)
             for fam in ("camera_flap", "camera_churn")]


@pytest.mark.parametrize("method,family,kind", KILL_GRID)
def test_kill_and_resume_differential(detectors, method, family, kind,
                                      tmp_path):
    T, WIN = 24, 8
    scfg, trace, faults = _stream_inputs(T, family)
    ref = _continuous_reference(detectors, scfg, trace, faults, method)

    # process A: serve, get killed before window 2 of 3
    cfg = StreamConfig(window_slots=WIN, ckpt_dir=str(tmp_path),
                       install_signal=(kind == "sigterm"))
    rA = _runner(detectors, scfg, method, cfg,
                 fault_hook=_interrupt_hook(kind))
    rA.offer(trace, faults=faults)
    if kind == "exception":
        with pytest.raises(_InjectedCrash):
            rA.serve(flush=True)
        rA.saver.wait()                      # the async save may be in flight
    else:
        # SIGTERM lands mid-window; the preempted checkpointer saves
        # BLOCKING at the window boundary and exits 128+15
        with pytest.raises(SystemExit) as exc:
            rA.serve(flush=True)
        assert exc.value.code == 143
    rA.checkpointer.close()
    assert rA.window >= 2 and rA.t_next < T

    # process B: fresh system + runner, restore, re-offer from t_next
    n_compiles = fleet_mod.episode_compile_count()
    d_before = sched_mod.d2h_fetch_counts()
    rB = _runner(detectors, scfg, method,
                 StreamConfig(window_slots=WIN, ckpt_dir=str(tmp_path)))
    assert rB.restore()
    assert rB.t_next == rB.window * WIN
    rB.offer(trace[rB.t_next:], faults=faults[rB.t_next:])
    resumed_windows = rB.serve(flush=True)
    assert rB.t_next == T

    # zero recompiles after restore, and the episode D2H contract holds:
    # exactly 2 'harvest' fetches per resumed window, no keep/control
    d_after = sched_mod.d2h_fetch_counts()
    assert fleet_mod.episode_compile_count() == n_compiles, \
        "episode executable recompiled after restore"
    assert d_after["harvest"] - d_before["harvest"] == 2 * resumed_windows
    assert d_after["keep"] == d_before["keep"]
    assert d_after["control"] == d_before["control"]

    harness.assert_logs_match(ref, _logs(rB), keys=STREAM_KEYS,
                              ctx=f"kill-resume {method}/{family}/{kind}")


def test_restore_without_checkpoint_is_fresh_start(detectors, tmp_path):
    scfg, trace, faults = _stream_inputs(8, "camera_flap")
    runner = _runner(detectors, scfg, "static",
                     StreamConfig(window_slots=8, ckpt_dir=str(tmp_path)))
    assert not runner.restore()              # empty dir -> fresh start
    assert runner.window == 0 and runner.t_next == 0


# -- SLO watchdog ladder -------------------------------------------------------

def test_watchdog_ladder_degrades_recovers_exactly(detectors):
    """Injected straggler walls drive the ladder episode ->
    episode_small -> pipelined; healthy walls climb it back.  Every rung
    threads the SAME carry chain, so the mixed-rung stream's logs STILL
    match the uninterrupted episode reference."""
    T, WIN = 40, 4
    scfg, trace, faults = _stream_inputs(T, "camera_flap")
    ref = _continuous_reference(detectors, scfg, trace, faults, "deepstream")

    # synthetic turnaround schedule (seconds), indexed by window: healthy
    # baseline 1.0 with straggler spikes at windows 2 and 4
    walls = {2: 6.0, 4: 6.0}

    cfg = StreamConfig(
        window_slots=WIN, queue_slots=T, recover_after=2,
        watchdog=WatchdogConfig(warmup_steps=1, escalate_after=1))
    runner = _runner(detectors, scfg, "deepstream", cfg,
                     wall_hook=lambda w, wall: walls.get(w, 1.0))
    runner.offer(trace, faults=faults)
    runner.serve(flush=True)

    kinds = [(e["kind"], e.get("to")) for e in runner.events
             if e["kind"] in ("degrade", "recover")]
    assert kinds == [("degrade", "episode_small"),
                     ("degrade", "pipelined"),
                     ("recover", "episode_small"),
                     ("recover", "episode")]
    assert runner.rung == 0 and runner.stats()["rung"] == LADDER[0]
    # ladder exactness: rung changes are numerically invisible
    harness.assert_logs_match(ref, _logs(runner), keys=STREAM_KEYS,
                              ctx="ladder")


def test_watchdog_rebaseline_on_rung_change(detectors):
    """After a degrade, the new rung's own (slower or faster) walls are a
    fresh warmup — the old rung's baseline never mis-gates them into an
    immediate second degrade."""
    T, WIN = 24, 4
    scfg, trace, faults = _stream_inputs(T, "camera_flap")
    # one spike degrades at window 2; the NEW rung then runs steadily at
    # 3x the old baseline — rebaseline makes that its normal
    def wall_hook(w, wall):
        return 6.0 if w == 2 else (3.0 if w > 2 else 1.0)

    cfg = StreamConfig(
        window_slots=WIN, queue_slots=T, recover_after=100,
        watchdog=WatchdogConfig(warmup_steps=1, escalate_after=1))
    runner = _runner(detectors, scfg, "static", cfg, wall_hook=wall_hook)
    runner.offer(trace, faults=faults)
    runner.serve(flush=True)
    degrades = [e for e in runner.events if e["kind"] == "degrade"]
    assert len(degrades) == 1 and runner.rung == 1


def test_supervisor_recovers_and_rebaselines(detectors):
    """Regression (ladder recovery): ``EpisodeSupervisor`` must climb BACK
    one rung after ``recover_after`` consecutive healthy runs at a degraded
    rung, and EVERY rung change — degrade or recover — must rebaseline the
    watchdog so the new rung's EMA is never seeded from the other rung's
    wall times.  Pre-fix the supervisor never recovered and never
    rebaselined."""
    class _ScriptedDog:
        """Scripted verdicts + a rebaseline call counter."""
        def __init__(self, verdicts):
            self.verdicts = list(verdicts)
            self.rebaselines = 0

        def record(self, step, t):
            return self.verdicts.pop(0)

        def rebaseline(self):
            self.rebaselines += 1

    scfg, trace, faults = _stream_inputs(2, "none")
    s = harness.build_system(detectors, "episode", scfg)
    s._key = jax.random.PRNGKey(1234)
    sup = sched_mod.EpisodeSupervisor(
        s, sched_mod.SupervisorConfig(recover_after=2))
    dog = _ScriptedDog(["replace", "ok", "ok", "ok"])
    sup.watchdog = dog
    scene = DeviceScene(scfg)
    for _ in range(4):
        sup.run(scene, trace, method="static", faults=faults)

    kinds = [(e["kind"], e.get("to")) for e in sup.events
             if e["kind"] in ("degrade", "recover")]
    assert kinds == [("degrade", "episode_chunked"), ("recover", "episode")]
    assert sup.mode == "episode"             # climbed back to the fast rung
    # one rebaseline per rung change: the watchdog degrade + the recovery
    assert dog.rebaselines == 2
    # run 4 happened back at the fast rung with a FRESH streak
    assert sup._ok_streak == 0 or sup._rung == 0


def test_recovered_watchdog_baseline_not_seeded_from_degraded_rung():
    """The seeding contract the supervisor's rebaseline call exists for: a
    recovered (faster) rung gated against the degraded rung's 5x walls
    would MASK real stragglers; a fresh warmup catches them."""
    from repro.ft import watchdog as ft_watchdog
    cfg = WatchdogConfig(warmup_steps=1, escalate_after=1)

    poisoned = ft_watchdog.Watchdog(cfg)
    fresh = ft_watchdog.Watchdog(cfg)
    for i in range(6):
        poisoned.record(i, 5.0)              # degraded-rung walls
        fresh.record(i, 5.0)
    fresh.rebaseline()                       # what recovery must do
    for dog in (poisoned, fresh):
        assert dog.record(10, 1.0) == "ok"   # healthy-rung walls
        assert dog.record(11, 1.0) == "ok"
    # a genuine healthy-rung straggler (4x): the poisoned baseline masks
    # it, the rebaselined one trips
    assert poisoned.record(12, 4.0) == "ok"
    assert fresh.record(12, 4.0) == "replace"


# -- bounded ingest + drop accounting ------------------------------------------

def test_bounded_queue_drops_and_restores_accounting(detectors, tmp_path):
    scfg, trace, faults = _stream_inputs(12, "camera_flap")
    cfg = StreamConfig(window_slots=8, queue_slots=8, ckpt_dir=str(tmp_path))
    runner = _runner(detectors, scfg, "static", cfg)

    # 12 slots into an 8-slot queue: 8 accepted, 4 shed and counted
    assert runner.offer(trace, faults=faults) == 8
    assert runner.dropped_slots == 4
    assert any(e["kind"] == "drop" and e["slots"] == 4
               for e in runner.events)
    assert runner.serve() == 1
    runner.saver.wait()

    # the shed-load count is part of the serving record: it survives
    # checkpoint/restore like everything else
    r2 = _runner(detectors, scfg, "static", cfg)
    assert r2.restore()
    assert r2.dropped_slots == 4 and r2.window == 1
    assert len(r2.logs["W"]) == 8
    # freed queue space: a re-offer of the tail is accepted now
    assert r2.offer(trace[r2.t_next:], faults=faults[r2.t_next:]) == 4


def test_offer_rejects_bad_fault_shape(detectors):
    scfg, trace, _ = _stream_inputs(8, "camera_flap")
    runner = _runner(detectors, scfg, "static", StreamConfig(window_slots=8))
    with pytest.raises(ValueError, match="faults mask"):
        runner.offer(trace, faults=np.ones((len(trace), 99), bool))


def test_stream_requires_pinned_capacity(detectors):
    scfg = _scene_cfg()
    s = harness.build_system(detectors, "episode", scfg, w_cap_kbps=None)
    with pytest.raises(ValueError, match="w_cap_kbps"):
        StreamingFleetRunner(s, DeviceScene(scfg))


# -- soak ----------------------------------------------------------------------

def test_soak_zero_recompiles_bounded_d2h(detectors):
    """A diurnal soak stream (env-scalable; the 1000-slot version runs in
    benchmarks/bench_serve.py and the chaos headline soak): after the
    warmup window, ZERO episode recompiles and exactly 2 harvest fetches
    per window — serving cost per window is flat no matter how long the
    stream runs — and (ROADMAP item 5) the post-warmup peak-RSS delta is
    bounded (``REPRO_SOAK_RSS_MB``): an always-on service must not grow
    host memory with stream length."""
    import resource
    slots = int(os.environ.get("REPRO_SOAK_SLOTS", "48"))
    rss_ceiling_mb = float(os.environ.get("REPRO_SOAK_RSS_MB", "768"))
    WIN = 8
    scfg = _scene_cfg()
    trace, live = make_soak_stream(slots, num_cams=scfg.num_cameras)

    runner = _runner(detectors, scfg, "deepstream",
                     StreamConfig(window_slots=WIN, queue_slots=WIN,
                                  degrade=False))
    # warmup: first window may compile the (method, bucket) executable
    runner.offer(trace[:WIN], faults=live[:WIN])
    runner.serve()
    n0 = fleet_mod.episode_compile_count()
    d0 = sched_mod.d2h_fetch_counts()
    rss0_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

    t = runner.t_next
    while t < slots:
        t += runner.offer(trace[t:t + WIN], faults=live[t:t + WIN])
        runner.serve()
    runner.serve(flush=True)

    d1 = sched_mod.d2h_fetch_counts()
    post_warmup = runner.window - 1
    assert fleet_mod.episode_compile_count() == n0
    assert d1["harvest"] - d0["harvest"] == 2 * post_warmup
    assert d1["keep"] == d0["keep"] and d1["control"] == d0["control"]

    rss_delta_mb = (resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                    - rss0_kb) / 1024.0
    assert rss_delta_mb <= rss_ceiling_mb, \
        f"post-warmup peak RSS grew {rss_delta_mb:.0f} MB " \
        f"(> {rss_ceiling_mb:.0f} MB) over {slots} slots"

    st = runner.stats()
    assert st["slots"] == slots and st["dropped_slots"] == 0
    assert st["quarantined_slots"] == 0 and st["gap_filled_slots"] == 0
    assert st["windows"] == runner.window and st["slots_per_s"] > 0


# -- ServeEngine drain budget (admission starvation) ---------------------------

def test_serve_engine_drain_budget_names_stuck_slots():
    """Regression: an admission-starved serve loop must raise a diagnosable
    error naming the stuck slots and the un-admitted backlog, not hang."""
    from repro.configs import smoke_config
    from repro.models.model import LM
    from repro.serve.engine import Request, ServeEngine
    cfg = smoke_config("granite-8b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    prompt = np.arange(8, dtype=np.int32) % cfg.vocab_size
    eng = ServeEngine(lm, params, batch_slots=1, max_seq=32)
    reqs = [Request(rid=i, prompt=prompt, max_new_tokens=6)
            for i in range(2)]
    with pytest.raises(RuntimeError) as exc:
        eng.run(reqs, max_steps=3)
    msg = str(exc.value)
    assert "did not drain in 3 steps" in msg
    assert "1 request(s) never admitted" in msg
    # prefill emits the first token, so 3 steps leave 4/6 emitted
    assert "slot 0: rid=0" in msg and "emitted=4/6" in msg

    # with the default budget the same load drains fine
    eng2 = ServeEngine(lm, params, batch_slots=1, max_seq=32)
    reqs2 = [Request(rid=i, prompt=prompt, max_new_tokens=6)
             for i in range(2)]
    stats = eng2.run(reqs2)
    assert stats["requests"] == 2
