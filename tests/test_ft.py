"""Unit tests for ``repro.ft.watchdog``: the EMA+sigma straggler gate, the
simulated fleet it is exercised against, and the preemption-aware
checkpointer.  (The gate's integration with episode dispatch, the stream
runner's degraded-mode ladder, is covered by tests/test_serve_stream.py.)"""
import signal

import numpy as np
import pytest

from repro.ft.watchdog import (PreemptionCheckpointer, SimulatedFleet,
                               Watchdog, WatchdogConfig)


def _feed_healthy(wd: Watchdog, n: int, base: float = 0.1,
                  start: int = 0) -> None:
    for i in range(n):
        # deterministic small jitter keeps sigma > 0 without tripping
        assert wd.record(start + i, base * (1 + 0.01 * ((i % 3) - 1))) == "ok"


def test_watchdog_warmup_immunity():
    wd = Watchdog(WatchdogConfig(warmup_steps=5))
    # a huge compile-time outlier inside warmup must not count
    assert wd.record(0, 30.0) == "ok"
    for i in range(1, 5):
        assert wd.record(i, 0.1) == "ok"
    assert wd.stats.violations == 0 and not wd.stats.events


def test_watchdog_detect_escalate_recover():
    cfg = WatchdogConfig(warmup_steps=5, escalate_after=3)
    wd = Watchdog(cfg)
    _feed_healthy(wd, 10)
    # sustained straggling: two flags, then escalation to 'replace'
    assert wd.record(10, 1.0) == "straggler"
    assert wd.record(11, 1.0) == "straggler"
    assert wd.record(12, 1.0) == "replace"
    assert [e["status"] for e in wd.stats.events] == \
        ["straggler", "straggler", "replace"]
    # a healthy step resets the consecutive-violation counter...
    assert wd.record(13, 0.1) == "ok"
    assert wd.stats.violations == 0
    # ...so the next violation is a fresh 'straggler', not 'replace'
    assert wd.record(14, 1.0) == "straggler"


def test_watchdog_stragglers_do_not_poison_baseline():
    wd = Watchdog(WatchdogConfig(warmup_steps=5))
    _feed_healthy(wd, 10)
    ema_before = wd.stats.ema
    for i in range(3):
        wd.record(10 + i, 5.0)
    # only healthy steps update the EMA — else a slow patch raises the
    # threshold until stragglers look normal
    assert wd.stats.ema == ema_before


def test_simulated_fleet_straggler_and_death():
    fleet = SimulatedFleet(4, base_step_time=0.1, seed=0)
    t = fleet.step_times()
    assert t.shape == (4,) and np.all(t > 0) and np.all(np.isfinite(t))
    fleet.inject_straggler(2, factor=5.0)
    t = fleet.step_times()
    assert t[2] > 2 * t[[0, 1, 3]].max()
    fleet.kill(1)
    assert np.isinf(fleet.step_times()[1])
    # SPMD: the fleet runs at the slowest live worker's pace — a dead
    # worker stalls the step entirely
    assert np.isinf(fleet.synchronous_step_time())


def test_simulated_fleet_drives_watchdog_to_replace():
    fleet = SimulatedFleet(4, base_step_time=0.1, seed=1)
    wd = Watchdog(WatchdogConfig(warmup_steps=5, escalate_after=3))
    for i in range(12):
        assert wd.record(i, fleet.synchronous_step_time()) == "ok"
    fleet.inject_straggler(3, factor=10.0)
    verdicts = [wd.record(12 + i, fleet.synchronous_step_time())
                for i in range(3)]
    assert verdicts == ["straggler", "straggler", "replace"]


def test_checkpointer_periodic_saves():
    saved = []
    ckpt = PreemptionCheckpointer(saved.append, every=3,
                                  install_signal=False)
    for step in range(1, 8):
        ckpt.maybe_save(step)
    assert saved == [3, 6]


def test_checkpointer_sigterm_saves_now_and_exits():
    saved = []
    with PreemptionCheckpointer(saved.append, every=100,
                                install_signal=True) as ckpt:
        assert not ckpt.maybe_save(1)       # far from a periodic save
        signal.raise_signal(signal.SIGTERM)  # spot preemption notice
        assert ckpt.preempted
        with pytest.raises(SystemExit) as exc:
            ckpt.maybe_save(2)
        assert exc.value.code == 143 and saved == [2]


def test_checkpointer_sigint_saves_now_and_exits():
    # Ctrl-C / SIGINT is a preemption notice too: save now, exit 130 — and
    # Python's default KeyboardInterrupt handler must NOT be chained (it
    # would raise inside our handler and abort the graceful save)
    saved = []
    with PreemptionCheckpointer(saved.append, every=100,
                                install_signal=True) as ckpt:
        signal.raise_signal(signal.SIGINT)   # no KeyboardInterrupt raised
        assert ckpt.preempted and ckpt.preempt_signum == signal.SIGINT
        with pytest.raises(SystemExit) as exc:
            ckpt.maybe_save(1)
        assert exc.value.code == 130 and saved == [1]


def test_checkpointer_chains_and_restores_previous_handler():
    hits = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: hits.append(s))
    try:
        ckpt = PreemptionCheckpointer([].append, every=100,
                                      install_signal=True)
        signal.raise_signal(signal.SIGTERM)
        # our handler ran AND chained the pre-existing one
        assert ckpt.preempted and hits == [signal.SIGTERM]
        ckpt.close()
        # close() put the displaced handler back
        assert signal.getsignal(signal.SIGTERM) is not ckpt._on_signal
        signal.raise_signal(signal.SIGTERM)
        assert hits == [signal.SIGTERM] * 2
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_watchdog_rebaseline_keeps_events_resets_baseline():
    cfg = WatchdogConfig(warmup_steps=5, escalate_after=3)
    wd = Watchdog(cfg)
    _feed_healthy(wd, 10)
    wd.record(10, 1.0)
    events_before = list(wd.stats.events)
    assert events_before
    wd.rebaseline()
    # the event log survives; the EMA baseline and counters do not —
    # a mode change (supervisor rung switch) is a fresh warmup
    assert wd.stats.events == events_before
    assert wd.stats.count == 0 and wd.stats.ema == 0.0
    # the new mode's 10x-slower steps are warmup, not stragglers
    for i in range(cfg.warmup_steps):
        assert wd.record(11 + i, 1.0) == "ok"
    assert wd.record(16, 1.0) == "ok"
