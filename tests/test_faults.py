"""Fault-tolerance tests: traced camera churn, link-fault injection and
checkify-guarded invariants.  (The watchdog recovery ladder is the stream
runner's, covered in tests/test_serve_stream.py.)

The contract under test (``fleet.fleet_episode`` / ``scheduler.run``
docstrings): a dead (camera, slot) cell is an *inert camera* — zero bits and
zero bytes, excluded from every allocator, no reducto-reference advance —
and a reconnect re-seeds the reference and clears elastic debt.  Liveness is
traced DATA, so fault episodes reuse the fault-free executables (zero
recompiles) and keep the episode path's zero-per-slot-transfer guarantee.

Headline differential: a fleet with one camera dead for the WHOLE trace must
log identically (<= 1e-5) to a fleet that never had that camera — across all
four methods, both fault-capable runner modes and the windowed stream
runner.  The absent fleet's
scene params are ROW-SLICED from the full fleet's (not re-drawn at C-1:
``init_device_scene`` consumes rng per camera, so a fresh (C-1)-camera scene
has different geometry).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from repro.core import allocation, elastic
from repro.core import fleet as fleet_mod
from repro.core import scheduler as sched_mod
from repro.data import scenarios
from repro.data.scenarios import make_faults, make_trace
from repro.data.synthetic import DeviceScene, DeviceSceneParams, SceneConfig
from repro.serve.stream import StreamConfig, StreamingFleetRunner

C = 3          # full fleet size (absent fleet = C - 1)
T = 4          # fits the first episode bucket

FAULT_MODES = ("batched", "episode")


def _scene_cfg(num_cameras: int = C, seed: int = 33) -> SceneConfig:
    return SceneConfig(seed=seed, num_cameras=num_cameras)


@pytest.fixture(scope="module")
def systems(detectors):
    """One full-fleet (C-camera) system per fault-capable runner mode —
    shared by every test so compiled programs are reused across cells."""
    return {m: harness.build_system(detectors, m, _scene_cfg())
            for m in FAULT_MODES}


@pytest.fixture(scope="module")
def absent_systems(detectors):
    """(C-1)-camera reference systems for the dead==absent differential."""
    return {m: harness.build_system(detectors, m, _scene_cfg(C - 1))
            for m in FAULT_MODES}


def _paired_scenes(seed: int = 33):
    """A C-camera scene plus the (C-1)-camera scene holding EXACTLY its
    first C-1 cameras: params row-sliced, same key, shared objects."""
    full = DeviceScene(_scene_cfg(C, seed))
    absent = DeviceScene(_scene_cfg(C - 1, seed))
    p = full.params
    absent.params = DeviceSceneParams(
        p.backgrounds[:C - 1], p.stat_boxes[:C - 1], p.stat_valid[:C - 1],
        p.offsets[:C - 1], p.lags[:C - 1], p.cam_ids[:C - 1], p.objects)
    absent.key = full.key
    return full, absent


def _run(system, scene, trace, method="deepstream", **kw):
    """Fixed-key run (harness.run_cell's key pin, custom scene)."""
    system._key = jax.random.PRNGKey(1234)
    return system.run(scene, trace, method=method, **kw)


# ---------------------------------------------------------------------------
# fault-family contracts (pure data, no fleet)
# ---------------------------------------------------------------------------

def test_fault_families_contract():
    for name in scenarios.fault_families():
        m1 = make_faults(name, 12, 4, seed=3)
        m2 = make_faults(name, 12, 4, seed=3)
        np.testing.assert_array_equal(m1, m2)       # pure in (name, seed)
        assert m1.dtype == np.bool_ and m1.shape == (12, 4)
        assert m1.any(axis=1).all()                  # >= 1 live per slot
    assert make_faults("none", 6, 3).all()
    dead = make_faults("dead_camera", 6, 3)
    assert not dead[:, -1].any() and dead[:, :-1].all()


def test_fault_anchor_camera_immune():
    # camera 0 is the >= 1-live-per-slot guarantee in every family
    for name in scenarios.fault_families():
        for seed in range(5):
            assert make_faults(name, 20, 4, seed=seed)[:, 0].all(), \
                f"{name} seed={seed} killed the anchor camera"


def test_make_faults_validates_contract(monkeypatch):
    monkeypatch.setitem(scenarios.FAULT_FAMILIES, "all_dead",
                        lambda rng, T_, C_: np.zeros((T_, C_), bool))
    with pytest.raises(ValueError, match="liveness"):
        make_faults("all_dead", 4, 3)


def test_hard_outage_trace_has_true_zero_slots():
    tr = make_trace("hard_outage", 64, seed=0, num_cams=C)
    assert (tr == 0.0).any(), "hard_outage must contain 0-Kbps slots"
    nz = tr[tr > 0.0]
    assert (nz >= scenarios.FLOOR_KBPS).all()
    # camera-count rescale preserves the zeros exactly
    tr1 = make_trace("hard_outage", 64, seed=0, num_cams=1)
    np.testing.assert_array_equal(tr == 0.0, tr1 == 0.0)


# ---------------------------------------------------------------------------
# run()-level validation
# ---------------------------------------------------------------------------

def test_run_rejects_malformed_faults(systems, detectors):
    s = systems["batched"]
    scene = DeviceScene(_scene_cfg())
    trace = make_trace("fcc_medium", 3, seed=8, num_cams=C)
    with pytest.raises(ValueError, match="must be"):
        s.run(scene, trace, faults=np.ones((2, C), bool))
    dark = np.ones((3, C), bool)
    dark[1] = False
    with pytest.raises(ValueError, match="zero live"):
        s.run(scene, trace, faults=dark)
    seq = harness.build_system(detectors, "sequential", _scene_cfg())
    with pytest.raises(NotImplementedError, match="batched or"):
        seq.run(scene, trace, faults=np.ones((3, C), bool))


def test_slot_camera_keys_fleet_size_independent():
    # the fold-in scheme is what makes dead==absent possible: camera i's
    # coding noise cannot depend on how many cameras the fleet has
    k = jax.random.PRNGKey(7)
    big = np.asarray(fleet_mod.slot_camera_keys(k, 3, np.arange(5)))
    small = np.asarray(fleet_mod.slot_camera_keys(k, 3, np.arange(3)))
    np.testing.assert_array_equal(big[:3], small)
    other_t = np.asarray(fleet_mod.slot_camera_keys(k, 4, np.arange(3)))
    assert not np.array_equal(small, other_t)


# ---------------------------------------------------------------------------
# the headline differential: dead camera == fleet that never had it
# ---------------------------------------------------------------------------

def _stream(system, scene, trace, method, faults=None):
    """Serve ``trace`` through the windowed stream runner over two
    windows, the device carry handed across the boundary."""
    system._key = jax.random.PRNGKey(1234)
    runner = StreamingFleetRunner(system, scene, method=method,
                                  cfg=StreamConfig(window_slots=T // 2))
    runner.offer(trace, faults=faults)
    assert runner.serve() == 2
    return {k: np.asarray(v) for k, v in runner.logs.items()}


@pytest.mark.parametrize("mode", FAULT_MODES + ("stream",))
@pytest.mark.parametrize("method", harness.METHODS)
def test_dead_camera_equals_absent(systems, absent_systems, mode, method):
    full_scene, absent_scene = _paired_scenes()
    trace = make_trace("fcc_medium", T, seed=8, num_cams=C)
    faults = np.ones((T, C), bool)
    faults[:, C - 1] = False
    if mode == "stream":
        got = _stream(systems["episode"], full_scene, trace, method,
                      faults=faults)
        ref = _stream(absent_systems["episode"], absent_scene, trace, method)
    else:
        got = _run(systems[mode], full_scene, trace, method=method,
                   faults=faults)
        ref = _run(absent_systems[mode], absent_scene, trace, method=method)
    harness.assert_logs_match(ref, got, tol=1e-5,
                              ctx=f"dead!=absent mode={mode} {method}")


# ---------------------------------------------------------------------------
# cross-mode equivalence under churn/flap/corruption
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family",
                         ("camera_churn", "camera_flap", "sensor_corrupt"))
def test_fault_cross_mode_equivalence(systems, family):
    faults = make_faults(family, T, C, seed=4)
    trace = make_trace("fcc_medium", T, seed=8, num_cams=C)
    logs = {m: _run(systems[m], DeviceScene(_scene_cfg()), trace,
                    faults=faults)
            for m in FAULT_MODES}
    harness.assert_logs_match(logs["batched"], logs["episode"],
                              ctx=f"{family} batched-vs-episode")


def test_fault_episode_stays_device_resident(systems):
    """Fault episodes keep the episode contract: zero per-slot keep/control
    fetches, exactly two harvest fetches per run, and — once warm — zero
    recompiles when only the fault mask changes (liveness is traced data)."""
    s = systems["episode"]
    trace = make_trace("fcc_medium", T, seed=8, num_cams=C)
    _run(s, DeviceScene(_scene_cfg()), trace,
         faults=make_faults("camera_churn", T, C, seed=2))      # warm
    before = sched_mod.d2h_fetch_counts()
    compiles = (fleet_mod.compile_count(), fleet_mod.episode_compile_count())
    _run(s, DeviceScene(_scene_cfg()), trace,
         faults=make_faults("camera_churn", T, C, seed=9))
    after = sched_mod.d2h_fetch_counts()
    assert after["keep"] == before["keep"]
    assert after["control"] == before["control"]
    assert after["harvest"] - before["harvest"] == 2
    assert (fleet_mod.compile_count(),
            fleet_mod.episode_compile_count()) == compiles


# ---------------------------------------------------------------------------
# zero-capacity hardening (hard_outage slots)
# ---------------------------------------------------------------------------

def test_allocators_zero_capacity_all_zero_infeasible():
    bitrates = (100, 200, 400, 800)
    I = 3
    rng = np.random.default_rng(0)
    util = rng.uniform(0.1, 1.0, (I, len(bitrates))).astype(np.float32)
    util.sort(axis=1)
    best_res = np.ones((I, len(bitrates)), np.float32)
    for name, alloc in (
            ("dp", allocation.allocate_dp(util, best_res, bitrates, 0.0)),
            ("greedy", allocation.allocate_greedy(util, best_res, bitrates,
                                                  0.0)),
            ("fair", allocation.allocate_fair(bitrates, 0.0, I))):
        assert not alloc.feasible, name
        np.testing.assert_array_equal(alloc.bitrates_kbps, 0.0, err_msg=name)

    w_cap = allocation.trace_capacity(bitrates, np.array([8000.0]), I)
    W0 = jnp.float32(0.0)
    _, b, _, total, feas = allocation.allocate_dp_jax(
        jnp.asarray(util), jnp.asarray(best_res), bitrates, W0, w_cap=w_cap)
    np.testing.assert_array_equal(np.asarray(b), 0.0)
    assert not bool(feas) and float(total) == 0.0
    _, b, _, total, feas = allocation.allocate_greedy_jax(
        jnp.asarray(util), jnp.asarray(best_res), bitrates, W0)
    np.testing.assert_array_equal(np.asarray(b), 0.0)
    assert not bool(feas) and float(total) == 0.0
    b, feas = allocation.allocate_fair_jax(bitrates, W0, I)
    np.testing.assert_array_equal(np.asarray(b), 0.0)
    assert not bool(feas)


def test_zero_capacity_slot_sends_nothing(systems):
    trace = make_trace("fcc_medium", T, seed=8, num_cams=C).copy()
    trace[1] = 0.0          # one hard_outage-style slot mid-trace
    for mode in FAULT_MODES:
        # elastic off: WITH elastic a hard-outage slot may legitimately
        # borrow against the debt budget (W_eff = W + extra > 0) — the
        # zero-capacity clamp is about true zero effective capacity
        logs = _run(systems[mode], DeviceScene(_scene_cfg()), trace,
                    use_elastic=False)
        assert logs["alloc_kbps"][1] == 0.0, mode
        for k in harness.LOG_KEYS:
            assert np.isfinite(logs[k]).all(), (mode, k)


# ---------------------------------------------------------------------------
# elastic reconnect clamp
# ---------------------------------------------------------------------------

def test_elastic_reset_debt_host_and_jax_agree():
    cfg = elastic.ElasticConfig()
    tau_wl, tau_wh = 900.0, 2000.0
    st = elastic.ElasticState(a_ema=0.1, a_var=0.0, debt_kbits=400.0,
                              initialized=True)
    stj = elastic.ElasticStateJax(
        a_ema=jnp.float32(0.1), a_var=jnp.float32(0.0),
        debt_kbits=jnp.float32(400.0), initialized=jnp.asarray(True))
    # high-area low-bandwidth slot: borrows either way, but a reconnect
    # clears the 400 Kbit of pre-fault debt first
    for reset in (False, True):
        h_st, h_extra, _ = elastic.update(cfg, st, 0.9, 500.0, tau_wl,
                                          tau_wh, reset_debt=reset)
        j_st, j_extra, _ = elastic.update_jax(
            cfg, stj, jnp.float32(0.9), jnp.float32(500.0),
            jnp.float32(tau_wl), jnp.float32(tau_wh),
            reset_debt=jnp.asarray(reset))
        np.testing.assert_allclose(float(j_extra), h_extra, rtol=1e-6)
        np.testing.assert_allclose(float(j_st.debt_kbits), h_st.debt_kbits,
                                   rtol=1e-6)
    # and the clamp actually freed budget: reset borrows more
    _, extra_keep, _ = elastic.update(cfg, st, 0.9, 500.0, tau_wl, tau_wh)
    _, extra_reset, _ = elastic.update(cfg, st, 0.9, 500.0, tau_wl, tau_wh,
                                       reset_debt=True)
    assert extra_reset >= extra_keep


# ---------------------------------------------------------------------------
# checkify-guarded invariants
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def checked_systems(detectors):
    out = {}
    for mode in FAULT_MODES:
        s = harness.build_system(detectors, mode, _scene_cfg())
        s.cfg.checked = True
        s.cfg.__post_init__()       # re-derive the forced-off knobs
        s.mesh = None
        out[mode] = s
    return out


@pytest.mark.parametrize("mode", FAULT_MODES)
def test_checked_run_matches_unchecked(systems, checked_systems, mode):
    trace = make_trace("fcc_medium", T, seed=8, num_cams=C)
    faults = make_faults("camera_churn", T, C, seed=4)
    ref = _run(systems[mode], DeviceScene(_scene_cfg()), trace,
               faults=faults)
    got = _run(checked_systems[mode], DeviceScene(_scene_cfg()), trace,
               faults=faults)
    harness.assert_logs_match(ref, got, ctx=f"checked {mode}")


@pytest.mark.parametrize("mode", FAULT_MODES)
def test_checked_run_catches_nonfinite_bandwidth(checked_systems, mode):
    trace = make_trace("fcc_medium", T, seed=8, num_cams=C).copy()
    trace[2] = np.nan
    with pytest.raises(Exception, match="(?i)finite|bandwidth"):
        _run(checked_systems[mode], DeviceScene(_scene_cfg()), trace)
