"""Device-resident control: traced elastic/allocation equivalence vs the
numpy reference, and full-loop device-vs-host log equivalence (the episode
scan against the host-control batched loop) for all four methods under
camera churn."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import allocation as alloc
from repro.core import codec as codec_mod
from repro.core import elastic as elastic_mod
from repro.core.codec import CodecConfig
from repro.core.elastic import ElasticConfig, ElasticState
from repro.core.scheduler import DeepStreamSystem, SystemConfig
from repro.data.scenarios import make_faults
from repro.data.synthetic import (DeviceScene, MultiCameraScene, SceneConfig,
                                  bandwidth_trace)


# ---------------------------------------------------------------------------
# elastic controller (section 5.3)
# ---------------------------------------------------------------------------

def test_elastic_jax_first_slot_initializes():
    cfg = ElasticConfig()
    st, extra, log = elastic_mod.update_jax(
        cfg, elastic_mod.init_state_jax(), jnp.float32(2.5), jnp.float32(400),
        jnp.float32(600), jnp.float32(900))
    assert float(extra) == 0.0
    assert float(st.a_ema) == pytest.approx(2.5)
    assert float(st.a_var) == 0.0
    assert float(st.debt_kbits) == 0.0
    assert bool(st.initialized)
    assert not np.isfinite(float(log["tau_a"]))   # host path logs inf too


def test_elastic_jax_borrow_clamped_by_budget():
    cfg = ElasticConfig(gamma_a=0.5, gamma_wl=50.0, budget_kbits=80.0)
    upd = jax.jit(functools.partial(elastic_mod.update_jax, cfg))
    st = elastic_mod.init_state_jax()
    for _ in range(4):   # settle the EMA on a calm area signal
        st, _, _ = upd(st, jnp.float32(1.0), jnp.float32(500),
                       jnp.float32(600), jnp.float32(900))
    st, extra, log = upd(st, jnp.float32(5.0), jnp.float32(300),
                         jnp.float32(600), jnp.float32(900))
    assert float(extra) > 0
    # gamma_wl * (600-300) = 15000 Kbit wanted, clamped to the 80 budget
    assert float(st.debt_kbits) == pytest.approx(cfg.budget_kbits, abs=1e-5)
    assert float(log["borrowed"]) == pytest.approx(cfg.budget_kbits, abs=1e-5)


def test_elastic_jax_repay_drains_debt():
    cfg = ElasticConfig(gamma_wl=50.0, budget_kbits=80.0)
    upd = jax.jit(functools.partial(elastic_mod.update_jax, cfg))
    st = elastic_mod.init_state_jax()
    for _ in range(4):
        st, _, _ = upd(st, jnp.float32(1.0), jnp.float32(500),
                       jnp.float32(600), jnp.float32(900))
    st, _, _ = upd(st, jnp.float32(5.0), jnp.float32(300),
                   jnp.float32(600), jnp.float32(900))
    assert float(st.debt_kbits) > 0
    # repay is capped by the surplus above tau_wh...
    st, extra, log = upd(st, jnp.float32(1.0), jnp.float32(920),
                         jnp.float32(600), jnp.float32(900))
    assert float(extra) == pytest.approx(-20.0, abs=1e-4)
    assert float(st.debt_kbits) == pytest.approx(60.0, abs=1e-4)
    # ...and a big surplus drains the debt to exactly zero, then stops
    st, extra2, _ = upd(st, jnp.float32(1.0), jnp.float32(2000),
                        jnp.float32(600), jnp.float32(900))
    assert float(extra2) == pytest.approx(-60.0, abs=1e-4)
    assert float(st.debt_kbits) == 0.0
    st, extra3, _ = upd(st, jnp.float32(1.0), jnp.float32(2000),
                        jnp.float32(600), jnp.float32(900))
    assert float(extra3) == 0.0


def test_elastic_jax_matches_numpy_reference():
    """Traced controller == numpy reference over random (area, W) traces."""
    cfg = ElasticConfig(budget_kbits=120.0, gamma_wl=2.0)
    upd = jax.jit(functools.partial(elastic_mod.update_jax, cfg))
    rng = np.random.default_rng(3)
    st_np, st_j = ElasticState(), elastic_mod.init_state_jax()
    for t in range(80):
        area = float(rng.uniform(0.2, 4.0))
        W = float(rng.uniform(100, 1500))
        st_np, ex_np, log_np = elastic_mod.update(cfg, st_np, area, W,
                                                  700.0, 1000.0)
        st_j, ex_j, log_j = upd(st_j, jnp.float32(area), jnp.float32(W),
                                jnp.float32(700.0), jnp.float32(1000.0))
        assert float(ex_j) == pytest.approx(ex_np, abs=1e-3), t
        assert float(st_j.a_ema) == pytest.approx(st_np.a_ema, abs=1e-4), t
        assert float(st_j.a_var) == pytest.approx(st_np.a_var, abs=1e-4), t
        assert float(st_j.debt_kbits) == pytest.approx(st_np.debt_kbits,
                                                       abs=1e-3), t
        # the host reference only logs debt after the first-slot init
        assert float(log_j["debt"]) == pytest.approx(
            log_np.get("debt", 0.0), abs=1e-3), t


def test_elastic_scan_matches_stepwise():
    """The lax.scan-over-slots variant reproduces the per-slot updates."""
    cfg = ElasticConfig(budget_kbits=90.0, gamma_wl=3.0)
    rng = np.random.default_rng(11)
    areas = rng.uniform(0.2, 4.0, 30).astype(np.float32)
    Ws = rng.uniform(100, 1500, 30).astype(np.float32)
    upd = jax.jit(functools.partial(elastic_mod.update_jax, cfg))
    st = elastic_mod.init_state_jax()
    extras = []
    for a, W in zip(areas, Ws):
        st, ex, _ = upd(st, jnp.float32(a), jnp.float32(W),
                        jnp.float32(700.0), jnp.float32(1000.0))
        extras.append(float(ex))
    st2, extras2 = elastic_mod.update_scan(
        cfg, elastic_mod.init_state_jax(), areas, Ws, jnp.float32(700.0),
        jnp.float32(1000.0))
    np.testing.assert_allclose(np.asarray(extras2), extras, atol=1e-5)
    assert float(st2.debt_kbits) == pytest.approx(float(st.debt_kbits),
                                                  abs=1e-5)


# ---------------------------------------------------------------------------
# traced allocators vs host references
# ---------------------------------------------------------------------------

BITR = [50, 100, 200, 400, 800, 1000]


def test_allocate_dp_jax_matches_host(rng):
    w_cap = alloc.dp_capacity(BITR, 6000.0)
    for use_kernel in (True, False):
        for trial in range(15):
            I = int(rng.integers(2, 8))
            util = rng.uniform(0, 1, (I, 6)).astype(np.float32)
            res = rng.choice([0.5, 0.75, 1.0], (I, 6)).astype(np.float32)
            W = float(rng.uniform(40, 5500))   # spans infeasible..saturated
            host = alloc.allocate_dp(util, res, BITR, W,
                                     use_kernel=use_kernel)
            _, b, r, total, feas = alloc.allocate_dp_jax(
                jnp.asarray(util), jnp.asarray(res), BITR, jnp.float32(W),
                w_cap=w_cap, use_kernel=use_kernel)
            np.testing.assert_array_equal(np.asarray(b), host.bitrates_kbps)
            np.testing.assert_array_equal(np.asarray(r), host.resolutions)
            assert float(total) == pytest.approx(host.predicted_utility,
                                                 abs=1e-5)
            assert bool(feas) == host.feasible, (use_kernel, trial)


def test_allocate_greedy_jax_matches_host(rng):
    for trial in range(20):
        I = int(rng.integers(1, 7))
        sat = float(rng.uniform(0.3, 0.95))
        util = np.minimum(np.sort(rng.uniform(0, 1, (I, 6)), axis=1),
                          sat).astype(np.float32)     # exact plateaus
        res = np.ones((I, 6), np.float32)
        W = float(rng.uniform(40, 4500))
        host = alloc.allocate_greedy(util, res, BITR, W)
        _, b, r, total, feas = alloc.allocate_greedy_jax(
            jnp.asarray(util), jnp.asarray(res), BITR, jnp.float32(W))
        assert float(total) == pytest.approx(host.predicted_utility,
                                             abs=1e-5), trial
        assert bool(feas) == host.feasible, trial
        assert float(np.asarray(b).sum()) <= max(W, BITR[0] * I) + 1e-6


def test_allocate_fair_reports_infeasibility():
    """Satellite regression: fair split returns an Allocation with
    ``feasible`` like its siblings instead of silently clamping."""
    al = alloc.allocate_fair(BITR, 620.0, 3)
    assert al.feasible and np.all(al.bitrates_kbps == 200)
    assert np.all(al.resolutions == 1.0)
    al = alloc.allocate_fair(BITR, 60.0, 3)    # W/I = 20 < every option
    assert not al.feasible and np.all(al.bitrates_kbps == 50)
    for W, want_feas in ((620.0, True), (60.0, False)):
        b, feas = alloc.allocate_fair_jax(BITR, jnp.float32(W), 3)
        host = alloc.allocate_fair(BITR, W, 3)
        assert bool(feas) == want_feas == host.feasible
        np.testing.assert_array_equal(np.asarray(b), host.bitrates_kbps)


def test_greedy_crosses_zero_gain_plateaus():
    """Satellite regression: a zero-gain (plateau) step must not block the
    positive-gain upgrade behind it — greedy now matches the DP here."""
    util = np.array([[0.5, 0.5, 0.9]], np.float32)
    res = np.ones((1, 3), np.float32)
    bitr = [50, 100, 200]
    gr = alloc.allocate_greedy(util, res, bitr, 200.0)
    dp = alloc.allocate_dp(util, res, bitr, 200.0)
    assert gr.predicted_utility == pytest.approx(dp.predicted_utility,
                                                 abs=1e-6)
    assert gr.bitrates_kbps[0] == 200.0


# ---------------------------------------------------------------------------
# full-loop device-vs-host equivalence under camera churn
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def control_pair(detectors):
    """Two systems over the same trained artifacts: the host-numpy control
    loop (batched) and the device-resident one (the episode scan)."""
    light, server = detectors
    pair = {}
    for episode in (False, True):
        cfg = SystemConfig(scene=SceneConfig(seed=5, num_cameras=3),
                           eval_frames=3, batched=True, episode=episode)
        pair[episode] = DeepStreamSystem(cfg, light, server)
    host, dev = pair[False], pair[True]
    prof = MultiCameraScene(SceneConfig(seed=42, num_cameras=3))
    host.profile(prof, num_slots=2, mlp_steps=120)
    dev.mlp, dev.tau_wl, dev.tau_wh = host.mlp, host.tau_wl, host.tau_wh
    dev.jcab_table = host.jcab_table
    return host, dev


@pytest.mark.parametrize("method", ["deepstream", "jcab", "static",
                                    "reducto"])
def test_run_device_control_matches_host(control_pair, method):
    """Acceptance: the on-device control loop reproduces the host path's
    utility (and control) logs to <= 1e-5 for every method, with cameras
    leaving and rejoining (the liveness mask and the reconnect clamp run
    through both control paths)."""
    trace = bandwidth_trace("medium", 4, seed=8) * 3 / 5
    faults = make_faults("camera_churn", 4, 3, seed=4)
    assert not faults.all()
    logs = {}
    for name, s in zip(("host", "device"), control_pair):
        s._key = jax.random.PRNGKey(1234)
        scene = DeviceScene(SceneConfig(seed=33, num_cameras=3))
        logs[name] = s.run(scene, trace, method=method, faults=faults)
    for k, tol in (("utility", 1e-5), ("bytes", 1e-3), ("alloc_kbps", 1e-3),
                   ("extra", 1e-3), ("area", 1e-4)):
        np.testing.assert_allclose(logs["device"][k], logs["host"][k],
                                   atol=tol, err_msg=(method, k))


# ---------------------------------------------------------------------------
# codec CRF satellite
# ---------------------------------------------------------------------------

def test_encode_segment_crf_effective_pixels_parity(rng):
    """CRF sizes must charge exactly effective_pixels (incl. the resolution
    term and the traced kept-frame override encode_segment honors)."""
    cfg = CodecConfig()
    frames = jnp.asarray(rng.uniform(0, 1, (6, 32, 64)).astype(np.float32))
    key = jax.random.PRNGKey(0)
    for roi_px, n, r in ((1000.0, 6, 1.0), (1000.0, 3, 1.0),
                         (500.0, 6, 0.5), (700.0, 2, 0.75)):
        _, size = codec_mod.encode_segment_crf(
            cfg, frames, jnp.float32(roi_px), key, res=jnp.float32(r),
            num_frames=jnp.float32(n))
        want = codec_mod.effective_pixels(cfg, roi_px, n, r) \
            * cfg.crf_bpp / 8.0
        assert float(size) == pytest.approx(want, rel=1e-6), (roi_px, n, r)
    # default call (no overrides) keeps the original shape-derived charge
    _, size = codec_mod.encode_segment_crf(cfg, frames, jnp.float32(1000.0),
                                           key)
    want = codec_mod.effective_pixels(cfg, 1000.0, 6, 1.0) * cfg.crf_bpp / 8.0
    assert float(size) == pytest.approx(want, rel=1e-6)


def test_encode_segment_crf_res_blurs_like_encode_segment(rng):
    """res < 1 routes through the same resolution-blur branches."""
    cfg = CodecConfig()
    frames = jnp.asarray(rng.uniform(0, 1, (4, 32, 64)).astype(np.float32))
    key = jax.random.PRNGKey(1)
    full, _ = codec_mod.encode_segment_crf(cfg, frames, jnp.float32(2048),
                                           key, res=jnp.float32(1.0))
    half, _ = codec_mod.encode_segment_crf(cfg, frames, jnp.float32(2048),
                                           key, res=jnp.float32(0.5))
    err_full = float(jnp.mean(jnp.abs(full - frames)))
    err_half = float(jnp.mean(jnp.abs(half - frames)))
    assert half.shape == frames.shape
    assert err_half > err_full     # downscale->upscale loss is applied
