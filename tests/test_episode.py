"""Whole-trace device-resident episodes: equivalence with the host-control
batched loop for every method, the zero-per-slot-transfer guarantee (fetch counters +
transfer guard, no scoped exemptions), the episode operands (cached
invariants plus one batched put) against the eager construction they
replace, traced keep-selection math vs the host mirror, and device-side
segment synthesis stats vs the host scene."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import elastic as elastic_mod
from repro.core import fleet as fleet_mod
from repro.core import scheduler as sched_mod
from repro.core import utility as util_mod
from repro.core.elastic import ElasticStateJax
from repro.core.scheduler import DeepStreamSystem, EpisodeCarry, SystemConfig
from repro.data import synthetic as synth_mod
from repro.data.synthetic import (DeviceScene, MultiCameraScene, SceneConfig,
                                  bandwidth_trace)
from repro.kernels.edge_motion import ops as em_ops
from repro.models import detector as det
from repro.sharding.rules import pad_cameras, pad_leading

METHODS = ["deepstream", "jcab", "reducto", "static"]


def _system(detectors, episode: bool) -> DeepStreamSystem:
    light, server = detectors
    cfg = SystemConfig(scene=SceneConfig(seed=5, num_cameras=3),
                       eval_frames=3, batched=True, episode=episode)
    s = DeepStreamSystem(cfg, light, server)
    s.mlp = util_mod.init_utility_mlp(jax.random.PRNGKey(0))
    s.tau_wl, s.tau_wh = 10.0, 50.0
    s.jcab_table = np.linspace(0.2, 0.8, 18).reshape(6, 3).astype(np.float32)
    return s


@pytest.fixture(scope="module")
def episode_pair(detectors):
    """(host-control batched reference, episode) systems over shared
    artifacts."""
    return _system(detectors, episode=False), _system(detectors, episode=True)


@pytest.mark.parametrize("method", METHODS)
def test_run_episode_matches_pipelined(episode_pair, method):
    """Acceptance: one lax.scan episode reproduces the host-control batched
    loop's utility/bytes/alloc logs (<= 1e-5) for all four methods —
    identical device-generated segments, keys and keep-flags, and a traced
    control trajectory equal to the numpy one."""
    logs = {}
    for s in episode_pair:
        s._key = jax.random.PRNGKey(1234)
        scene = DeviceScene(SceneConfig(seed=33, num_cameras=3))
        trace = bandwidth_trace("medium", 3, seed=8) * 3 / 5
        logs[s.cfg.episode] = s.run(scene, trace, method=method)
    for k, tol in (("utility", 1e-5), ("bytes", 1e-3), ("alloc_kbps", 1e-3),
                   ("extra", 1e-3), ("area", 1e-4)):
        np.testing.assert_allclose(logs[True][k], logs[False][k], atol=tol,
                                   err_msg=(method, k))


def test_episode_zero_per_slot_transfers(episode_pair):
    """During an episode run every per-slot D2H category stays at ZERO —
    including reducto's 'keep' (now traced) — and the whole-trace harvest
    is exactly two packed fetches, slot-count independent.  The timed
    region itself runs under jax.transfer_guard("disallow") in BOTH
    directions inside run_episode, with no scoped exemptions."""
    _, ep = episode_pair
    for method, slots in (("reducto", 3), ("deepstream", 5)):
        ep._key = jax.random.PRNGKey(7)
        scene = DeviceScene(SceneConfig(seed=11, num_cameras=3))
        trace = bandwidth_trace("medium", slots, seed=4) * 3 / 5
        before = sched_mod.d2h_fetch_counts()
        ep.run(scene, trace, method=method)
        after = sched_mod.d2h_fetch_counts()
        assert after["keep"] == before["keep"], method
        assert after["control"] == before["control"], method
        assert after["harvest"] == before["harvest"] + 2, method


def test_episode_zero_recompiles(episode_pair):
    """Re-running a method's episode must not re-trace its executable."""
    _, ep = episode_pair
    trace = bandwidth_trace("medium", 3, seed=3) * 3 / 5
    ep.run(DeviceScene(SceneConfig(seed=21, num_cameras=3)), trace,
           method="deepstream")
    n0 = fleet_mod.episode_compile_count()
    ep.run(DeviceScene(SceneConfig(seed=22, num_cameras=3)), trace,
           method="deepstream")
    assert fleet_mod.episode_compile_count() == n0


# ---------------------------------------------------------------------------
# episode operands: cached invariants + one batched put vs the eager build
# ---------------------------------------------------------------------------

def _eager_episode_args(s, method, scene, trace_kbps, faults, carry):
    """The episode executable's arguments as ``run_episode`` built them
    before its operands were cached and batched — about eighteen eager
    device operations per window.  Kept only as this test's reference."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    C, mesh = s.cfg.scene.num_cameras, s.mesh
    # the control context
    trace = jnp.asarray(np.asarray(trace_kbps, np.float32))
    lam = jnp.asarray(s.cfg.lam(), jnp.float32)
    tau_wl, tau_wh = jnp.float32(s.tau_wl), jnp.float32(s.tau_wh)
    est0 = elastic_mod.init_state_jax()
    jcab_util = jcab_res = None
    if method == "jcab":
        util, best_res = s._jcab_utility_table()
        jcab_util, jcab_res = jnp.asarray(util), jnp.asarray(best_res)
    if carry is not None:
        est0 = carry.est
    deep = method in ("deepstream", "deepstream_no_elastic")
    mlp_params = s.mlp if deep else None
    ref0 = None if carry is None else carry.ref
    live_prev0 = None if carry is None else carry.live_prev
    t_first = None if carry is None else carry.t_first
    t_start, key0, skey = scene._t, s._key, scene.key
    # the operand half of prepare_episode
    C_pad = pad_cameras(C, mesh)
    scene_params = synth_mod.pad_scene_params(scene.params, C_pad)
    T = int(trace.shape[0])
    T_b = fleet_mod.bucket_len(T, s.cfg.episode_buckets)
    if faults is None:
        live_np = np.ones((T_b, C), bool)
    else:
        live_np = np.asarray(faults, bool)
        if T_b != T:
            live_np = np.concatenate([live_np, np.ones((T_b - T, C), bool)])
    live_tr = jnp.asarray(live_np)
    if T_b != T:
        trace = jnp.concatenate(
            [jnp.asarray(trace, jnp.float32), jnp.zeros(T_b - T, jnp.float32)])
    active = jnp.arange(T_b) < T
    if ref0 is None:
        ref0 = jnp.zeros((C_pad, scene.cfg.height, scene.cfg.width),
                         jnp.float32)
    else:
        ref0 = pad_leading(jnp.asarray(ref0, jnp.float32), C_pad)
    live_prev0 = (jnp.ones((C,), bool) if live_prev0 is None
                  else jnp.asarray(live_prev0, bool))
    J = len(s.cfg.codec.bitrates_kbps)
    if jcab_util is None:
        jcab_util = jnp.zeros((C, J), jnp.float32)
        jcab_res = jnp.ones((C, J), jnp.float32)
    if mlp_params is None:
        mlp_params = {}
    t_idx = jnp.arange(T_b, dtype=jnp.int32) + jnp.int32(t_start)
    t_first = jnp.int32(t_start if t_first is None else t_first)
    t_len = jnp.int32(T)
    server_params, light_params = s.server, s.light
    if mesh is not None:
        cam_sh = NamedSharding(mesh, P("camera"))
        rep_sh = NamedSharding(mesh, P())
        rep = lambda tree: jax.tree.map(
            lambda x: jax.device_put(x, rep_sh), tree)
        scene_params = synth_mod.DeviceSceneParams(*(
            jax.device_put(x, cam_sh if sp == P("camera") else rep_sh)
            for x, sp in zip(scene_params,
                             synth_mod.DeviceSceneParams.pspecs())))
        ref0 = jax.device_put(ref0, cam_sh)
        (server_params, light_params, mlp_params, jcab_util, jcab_res, lam,
         trace, live_tr, active, t_idx, t_first, t_len, key0, skey, tau_wl,
         tau_wh, est0, live_prev0) = rep(
            (server_params, light_params, mlp_params, jcab_util, jcab_res,
             lam, trace, live_tr, active, t_idx, t_first, t_len, key0, skey,
             tau_wl, tau_wh, est0, live_prev0))
    return (server_params, light_params, mlp_params, jcab_util, jcab_res,
            lam, scene_params, trace, live_tr, active, t_idx, t_first, t_len,
            key0, skey, tau_wl, tau_wh, est0, ref0, live_prev0)


@pytest.fixture(scope="module")
def operand_system():
    """An episode system with untrained detectors: the operand tests build
    arguments and never launch them."""
    cfg = SystemConfig(scene=SceneConfig(seed=5, num_cameras=3),
                       eval_frames=3, batched=True, episode=True)
    s = DeepStreamSystem(cfg, det.init_detector(jax.random.PRNGKey(1), "light"),
                         det.init_detector(jax.random.PRNGKey(2), "server"))
    s.mlp = util_mod.init_utility_mlp(jax.random.PRNGKey(0))
    s.tau_wl, s.tau_wh = 10.0, 50.0
    s.jcab_table = np.linspace(0.2, 0.8, 18).reshape(6, 3).astype(np.float32)
    return s


@pytest.mark.parametrize("faulty", [False, True], ids=["live", "faults"])
@pytest.mark.parametrize("carried", [False, True], ids=["fresh", "carry"])
@pytest.mark.parametrize("T", [8, 5], ids=["full", "flush"])
@pytest.mark.parametrize("method", ["deepstream", "reducto", "jcab"])
def test_episode_args_match_eager_construction(operand_system, method, T,
                                               carried, faulty):
    """Every argument of the episode executable — tree structure, shape,
    dtype and weak type, value bit for bit, and sharding — is what the
    eager construction gave, for full and flush (T < bucket) windows, with
    and without a carry and faults, on one device or (in the fake-device
    lane) the camera mesh.  Built twice, so the second build reads the
    cached invariants."""
    s = operand_system
    C = s.cfg.scene.num_cameras
    fake = os.environ.get("REPRO_FAKE_DEVICES")
    if fake:
        assert jax.device_count() == int(fake) and s.mesh is not None
    scene = DeviceScene(SceneConfig(seed=33, num_cameras=C))
    rng = np.random.default_rng(T + 2 * carried + 4 * faulty)
    trace = rng.uniform(100.0, 3000.0, T)
    faults = None
    if faulty:
        faults = rng.uniform(size=(T, C)) < 0.7
        faults[:, 0] = True
    carry = None
    if carried:
        scene._t = 16
        carry = EpisodeCarry(
            est=ElasticStateJax(jnp.float32(0.3), jnp.float32(0.01),
                                jnp.float32(120.0), jnp.asarray(True)),
            ref=jax.random.uniform(jax.random.PRNGKey(3),
                                   (C, scene.cfg.height, scene.cfg.width)),
            live_prev=np.array([True, False, True]), t_first=3)
    want = _eager_episode_args(s, method, scene, trace, faults, carry)
    for _ in range(2):
        got = s._episode_args(method, scene, trace, faults=faults,
                              carry=carry)
        assert isinstance(got, fleet_mod.EpisodeArgs)
        for name, g, w in zip(fleet_mod.EpisodeArgs._fields, got, want):
            gl, gdef = jax.tree.flatten(g)
            wl, wdef = jax.tree.flatten(w)
            assert gdef == wdef, name
            for a, b in zip(gl, wl):
                assert isinstance(a, jax.Array), name
                assert jax.typeof(a) == jax.typeof(b), (name, jax.typeof(a),
                                                        jax.typeof(b))
                assert a.sharding == b.sharding, (name, a.sharding,
                                                  b.sharding)
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=name)


def test_episode_args_match_on_camera_mesh():
    """The operand differential above on a camera mesh of four CPU host
    devices (the suite's fake-device lane), where three cameras pad to
    four: the invariants and each window's put are placed replicated or
    camera-sharded exactly as the eager construction placed them."""
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, REPRO_FAKE_DEVICES="4")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", str(Path(__file__)),
         "-k", "test_episode_args_match_eager_construction"],
        capture_output=True, text=True, timeout=570, env=env, cwd=str(root))
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert "24 passed" in proc.stdout, proc.stdout[-4000:]


# ---------------------------------------------------------------------------
# traced keep-selection vs the host mirror
# ---------------------------------------------------------------------------

def _host_selection(keep: np.ndarray, F: int):
    """The host-side math keep_selection replaces (what the pre-episode
    scheduler built per slot with numpy index arrays)."""
    C, N = keep.shape
    eval_idx = np.zeros((C, F), np.int64)
    eval_w = np.zeros((C, F), np.float32)
    miss_w = np.zeros((C, F), np.float32)
    reuse_idx = np.zeros(C, np.int64)
    w_keep = np.ones(C, np.float32)
    for i in range(C):
        kept = np.flatnonzero(keep[i])
        ev = kept[fleet_mod.eval_indices(len(kept), F)]
        m = len(ev)
        eval_idx[i, :m] = ev
        eval_idx[i, m:] = ev[-1]
        eval_w[i, :m] = 1.0 / m
        reuse_idx[i] = kept[-1]
        miss = np.flatnonzero(~keep[i])
        if len(miss):
            msel = fleet_mod.eval_indices(len(miss), F)
            miss_w[i, :len(msel)] = 1.0 / len(msel)
            w_keep[i] = keep[i].mean()
    return eval_idx, eval_w, reuse_idx, miss_w, w_keep


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 100_000), n=st.integers(2, 12),
       f=st.integers(1, 6))
def test_keep_selection_matches_host(seed, n, f):
    rng = np.random.default_rng(seed)
    keep = rng.uniform(size=(4, n)) < 0.5
    keep[:, 0] |= ~keep.any(axis=1)          # invariant: >= 1 kept per row
    sel = fleet_mod.keep_selection(jnp.asarray(keep), min(f, n))
    ev, ew, ri, mw, wk = _host_selection(keep, min(f, n))
    np.testing.assert_array_equal(np.asarray(sel.eval_idx), ev)
    np.testing.assert_allclose(np.asarray(sel.eval_w), ew, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(sel.reuse_idx), ri)
    np.testing.assert_allclose(np.asarray(sel.miss_w), mw, atol=1e-7)
    np.testing.assert_allclose(np.asarray(sel.w_keep), wk, atol=1e-7)
    np.testing.assert_allclose(np.asarray(sel.n_eff), keep.sum(1), atol=0)


# ---------------------------------------------------------------------------
# device-side segment synthesis vs host synthesis stats
# ---------------------------------------------------------------------------

@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_segments_device_stats_match_host(seed):
    """The traced generator preserves the content statistics the paper's
    mechanisms exploit: per-frame GT box counts and block-motion energy in
    the same regime as the host numpy scene (loose ratio bounds — the
    generators share parameter distributions, not RNG streams)."""
    cfg = SceneConfig(seed=seed, num_cameras=2)
    dev, host = DeviceScene(cfg), MultiCameraScene(cfg)
    counts_d, counts_h, motion_d, motion_h = [], [], [], []
    for _ in range(4):
        sd, sh = dev.segment(), host.segment()
        counts_d += [len(b) for cam in sd["boxes"] for b in cam]
        counts_h += [len(b) for cam in sh["boxes"] for b in cam]
        motion_d.append(float(jnp.mean(em_ops.segment_motion_fleet(
            jnp.asarray(sd["frames"])))))
        motion_h.append(float(jnp.mean(em_ops.segment_motion_fleet(
            jnp.asarray(sh["frames"])))))
    # same order of magnitude, not degenerate
    assert 1.0 <= np.mean(counts_d) <= cfg.max_objects + cfg.num_stationary
    ratio = np.mean(counts_d) / max(np.mean(counts_h), 0.5)
    assert 0.25 <= ratio <= 6.0, (np.mean(counts_d), np.mean(counts_h))
    assert np.mean(motion_d) > 0.1                  # objects genuinely move
    mratio = np.mean(motion_d) / max(np.mean(motion_h), 1e-3)
    assert 0.2 <= mratio <= 8.0, (np.mean(motion_d), np.mean(motion_h))


def test_segments_device_deterministic_and_order_free():
    """Slot content is a pure function of (seed, t): two adapters agree
    bit-for-bit, and regenerating slot 0 after slot 3 is unchanged."""
    cfg = SceneConfig(seed=9, num_cameras=2)
    a, b = DeviceScene(cfg), DeviceScene(cfg)
    sa = a.segment()
    for _ in range(3):
        b.segment()
    from repro.data.synthetic import _segments_device_jit
    again = _segments_device_jit(cfg, b.params, b.key, 0, b.G)
    np.testing.assert_array_equal(sa["frames"], np.asarray(again[0]))
    np.testing.assert_array_equal(np.asarray(sa["gt_dev"][1]),
                                  np.asarray(again[2]))
