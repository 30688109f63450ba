"""Scenario matrix + cross-mode differential tests.

The correctness story before this suite: the runner modes (sequential /
batched / episode) were proven equivalent on a handful of hand-picked
FCC-like seeds.  Here every (method x trace-family x runner-mode)
cell of the scenario matrix is run on small shapes and cross-checked:

  * utility/bytes/alloc log equivalence across modes per cell;
  * episode zero-transfer invariants per cell (no per-slot keep/control
    fetches, exactly two whole-trace harvest fetches);
  * ZERO mid-suite recompiles once a (method, bucket) executable is warm —
    trace-length bucketing + the harness's pinned DP capacity mean a whole
    mixed-(family, seed, T) matrix shares compiled programs;
  * one episode executable per (method, bucket) serves every T (bucket
    padding diffs <= 1e-5 vs the unbucketed program);
  * golden-log regression: the host-control reference must keep reproducing
    the committed per-method logs, so numerics can't silently shift;
  * trace-family properties (floor, paper stats, autocorrelation,
    determinism — including cross-process determinism, the
    PYTHONHASHSEED regression).
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import harness
from repro.core import fleet as fleet_mod
from repro.core import scheduler as sched_mod
from repro.data import scenarios
from repro.data.scenarios import make_scene, make_trace, trace_families
from repro.data.synthetic import DeviceScene, bandwidth_trace
from repro.kernels.edge_motion import ops as em_ops

METHODS = harness.METHODS
FAMILIES = harness.default_families()
# mixed trace lengths cycled over the matrix cells — all inside the first
# bucket, so the whole matrix must reuse ONE episode executable per method
MATRIX_TS = (2, 3, 4, 5)


@pytest.fixture(scope="module")
def mx(detectors):
    """One system per fleet runner mode over the default scene family —
    shared by the whole matrix (the harness pins the DP capacity, so every
    cell reuses the same compiled programs)."""
    scene_cfg = make_scene("urban_mid", 5)
    return {mode: harness.build_system(detectors, mode, scene_cfg)
            for mode in ("batched", "episode")}


# ---------------------------------------------------------------------------
# the differential matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", METHODS)
def test_cross_mode_matrix(mx, method):
    """Every (trace family x runner mode) cell for one method: cross-mode
    log equivalence, per-cell episode zero-transfer invariants, and zero
    recompiles of any fleet program after the method's first cell."""
    for i, family in enumerate(FAMILIES):
        T = MATRIX_TS[i % len(MATRIX_TS)]
        ctx = f"method={method} family={family} T={T}"
        n_slot0 = fleet_mod.compile_count()
        n_ep0 = fleet_mod.episode_compile_count()
        logs = {}
        for mode in ("batched", "episode"):
            d0 = sched_mod.d2h_fetch_counts()
            logs[mode] = harness.run_cell(mx[mode], method, family, T,
                                          trace_seed=17 + i)
            assert len(logs[mode]["utility"]) == T, (ctx, mode)
            if mode == "episode":
                d1 = sched_mod.d2h_fetch_counts()
                assert d1["keep"] == d0["keep"], (ctx, "keep fetch")
                assert d1["control"] == d0["control"], (ctx, "control fetch")
                assert d1["harvest"] == d0["harvest"] + 2, (ctx, "harvest")
        if i > 0:
            # bucket + capacity-pin reuse: past the method's first cell the
            # suite must never trace another fleet program
            assert fleet_mod.compile_count() == n_slot0, ctx
            assert fleet_mod.episode_compile_count() == n_ep0, ctx
        harness.assert_logs_match(logs["batched"], logs["episode"],
                                  ctx=ctx + " episode-vs-batched")


def test_sequential_cross_mode_slice(mx, detectors):
    """The per-camera Python reference joins the matrix on a reduced slice
    (it is ~10x slower per slot; its batched equivalence is already pinned
    seed-by-seed in test_fleet.py): all four methods, one family, every
    fleet mode compared against it."""
    seq = harness.build_system(detectors, "sequential",
                               make_scene("urban_mid", 5))
    for method in METHODS:
        ref = harness.run_cell(seq, method, FAMILIES[0], 2)
        for mode in ("batched", "episode"):
            got = harness.run_cell(mx[mode], method, FAMILIES[0], 2)
            # the sequential control path is float64 numpy — equivalence to
            # the f32 device programs is to rounding (1e-3, the test_fleet
            # tolerance), not the 1e-5 the device modes hold between each
            # other
            harness.assert_logs_match(
                ref, got, tol=1e-3, keys=("utility", "bytes", "alloc_kbps"),
                ctx=f"sequential-vs-{mode} method={method}")


# ---------------------------------------------------------------------------
# trace-length bucketing
# ---------------------------------------------------------------------------

def test_bucket_len_contract():
    assert [fleet_mod.bucket_len(t) for t in (1, 3, 8, 9, 16, 17, 32)] == \
        [8, 8, 8, 16, 16, 32, 32]
    # past the largest bucket: doubling, never unbounded specialization
    assert fleet_mod.bucket_len(33) == 64
    assert fleet_mod.bucket_len(100) == 128
    # disabled bucketing is the unbucketed reference
    assert fleet_mod.bucket_len(5, None) == 5
    assert fleet_mod.bucket_len(5, ()) == 5
    assert fleet_mod.bucket_len(5, (4,)) == 8


def test_one_executable_per_bucket_serves_mixed_T(mx):
    """Acceptance: a mixed-T suite compiles at most one episode program per
    (method, bucket).  After a bucket's first trace, every other T in that
    bucket reuses the executable — including bucket-edge T == bucket."""
    ep = mx["episode"]
    buckets = {8: (3, 5, 8), 16: (12, 16), 32: (20,)}
    for bucket, ts in buckets.items():
        n0 = fleet_mod.episode_compile_count()
        first = None
        for T in ts:
            logs = harness.run_cell(ep, "deepstream", "fcc_medium", T)
            assert len(logs["utility"]) == T
            assert np.all(np.isfinite(logs["utility"]))
            if first is None:
                first = fleet_mod.episode_compile_count()
                assert first - n0 <= 1, (bucket, "first trace of a bucket "
                                         "may trace at most one program")
            else:
                assert fleet_mod.episode_compile_count() == first, (bucket, T)


def test_bucketed_matches_unbucketed(detectors):
    """Acceptance: padding T up to a bucket must not move a single logged
    number (<= 1e-5; the padded tail is masked out of every observable).
    reducto exercises the cross-slot reference carry, deepstream the
    elastic state."""
    scene_cfg = make_scene("urban_mid", 5)
    buck = harness.build_system(detectors, "episode", scene_cfg)
    unbuck = harness.build_system(detectors, "episode", scene_cfg,
                                  episode_buckets=None)
    assert buck.cfg.episode_buckets == fleet_mod.EPISODE_BUCKETS
    assert unbuck.cfg.episode_buckets is None
    for method in ("deepstream", "reducto"):
        a = harness.run_cell(buck, method, "fcc_medium", 5)
        b = harness.run_cell(unbuck, method, "fcc_medium", 5)
        harness.assert_logs_match(b, a, ctx=f"bucketed-vs-unbucketed "
                                  f"method={method}")
        # the post-run codec key chain must match too: padded slots may not
        # consume PRNG keys
        ka, kb = np.asarray(buck._key), np.asarray(unbuck._key)
        np.testing.assert_array_equal(ka, kb, err_msg=method)


def test_bucketed_episode_resume(mx, detectors):
    """Back-to-back episodes on ONE reused scene (the second run resumes at
    t_start=3; both pad to bucket 8) reproduce a batched run over the same
    slots split the same way — t_start stays a data value under bucketing
    and the sliced key chain threads runs together correctly."""
    import dataclasses
    ep = mx["episode"]
    pi = mx["batched"]
    tr = make_trace("step_drop", 6, seed=3, num_cams=3)
    for method in ("deepstream", "reducto"):
        logs = {}
        for name, s in (("ep", ep), ("pi", pi)):
            s._key = jax.random.PRNGKey(1234)
            scfg = dataclasses.replace(s.cfg.scene, seed=33)
            scene = DeviceScene(scfg)
            a = s.run(scene, tr[:3], method=method)
            b = s.run(scene, tr[3:], method=method)
            logs[name] = {k: np.concatenate([a[k], b[k]])
                          for k in ("utility", "bytes", "alloc_kbps")}
        harness.assert_logs_match(logs["pi"], logs["ep"],
                                  keys=("utility", "bytes", "alloc_kbps"),
                                  ctx=f"resumed episode method={method}")


def test_bucketed_episode_fetch_counts(mx):
    """d2h_fetch_counts() under bucketed episodes: zero 'keep'/'control'
    fetches and EXACTLY two harvest fetches per run for every bucket —
    including a T that pads (T=5 -> bucket 8) and a second bucket — i.e.
    the padding slots add no transfers of any kind."""
    ep = mx["episode"]
    for method, T in (("deepstream", 5), ("reducto", 5), ("deepstream", 12),
                      ("jcab", 2), ("static", 3)):
        before = sched_mod.d2h_fetch_counts()
        harness.run_cell(ep, method, "fcc_medium", T)
        after = sched_mod.d2h_fetch_counts()
        assert after["keep"] == before["keep"], (method, T)
        assert after["control"] == before["control"], (method, T)
        assert after["harvest"] == before["harvest"] + 2, (method, T)


# ---------------------------------------------------------------------------
# golden-log regression
# ---------------------------------------------------------------------------

def test_golden_logs_regression(detectors):
    """The committed host-control reference logs must keep reproducing: any
    future PR that shifts numerics now fails loudly instead of silently
    re-baselining itself through the cross-mode equivalence tests (which
    compare modes only against each other)."""
    doc = json.loads(harness.GOLDEN_PATH.read_text())
    assert tuple(doc["scene"]) == harness.GOLDEN_SCENE
    assert tuple(doc["trace"]) == harness.GOLDEN_TRACE
    got = harness.golden_reference_logs(detectors)
    for method, want in doc["methods"].items():
        harness.assert_logs_match(want, got[method], tol=doc["tol"],
                                  ctx=f"golden method={method}")


# ---------------------------------------------------------------------------
# scene families
# ---------------------------------------------------------------------------

def _scene_family_subset():
    fams = scenarios.scene_families()
    return fams[:3] if harness.quick_mode() else fams


def test_scene_families_pure_and_distinct():
    for name in scenarios.scene_families():
        a, b = make_scene(name, 3), make_scene(name, 3)
        assert a == b, name                       # pure in (name, seed)
    cams = {make_scene(n, 0).num_cameras for n in scenarios.scene_families()}
    assert {2, 3, 4} <= cams                      # spans camera counts
    objs = {make_scene(n, 0).max_objects for n in scenarios.scene_families()}
    assert len(objs) >= 2                         # spans object density


def test_scene_family_motion_energy_ordering():
    """Content knobs do what they claim: the dense fast-moving family shows
    more block-motion energy than the sparse slow one (device-side
    synthesis, a few slots averaged)."""
    energies = {}
    for name in ("sparse_suburb", "dense_junction"):
        scene = DeviceScene(make_scene(name, 11))
        vals = [float(np.mean(np.asarray(em_ops.segment_motion_fleet(
            scene.segment()["frames"])))) for _ in range(3)]
        energies[name] = float(np.mean(vals))
    assert energies["dense_junction"] > 1.2 * energies["sparse_suburb"], \
        energies


@pytest.mark.parametrize("family", _scene_family_subset())
def test_scene_family_differential(detectors, family):
    """Cross-mode equivalence holds on every scene family too (episode vs
    the host-control batched loop, deepstream — the content-dependent
    route: ROI masks, (a, c) features and elastic state all vary with the
    scene)."""
    scene_cfg = make_scene(family, 5)
    logs = {}
    for mode in ("batched", "episode"):
        s = harness.build_system(detectors, mode, scene_cfg)
        logs[mode] = harness.run_cell(s, "deepstream", "fcc_medium", 2,
                                      scene_seed=41)
    harness.assert_logs_match(logs["batched"], logs["episode"],
                              ctx=f"scene family={family}")


def test_scene_family_episode_small_fleet(detectors):
    """The episode runner holds its batched equivalence off the default
    camera count too (C=2, the smallest fleet the allocator sees)."""
    scene_cfg = make_scene("cam_pair", 5)
    logs = {}
    for mode in ("batched", "episode"):
        s = harness.build_system(detectors, mode, scene_cfg)
        logs[mode] = harness.run_cell(s, "deepstream", "step_drop", 3,
                                      scene_seed=23)
    harness.assert_logs_match(logs["batched"], logs["episode"],
                              ctx="scene family=cam_pair episode")


# ---------------------------------------------------------------------------
# trace-family properties
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 100_000),
       name=st.sampled_from(trace_families()))
def test_trace_family_invariants(seed, name):
    """Every family, any seed: the per-family floor holds (64 Kbps clip for
    most; ``ZERO_FLOOR_FAMILIES`` like hard_outage may hit a true 0 Kbps,
    never negative), values are finite, the length contract holds, and the
    trace is a pure function of (name, num_slots, seed)."""
    floor = (0.0 if name in scenarios.ZERO_FLOOR_FAMILIES
             else scenarios.FLOOR_KBPS)
    tr = make_trace(name, 48, seed=seed)
    assert tr.shape == (48,)
    assert np.all(np.isfinite(tr))
    assert np.all(tr >= floor - 1e-9)
    np.testing.assert_array_equal(tr, make_trace(name, 48, seed=seed))
    # scaling preserves the floor (and never resurrects a 0 Kbps outage slot)
    small = make_trace(name, 48, seed=seed, num_cams=1)
    assert np.all(small >= floor - 1e-9)
    if name in scenarios.ZERO_FLOOR_FAMILIES:
        np.testing.assert_array_equal(small == 0.0, tr == 0.0)
    else:
        assert np.all(small >= scenarios.FLOOR_KBPS - 1e-9)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_fcc_families_match_paper_stats(seed):
    """The fcc kinds track the paper's Section 7.1 mean/std parameters
    (loose tolerances: finite sample + the 64 Kbps clip bias the moments
    slightly) and show the positive AR(1) lag-1 autocorrelation the
    generator models."""
    from repro.data.synthetic import FCC_PARAMS
    n = 600
    for kind, (mu, sd) in FCC_PARAMS.items():
        tr = bandwidth_trace(kind, n, seed=seed)
        assert abs(tr.mean() - mu) < 0.45 * sd, (kind, tr.mean())
        assert 0.55 * sd < tr.std() < 1.35 * sd, (kind, tr.std())
        x = tr - tr.mean()
        rho1 = float(np.dot(x[1:], x[:-1]) / np.dot(x, x))
        assert rho1 > 0.3, (kind, rho1)


def test_trace_families_registry_covers_matrix():
    fams = trace_families()
    assert len(fams) >= 8
    for want in ("fcc_low", "fcc_medium", "fcc_high", "step_drop", "outage",
                 "spike", "diurnal", "adversarial_sawtooth"):
        assert want in fams
    # structural families do what their names claim
    sdrop = make_trace("step_drop", 24, seed=1)
    assert sdrop[:1].mean() > 1200 and sdrop[-4:].mean() < 1200
    out = make_trace("outage", 24, seed=1)
    assert np.any(out <= scenarios.FLOOR_KBPS + 1e-9)
    saw = make_trace("adversarial_sawtooth", 24, seed=1)
    assert saw.max() > 4 * saw.min()


def test_bandwidth_trace_cross_process_deterministic(tmp_path):
    """Regression for the PYTHONHASHSEED bug: `seed + hash(kind) % 1000`
    made "reproducible" traces differ across interpreter runs.  A
    subprocess with a different hash seed must reproduce the parent's
    traces bit-for-bit (compared as raw float64 bytes)."""
    names = list(trace_families())
    code = (
        "import sys, json\n"
        "sys.path.insert(0, sys.argv[1])\n"
        "from repro.data.scenarios import make_trace\n"
        "from repro.data.synthetic import bandwidth_trace\n"
        "out = {n: make_trace(n, 32, seed=9).tobytes().hex()\n"
        "       for n in json.loads(sys.argv[2])}\n"
        "out.update({'raw_' + k: bandwidth_trace(k, 32, seed=9)"
        ".tobytes().hex()\n"
        "            for k in ('low', 'medium', 'high')})\n"
        "print(json.dumps(out))\n")
    env = dict(os.environ)
    env["PYTHONHASHSEED"] = "271828"   # a salt the parent does not use
    src = str(Path(harness.ROOT) / "src")
    proc = subprocess.run(
        [sys.executable, "-c", code, src, json.dumps(names)],
        capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr
    got = json.loads(proc.stdout)
    for n in names:
        assert got[n] == make_trace(n, 32, seed=9).tobytes().hex(), n
    for k in ("low", "medium", "high"):
        assert got["raw_" + k] == \
            bandwidth_trace(k, 32, seed=9).tobytes().hex(), k
