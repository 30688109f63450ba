"""Pipelined-episode fast-path lane (`make ci-pipeline`).

Three contracts of the PR 10 episode fast path, in one place:

* **differential** — the software-pipelined scan body (stage B finishes
  slot t's detector batch while stage A encodes slot t+1) reproduces the
  straight-line reference body's logs to <= 1e-5 for every method, with
  and without camera-churn faults (the fault runs drive the live-camera
  compaction gather through non-trivial permutations);
* **serving contracts** — re-running the pipelined episode causes zero
  mid-run recompiles, keeps every per-slot D2H category at zero, and
  harvests exactly TWO stacked fetches per episode (pack + control pack),
  slot-count independent — the same invariants the reference body pinned;
* **dead compute** — the executable manifest's XLA ``cost_analysis``
  proves the masking is *structural*, not just output masking: padded
  tail slots and the statically dropped reuse arm contribute ZERO
  detector FLOPs.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

import jax

import harness
from repro.core import fleet as fleet_mod
from repro.core import scheduler as sched_mod
from repro.data.scenarios import make_faults, make_scene, make_trace
from repro.data.synthetic import DeviceScene

METHODS = harness.METHODS
T = 7
FAMILY = "fcc_medium"
MANIFEST = Path(__file__).parent / "golden" / "executable_manifest.json"


def _run(system, method, *, faults=None, scene_seed=33, trace_seed=8):
    """One episode cell with run_cell's fixed artifacts, plus a fault
    schedule (harness.run_cell has no faults hook)."""
    import dataclasses
    scfg = dataclasses.replace(system.cfg.scene, seed=int(scene_seed))
    scene = DeviceScene(scfg)
    trace = make_trace(FAMILY, T, seed=trace_seed,
                       num_cams=scfg.num_cameras)
    system._key = jax.random.PRNGKey(1234)
    return system.run(scene, trace, method=method, faults=faults)


@pytest.fixture(scope="module")
def pipeline_pair(detectors):
    """(reference-body system, pipelined system) — identical artifacts,
    only ``SystemConfig.episode_pipelined`` differs."""
    ref = harness.build_system(detectors, "episode",
                               make_scene("urban_mid", 101))
    ref.cfg.episode_pipelined = False
    fast = harness.build_system(detectors, "episode",
                                make_scene("urban_mid", 101))
    assert fast.cfg.episode_pipelined            # the default IS the fast path
    return ref, fast


# ---------------------------------------------------------------------------
# pipelined-vs-reference differential
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fault_family", [None, "camera_churn"])
@pytest.mark.parametrize("method", METHODS)
def test_pipelined_matches_reference(pipeline_pair, method, fault_family):
    """The 2-stage pipeline is an exact program transformation: identical
    keys, identical per-camera math (the compaction gather is a pure
    permutation of camera rows), so logs agree with the un-pipelined
    reference to the matrix tolerance."""
    ref_sys, fast_sys = pipeline_pair
    C = ref_sys.cfg.scene.num_cameras
    faults = (None if fault_family is None
              else make_faults(fault_family, T, C, seed=4))
    ref = _run(ref_sys, method, faults=faults)
    got = _run(fast_sys, method, faults=faults)
    harness.assert_logs_match(ref, got, tol=1e-5,
                              ctx=f"{method} faults={fault_family}")


def test_pipelined_zero_recompiles_two_fetches(pipeline_pair):
    """Warm pipelined episodes re-serve with zero recompiles and the
    two-fetch harvest contract (no per-slot keep/control syncs)."""
    _, fast = pipeline_pair
    _run(fast, "deepstream")                                # warm
    n0 = fleet_mod.episode_compile_count()
    before = sched_mod.d2h_fetch_counts()
    _run(fast, "deepstream", scene_seed=35)
    _run(fast, "reducto", scene_seed=36)
    after = sched_mod.d2h_fetch_counts()
    assert fleet_mod.episode_compile_count() == n0
    assert after["keep"] == before["keep"]
    assert after["control"] == before["control"]
    assert after["harvest"] == before["harvest"] + 2 * 2


# ---------------------------------------------------------------------------
# dead compute is structurally absent (manifest cost_analysis)
# ---------------------------------------------------------------------------

def _episode_flops():
    doc = json.loads(MANIFEST.read_text())
    out = {}
    for name, e in doc["executables"].items():
        if name.startswith("episode/"):
            _, method, bucket = name.split("/")
            out[(method, int(bucket[1:]))] = float(e["cost"]["flops"])
    return out


def test_masked_tail_slots_cost_zero_flops():
    """Padded tail slots are dead compute the program never materializes:
    XLA's cost_analysis costs a ``lax.scan`` body ONCE (trip count never
    multiplies flops), so a bucket's padding changes only the xs buffer
    bytes — per-method episode flops must be IDENTICAL across the b8/b16/
    b32 buckets.  The golden manifest is pinned to live code by the
    ci-audit lane's full manifest check, so asserting over it here is
    asserting over the compiled programs."""
    flops = _episode_flops()
    buckets = sorted({b for (_, b) in flops})
    assert buckets == sorted(fleet_mod.EPISODE_BUCKETS)
    for method in METHODS:
        per_bucket = {b: flops[(method, b)] for b in buckets}
        assert len(set(per_bucket.values())) == 1, (method, per_bucket)


def _server_detector_rows(method: str, bucket: int = 8) -> int:
    """Largest batch the server detector's first conv sees anywhere in the
    method's episode program (sub-jaxprs included) — identified by the
    server variant's first-layer kernel shape."""
    from repro.analysis import jaxpr_audit, programs
    from repro.models.detector import detector_defs

    c1 = tuple(detector_defs("server")["c1"].shape)
    prog, = (p for p in programs.get_programs(kinds=["episode"])
             if p.name == f"episode/{method}/b{bucket}")
    rows, stack = [], [jaxpr_audit.trace_program(prog).jaxpr]
    while stack:
        jx = stack.pop()
        for eqn in jx.eqns:
            if (eqn.primitive.name == "conv_general_dilated"
                    and tuple(eqn.invars[1].aval.shape) == c1):
                rows.append(eqn.invars[0].aval.shape[0])
            stack.extend(jaxpr_audit._sub_jaxprs(eqn.params))
    return max(rows)


def test_dropped_reuse_arm_costs_zero_flops():
    """Only reducto consumes the keep-mask reuse arm, so PR 10 drops that
    arm STATICALLY (``with_reuse = method == "reducto"``) instead of
    masking its outputs — the C extra detector rows must be absent from
    the compiled program.  Structurally: every other method's server
    detector batch is strictly smaller than reducto's.  In static flops:
    jcab and static, which share reducto's full-frame front end, sit
    strictly below it at every bucket.  (``lax.cond`` branches are costed
    statically, so an output-masked arm would still show up here — this
    asserts the compute is GONE, not hidden.)  Deepstream's flops are not
    compared: its ROIDet front end alone out-costs the reuse arm in XLA's
    count."""
    rows = {m: _server_detector_rows(m) for m in METHODS}
    flops = _episode_flops()
    for method in METHODS:
        if method == "reducto":
            continue
        assert rows[method] < rows["reducto"], (method, rows)
        if method == "deepstream":
            continue
        for bucket in fleet_mod.EPISODE_BUCKETS:
            assert flops[(method, bucket)] < flops[("reducto", bucket)], \
                (method, bucket, flops[(method, bucket)],
                 flops[("reducto", bucket)])
