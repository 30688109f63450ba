"""Cross-mode differential harness for the scenario matrix.

One place owns three things the scenario tests, the Makefile CI lanes and
the golden fixture all need:

  * **cell runners** — build canonical ``DeepStreamSystem``s per runner
    mode (sequential / batched / episode) over a named scene
    family, run one (method, trace-family, T) cell with a fixed PRNG
    stream, and assert cross-mode log equivalence.  All modes share ONE
    pinned DP capacity (``W_CAP_KBPS``) so every cell of the matrix — any
    family, any seed, any T — reuses the same compiled control/episode
    programs; together with episode trace-length bucketing this is what
    makes "zero mid-suite recompiles" assertable.
  * **CI lane lists** — ``LANES``: ``make ci-episode`` / ``make
    ci-scenarios`` invoke ``python tests/harness.py --lane <name>``, so
    pytest selections live here once instead of being duplicated in the
    Makefile.  ``ci-scenarios`` sets ``REPRO_SCENARIO_QUICK=1``, which
    shrinks the family matrix (``default_families``).
  * **the golden-log writer** — ``python tests/harness.py --write-golden``
    regenerates ``tests/golden/golden_logs.json`` (per-method
    utility/bytes/alloc logs of the host-control reference on one fixed
    (scene seed, trace seed)); ``tests/test_scenarios.py`` asserts today's
    code still reproduces it to <= 1e-5.  Regenerate ONLY on an
    intentional numerics change, and say so in the PR.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
GOLDEN_PATH = ROOT / "tests" / "golden" / "golden_logs.json"

# pytest selections per CI lane — the single source the Makefile shells out
# to (ci-episode used to duplicate this list inline)
LANES = {
    "episode": [
        "tests/test_episode.py",
        "tests/test_sharded.py::test_episode_sharded_matches_pipelined",
    ],
    "scenarios": [
        "tests/test_scenarios.py",
    ],
    "faults": [
        "tests/test_faults.py",
        "tests/test_ft.py",
    ],
    "serve": [
        "tests/test_serve_stream.py",
        "tests/test_ckpt.py",
    ],
    "audit": [
        "tests/test_audit.py",
    ],
    "pipeline": [
        "tests/test_pipeline.py",
        "tests/test_kernels.py",
    ],
    "chaos": [
        "tests/test_chaos.py",
        "tests/test_ingest.py",
        "tests/test_ckpt.py",
    ],
}

METHODS = ("deepstream", "jcab", "reducto", "static")

# runner modes under differential test.  "batched" is the host-control
# reference (one fleet program per slot, numpy allocator); "episode" the
# whole-trace scan, the only device-control path.  "sequential" (the
# per-camera Python reference) is run on a reduced slice — it is ~10x
# slower per slot and its equivalence vs "batched" is already pinned by
# tests/test_fleet.py on several seeds.
MODES = {
    "sequential": dict(batched=False),
    "batched": dict(batched=True, shard="off"),
    "episode": dict(batched=True, episode=True),
}

# one pinned DP capacity for the WHOLE matrix: covers every family's max
# (<= ~5 Mbps at the harness camera counts) plus the elastic borrow
# (budget_kbits / slot_seconds = 1.5 Mbps) with slack;
# allocation.trace_capacity asserts if a trace ever outgrows it
W_CAP_KBPS = 8000.0

GOLDEN_SCENE = ("urban_mid", 101)     # (scene family, seed)
GOLDEN_TRACE = ("fcc_medium", 4, 7)   # (trace family, T, seed)

# log keys every runner mode emits, with the reference-relative tolerance
# scheme of the episode equivalence tests (atol = tol * max(1, |ref|max))
LOG_KEYS = ("utility", "bytes", "alloc_kbps", "extra", "area")


def quick_mode() -> bool:
    return os.environ.get("REPRO_SCENARIO_QUICK") == "1"


def train_default_detectors():
    """The ONE detector recipe (steps/batch, checkpoint-cached) shared by
    conftest's session ``detectors`` fixture and the golden-log writer — a
    recipe drift between them would regenerate the golden fixture from
    detectors the regression test never uses."""
    from repro.train.detector_train import train_detector
    server = train_detector("server", steps=600, batch=12, cache=True)
    light = train_detector("light", steps=300, batch=12, cache=True)
    return light, server


def default_families() -> tuple:
    """The >= 6-family matrix (3 in the quick lane).  fcc_low/fcc_high are
    statistical siblings of fcc_medium, so the default matrix trades them
    for the structurally distinct regimes; they stay covered by the trace
    property tests."""
    if quick_mode():
        return ("fcc_medium", "step_drop", "adversarial_sawtooth")
    return ("fcc_medium", "step_drop", "outage", "spike", "diurnal",
            "adversarial_sawtooth")


def build_system(detectors, mode: str, scene_cfg, *, eval_frames: int = 3,
                 w_cap_kbps: float = W_CAP_KBPS, episode_buckets="default"):
    """Canonical harness system: the fixed untrained-MLP + linspace
    jcab-table + tau setup every equivalence test uses (profiling is out of
    scope here — the matrix tests CONTROL + runner equivalence, so all
    modes just need identical artifacts)."""
    import jax
    from repro.core import utility as util_mod
    from repro.core.scheduler import DeepStreamSystem, SystemConfig

    light, server = detectors
    kw = dict(MODES[mode])
    if episode_buckets != "default":
        kw["episode_buckets"] = episode_buckets
    cfg = SystemConfig(scene=scene_cfg, eval_frames=eval_frames,
                       w_cap_kbps=w_cap_kbps, **kw)
    s = DeepStreamSystem(cfg, light, server)
    s.mlp = util_mod.init_utility_mlp(jax.random.PRNGKey(0))
    s.tau_wl, s.tau_wh = 10.0, 50.0
    s.jcab_table = np.linspace(0.2, 0.8, 18).reshape(6, 3).astype(np.float32)
    return s


def run_cell(system, method: str, family: str, T: int, *,
             scene_seed: int = 33, trace_seed: int = 8):
    """One matrix cell: a fresh ``DeviceScene`` (same scene family as the
    system was built for), the named bandwidth trace scaled to the fleet
    size, and a FIXED key stream — every runner mode draws identical
    coding noise, so logs are comparable across modes."""
    import jax
    from repro.data.scenarios import make_trace
    from repro.data.synthetic import DeviceScene

    import dataclasses
    scfg = dataclasses.replace(system.cfg.scene, seed=int(scene_seed))
    scene = DeviceScene(scfg)
    trace = make_trace(family, T, seed=trace_seed,
                       num_cams=scfg.num_cameras)
    system._key = jax.random.PRNGKey(1234)
    return system.run(scene, trace, method=method)


def assert_logs_match(ref: dict, got: dict, *, tol: float = 1e-5,
                      keys=LOG_KEYS, ctx: str = "") -> None:
    """Reference-relative equivalence over the shared log keys."""
    for k in keys:
        scale = max(1.0, float(np.max(np.abs(ref[k]))) if len(ref[k]) else 1.0)
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(ref[k]), rtol=0.0,
            atol=tol * scale, err_msg=f"{ctx} key={k}")


# -- golden fixture -----------------------------------------------------------

def golden_reference_logs(detectors) -> dict:
    """Per-method host-control reference logs for the golden (scene,
    trace)."""
    from repro.data.scenarios import make_scene

    fam_s, seed_s = GOLDEN_SCENE
    fam_t, T, seed_t = GOLDEN_TRACE
    out = {}
    for method in METHODS:
        s = build_system(detectors, "batched", make_scene(fam_s, seed_s))
        logs = run_cell(s, method, fam_t, T,
                        scene_seed=seed_s, trace_seed=seed_t)
        out[method] = {k: [float(v) for v in logs[k]] for k in LOG_KEYS}
    return out


def write_golden(path: Path = GOLDEN_PATH) -> Path:
    light, server = train_default_detectors()
    doc = {
        "comment": ("Host-control reference logs pinning today's numerics; "
                    "regenerate with `python tests/harness.py "
                    "--write-golden` only on an INTENTIONAL numerics "
                    "change and call it out in the PR"),
        "scene": list(GOLDEN_SCENE),
        "trace": list(GOLDEN_TRACE),
        "tol": 1e-5,
        "methods": golden_reference_logs((light, server)),
    }
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(doc, indent=1) + "\n")
    return path


# -- CLI ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--lane", choices=sorted(LANES),
                    help="run one CI lane's pytest selection")
    ap.add_argument("--write-golden", action="store_true",
                    help="regenerate tests/golden/golden_logs.json")
    args = ap.parse_args(argv)
    if args.write_golden:
        print(f"wrote {write_golden()}")
        return 0
    if args.lane:
        cmd = [sys.executable, "-m", "pytest", "-q", *LANES[args.lane]]
        return subprocess.call(cmd, cwd=str(ROOT))
    ap.error("nothing to do: pass --lane or --write-golden")


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT / "src"))
    raise SystemExit(main())
