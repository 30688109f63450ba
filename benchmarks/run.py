"""Benchmark harness — one module per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME ...]

Prints ``name,us_per_call,derived`` CSV rows plus per-benchmark result tables,
and writes JSON artifacts to ``artifacts/bench/``.  Each artifact records the
execution environment (host device count, platform, fake-device override,
fleet sharding and run mode) so sharded and single-device runs are
distinguishable after the fact.  Set ``REPRO_FAKE_DEVICES=8`` to fan the CPU
host out into 8 XLA devices (the `make ci-sharded` lane).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from pathlib import Path

BENCHES = [
    "bench_roidet",       # Fig. 4 + Fig. 5
    "bench_allocation",   # section 5.2 optimality + scaling
    "bench_e2e_utility",  # Fig. 3
    "bench_kernels",      # kernel vs oracle timings
    "bench_serve",        # continuous-serving SLO (window p50/p99, slots/s)
    "bench_roofline",     # dry-run roofline table (reads artifacts/dryrun)
    "bench_static_cost",  # compile-time flops/bytes/peak per executable
]

ART = Path(__file__).resolve().parents[1] / "artifacts" / "bench"


def _env_metadata() -> dict:
    """Device/sharding provenance stamped into every bench artifact.
    Imported lazily so REPRO_FAKE_DEVICES can take effect first.

    ``system_defaults`` records the SystemConfig defaults a bench inherits
    when it does not override them."""
    import jax
    from repro.core.scheduler import SystemConfig
    cfg = SystemConfig()
    fake = os.environ.get("REPRO_FAKE_DEVICES")
    return {
        "device_count": jax.device_count(),   # what actually ran
        "platform": jax.default_backend(),
        "requested_fake_devices": int(fake) if fake else None,
        "system_defaults": {"shard": cfg.shard, "batched": cfg.batched,
                            "episode": cfg.episode},
    }


def main() -> None:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced slot/sample counts")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()

    # must happen before anything imports jax; append to (rather than skip
    # on) pre-existing XLA_FLAGS so the fake-device request is never
    # silently ignored — if XLA_FLAGS already pins a host device count, that
    # wins, and we say so (env metadata records the device count that ran)
    fake = os.environ.get("REPRO_FAKE_DEVICES")
    if fake:
        flag = f"--xla_force_host_platform_device_count={int(fake)}"
        existing = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in existing:
            os.environ["XLA_FLAGS"] = (existing + " " + flag).strip()
        else:
            print(f"# REPRO_FAKE_DEVICES={fake} ignored: XLA_FLAGS already "
                  "pins a host device count", file=sys.stderr)

    ART.mkdir(parents=True, exist_ok=True)
    env_meta = _env_metadata()
    print(f"# devices={env_meta['device_count']} "
          f"platform={env_meta['platform']} "
          f"defaults={env_meta['system_defaults']}")
    names = args.only or BENCHES
    print("name,us_per_call,derived")
    for name in names:
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.perf_counter()
        result = mod.run(quick=args.quick)
        dt = (time.perf_counter() - t0) * 1e6
        derived = result.get("headline", "")
        print(f"{name},{dt:.0f},{derived}", flush=True)
        result["env"] = env_meta
        (ART / f"{name}.json").write_text(json.dumps(result, indent=2,
                                                     default=str))
        if "trajectory" in result:
            _append_trajectory(result["trajectory"], env_meta, args.quick)


def _append_trajectory(entry: dict, env_meta: dict, quick: bool) -> None:
    """Append a perf-trajectory datapoint to BENCH_trajectory.json — the
    committed per-PR record of the headline numbers (serving SLOs, static
    executable cost), so regressions are visible across PRs.  Quick runs
    are stamped ``quick=True`` (fewer slots/reps — not comparable to full
    datapoints)."""
    path = ART / "BENCH_trajectory.json"
    history = json.loads(path.read_text()) if path.exists() else []
    stamp = {"date": time.strftime("%Y-%m-%d %H:%M:%S"),
             "devices": env_meta["device_count"],
             "platform": env_meta["platform"], "quick": quick}
    # per-lever A/B datapoints become SEPARATE records (one per lever per
    # camera count, bucket/C metadata inline) so a single lever's
    # regression is greppable across PRs without diffing nested blobs
    levers = entry.pop("levers", None) or []
    history.append({**stamp, **entry})
    for lv in levers:
        history.append({**stamp, "bench": f"{entry.get('bench')}:lever",
                        **lv})
    path.write_text(json.dumps(history, indent=2, default=str))


if __name__ == "__main__":
    main()
