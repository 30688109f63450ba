"""Static per-executable cost table for the (method x bucket) matrix.

NOT a timing bench: nothing executes.  Every audited episode/slot-step
program (``repro.analysis.programs``) is lowered and compiled
once, and the table reports XLA's static ``cost_analysis()`` flops /
bytes-accessed and ``memory_analysis()`` peak estimate per executable —
the compile-time view of how episode cost scales with trace bucket and
method.  Cross-checks:

  * ``roofline/analysis.py`` agreement — ``roofline_terms`` fed with the
    same cost dict must echo the flops/bytes verbatim, and
    ``parse_collectives`` over the compiled HLO must find ZERO
    collectives (the audited programs are the unsharded single-device
    lowerings; a collective appearing here means the registry silently
    started auditing sharded programs);
  * golden-manifest agreement — flops/bytes/peak must match the pinned
    ``tests/golden/executable_manifest.json`` entry exactly (same
    numbers the `make ci-audit` lane asserts).

A ``trajectory`` entry lands in ``artifacts/bench/BENCH_trajectory.json``
so per-PR growth of episode flops/bytes/peak is visible next to the
measured ms/slot trajectory.  Quick mode keeps only the bucket-8 episode
row per method (plus the slot-step), full mode compiles all 13.
"""
from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
GOLDEN = ROOT / "tests" / "golden" / "executable_manifest.json"


def run(quick: bool = False) -> dict:
    from repro.analysis.manifest import compiled_stats, lower_program
    from repro.analysis.programs import get_programs
    from repro.roofline.analysis import parse_collectives, roofline_terms

    progs = get_programs()
    if quick:
        progs = [p for p in progs
                 if not p.name.startswith("episode/")
                 or p.name.endswith("/b8")]

    golden = (json.loads(GOLDEN.read_text())["executables"]
              if GOLDEN.exists() else {})

    rows, mismatches = [], []
    for prog in progs:
        compiled = lower_program(prog).compile()   # ONE compile per program
        stats = compiled_stats(compiled)
        coll = parse_collectives(compiled.as_text())
        n_coll = sum(int(v["count"]) for v in coll.values())
        terms = roofline_terms(
            {"flops": stats["cost"].get("flops", 0.0),
             "bytes accessed": stats["cost"].get("bytes_accessed", 0.0)},
            coll)
        # roofline cross-check: same cost dict in, same flops/bytes out
        if terms["hlo_flops_per_device"] != stats["cost"].get("flops", 0.0) \
                or terms["hlo_bytes_per_device"] != \
                stats["cost"].get("bytes_accessed", 0.0):
            mismatches.append(f"{prog.name}: roofline_terms does not echo "
                              "cost_analysis")
        if n_coll != 0:
            mismatches.append(f"{prog.name}: {n_coll} collectives in an "
                              "unsharded single-device lowering")
        g = golden.get(prog.name, {})
        for field in ("cost", "memory"):
            if field in g and g[field] != stats[field]:
                mismatches.append(f"{prog.name}: {field} drifted from the "
                                  "golden manifest")
        rows.append({
            "name": prog.name,
            "flops": stats["cost"].get("flops", 0.0),
            "bytes_accessed": stats["cost"].get("bytes_accessed", 0.0),
            "peak_bytes": stats["memory"]["peak_estimate_bytes"],
            "collectives": n_coll,
            "matches_golden": prog.name in golden and not any(
                m.startswith(prog.name + ":") for m in mismatches),
        })

    print("\n[StaticCost] compile-time cost per executable (nothing ran):")
    print(f"{'executable':26s} {'GFLOP':>8s} {'MB acc':>8s} {'peak MB':>8s} "
          f"{'coll':>5s} {'golden':>7s}")
    for r in rows:
        print(f"{r['name']:26s} {r['flops'] / 1e9:8.3f} "
              f"{r['bytes_accessed'] / 1e6:8.1f} "
              f"{r['peak_bytes'] / 1e6:8.1f} {r['collectives']:5d} "
              f"{'ok' if r['matches_golden'] else 'DRIFT':>7s}")
    for m in mismatches:
        print(f"  MISMATCH {m}")

    episodes = {r["name"]: {"flops": r["flops"],
                            "bytes_accessed": r["bytes_accessed"],
                            "peak_bytes": r["peak_bytes"]}
                for r in rows if r["name"].startswith(("episode/",
                                                       "slot_step/"))}
    ok = not mismatches
    return {
        "rows": rows,
        "mismatches": mismatches,
        "headline": (f"{len(rows)} executables, "
                     f"{'all cross-checks ok' if ok else 'MISMATCHES'}"),
        "trajectory": {"bench": "bench_static_cost",
                       "static_cost_ok": ok,
                       "per_executable": episodes},
    }


if __name__ == "__main__":
    run(quick=True)
