#!/usr/bin/env python3
"""Read the two ends of a cell's correctness limit, many seeds in one
process.

    python3 benchmarks/chip/readings.py --workload s01-deepstream.fcc \\
        --seconds 20 --seeds 11 12 13 --control

For each seed, one JSON line: the program's reading (its served logs
against the plain reference, the number ``run.py`` compares) over the
slots the cell's loop serves in ``--seconds`` after one warm window, as a
run serves them; with ``--control``, also the control's reading (the
reference with both detectors in bfloat16, the precision below the
float32 the configurations state, against the float32 reference on the
same stream).  The limit in ``limits/<cell>.json`` lies above every
program reading and below every control reading.  ``run.py`` never runs
this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(CHIP_DIR))
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import jax

    from chipbench import reference, spec
    from chipbench.harness import Served, check, compare

    cell = spec.find_cell(args.workload, spec.load_benchmark(ROOT))
    platform = jax.devices()[0].platform
    for seed in args.seeds:
        t0 = time.perf_counter()
        sv = Served.build(cell, seed)
        sv.loop.window()
        sv.loop.run_for(args.seconds)
        logs, _ = sv.close()
        t_served = time.perf_counter() - t0
        prog, ref = check(sv, seed, logs)
        out = {"workload": cell.name, "seed": seed, "platform": platform,
               "slots": len(logs["utility"]), "program": prog,
               "serve_s": t_served}
        if args.control:
            idx = sv.accepted[:len(logs["utility"])]
            low = reference.run(sv.cfg, sv.weights.ref(), seed,
                                sv.stream.bandwidth[idx], sv.stream.live[idx],
                                detector_dtype="bfloat16")
            out["control"] = compare({k: list(v) for k, v in low.items()},
                                     ref)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
