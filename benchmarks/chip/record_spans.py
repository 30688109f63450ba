#!/usr/bin/env python3
"""Record a test fixture of the program's device scopes and host spans on
the chip: two traced windows of one cell.

    python3 benchmarks/chip/record_spans.py --workload s01-deepstream.fcc \\
        --seed 7 --out benchmarks/chip/tests/data/trace_s01-deepstream.spans.json.gz

The cell is built and warmed up as ``run.py`` does; three windows are
profiled and the first two kept.  The file holds the harness's extract of
those two windows (``devices``, ``spans``, as ``trace.trim`` cuts it) and
what the extract leaves out:

- ``scopes``: instruction name -> [scope or null, result type] for every
  operation of the episode in the two windows, from the loaded executable
  (``chipbench/scopes.py``);
- ``host``: every host event named ``serve.*`` or ``ckpt.*`` in the two
  windows, as [name, start ns, duration ns, "<plane>#<line> <name>"]
  (one line per thread);
- ``recent``: the program's own record (``repro.common.obs``) of each of
  those spans in the two windows, in seconds.

The trace's planes and lines are described on stderr.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]
HOST_PREFIXES = ("serve.", "ckpt.")
SPANS = ("serve.prep", "serve.device", "serve.harvest", "serve.logs",
         "serve.supervise", "serve.checkpoint", "ckpt.meta", "ckpt.wait",
         "ckpt.fetch", "ckpt.write")
TRACED, KEPT = 3, 2          # windows profiled, windows kept


def host_events(pd, t0: int, t1: int):
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):   # one line per thread
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIXES) and \
                        t0 <= int(ev.start_ns) < t1:
                    out.append([ev.name, int(ev.start_ns),
                                int(ev.duration_ns),
                                f"{plane.name}#{i} {line.name}"])
    return sorted(out, key=lambda e: e[1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(CHIP_DIR))
    from chipbench import scopes, spec, trace as trace_mod
    from chipbench.harness import Served
    from chipbench.serve import SCRATCH, CompileClock
    from repro.common import obs
    from repro.launch.cache import enable_compile_cache

    cell = spec.find_cell(args.workload, spec.load_benchmark(ROOT))
    enable_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_spans.py: needs a TPU", file=sys.stderr)
        return 2
    clock = CompileClock()
    sv = Served.build(cell, args.seed)
    sv.warm_up(clock, lambda msg: print(f"# {msg}", file=sys.stderr))
    tdir = SCRATCH / "trace" / f"{cell.name}.spans"
    shutil.rmtree(tdir, ignore_errors=True)
    sv.runner.saver.wait()      # no write of a warm-up window runs on
    before = {n: len(obs.recent(n)) for n in SPANS}
    with jax.profiler.trace(str(tdir)):
        sv.loop.run_windows(TRACED)
    pd = trace_mod.load(tdir)
    trace_mod.describe(pd, sys.stderr)
    kept = trace_mod.trim(trace_mod.extract(pd), KEPT)
    w0 = min(s for n, s, _ in kept["spans"] if n == "bench.window")
    w1 = max(s + d for n, s, d in kept["spans"] if n == "bench.window")
    host = host_events(pd, w0, w1)
    names = sorted({e[0] for e in host})
    # the program's record of each span, for the kept windows only
    kept_n = {n: sum(1 for e in host if e[0] == n) for n in SPANS}
    recent = {n: obs.recent(n)[before[n]:before[n] + kept_n[n]]
              for n in SPANS if kept_n[n]}
    summary = trace_mod.reduce(kept)
    ran = {name for name, _, _ in scopes.episode_ops(summary)}
    maps = scopes.live_scope_maps()
    smap = max(maps, key=lambda m: len(ran & set(m))) if maps else {}
    doc = dict(kept, scopes={n: smap[n] for n in sorted(ran) if n in smap},
               host=host, recent=recent)
    with gzip.open(args.out, "wt") as f:
        json.dump(doc, f)
    secs = scopes.layer_seconds(summary, [smap] if smap else [])
    print(f"# {cell.name}: {len(host)} host spans {names}; scope seconds "
          f"{secs}; wrote {args.out}", file=sys.stderr)
    sv.close()
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
