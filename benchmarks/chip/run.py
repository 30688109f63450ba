#!/usr/bin/env python3
"""Chip benchmark of the served fleet stream: one cell, one run.

    python3 benchmarks/chip/run.py --workload s01-deepstream.fcc \\
        --seed 7 --seconds 20 --trace 0

The cell (BENCHMARK.json ``workloads``) names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``).  The
run builds that fleet from the program's public constructors, warms up
(compiles the window's episode and serves one window warm), then offers the
mix for ``--seconds`` in the loop the mix names: closed (one window of
slots offered, then ``serve()``, again and again) or open (slots offered at
a fixed rate).  ``--trace 0`` prints the end-to-end metrics; ``--trace 1``
then profiles a fixed number of further windows and prints the per-layer
metrics, each read by its own ``metrics/<name>.py``.

After the measured window the plain reference (``chipbench/reference.py``)
recomputes every served slot from the same seed and stream, and a
checkpointing cell's newest committed checkpoint is read from disk;
``correct`` is whether every number compared stays within its limit.  The
last line of
stdout is one JSON object; the numbers compared, beside their limits, are
the last lines of stderr and the result's last key.

Fails (nonzero exit, no result line) when JAX finds no TPU or fewer chips
than the cell asks for, and outside a checkout of this repository.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="write two traced windows of a --trace 1 run, as the "
                         "reduction reads them, to this .json.gz")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: no program at {ROOT / 'src' / 'repro'}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(CHIP_DIR))
    from chipbench import spec

    try:
        cell = spec.find_cell(args.workload, spec.load_benchmark(ROOT))
    except ValueError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from chipbench.harness import run_cell
    from chipbench.serve import CompileClock

    clock = CompileClock()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    print(f"# compile cache: {cache_dir}; {len(devices)} x "
          f"{devices[0].device_kind} at {time.perf_counter() - T_START:.3f} s",
          file=sys.stderr)
    result, checks = run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), clock=clock,
                              t_start=T_START, keep_trace=args.keep_trace)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
