"""One run of one cell: set-up, warm-up, the measured window, the traced
windows, the check against the plain reference, and the result line."""
from __future__ import annotations

import gc
import gzip
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench import reference, trace as trace_mod, work
from chipbench.serve import SCRATCH, Loop, build_runner, probe
from chipbench.spec import CHIP_DIR, Cell
from chipbench.traffic import Stream

WARMUP_WINDOWS_MAX = 6     # a window that still compiles after these fails
TRACE_WINDOWS = 8          # windows profiled by a --trace 1 run
ROW_GATE = 1e-5   # a row is off when a key differs by more than this x scale


def load_limits(cell_name: str) -> Dict[str, float]:
    path = CHIP_DIR / "limits" / f"{cell_name}.json"
    if not path.is_file():
        raise ValueError(f"no limits for {cell_name!r}: {path}")
    return json.loads(path.read_text())["limits"]


def load_peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads((CHIP_DIR / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         "peaks.json")
    return table[device_kind]


def load_metric(name: str):
    """The module ``metrics/<name>.py``: its ``read(ctx)`` returns the
    metric or None.  None fails the run, unless the module's
    ``SILENT_WHEN`` says when the metric has nothing to read."""
    path = CHIP_DIR / "metrics" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no reader for per-layer metric {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str) -> Callable:
    return load_metric(name).read


def quantile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def written_bytes() -> Optional[int]:
    """Bytes this process has caused to be written to storage so far."""
    try:
        for line in Path("/proc/self/io").read_text().splitlines():
            if line.startswith("write_bytes:"):
                return int(line.split()[1])
    except OSError:
        pass
    return None


def compare(served: Dict[str, List[float]], ref: Dict[str, np.ndarray]
            ) -> Dict:
    """Every served slot's log row against the reference's.  A row is off
    when any key differs by more than ``ROW_GATE`` times that key's scale
    max(1, max |ref|) (the repository's log gate).  Returns the share of
    rows off (%), the widest gap against its key's scale, and the first
    rows off, for the log."""
    T = len(next(iter(ref.values())))
    off = np.zeros(T, bool)
    widest, first = 0.0, []
    for k, r in ref.items():
        got = np.asarray(served[k], np.float64)
        if got.shape != r.shape:
            return {"rows_off_pct": 100.0, "widest": float("inf"),
                    "first": [f"{k}: shape {got.shape} != {r.shape}"]}
        scale = max(1.0, float(np.max(np.abs(r)))) if T else 1.0
        gap = np.abs(got - r) / scale
        gap = np.where(np.isnan(gap), np.inf, gap)
        off |= gap > ROW_GATE
        if T:
            widest = max(widest, float(np.max(gap)))
        first += [(int(i), k, float(got[i]), float(r[i]))
                  for i in np.nonzero(gap > ROW_GATE)[0][:5]]
    return {"rows_off_pct": 100.0 * float(np.mean(off)) if T else 0.0,
            "widest": widest, "first": sorted(first)[:8]}


class Weights:
    """The weights handed to both the program and the reference."""

    def __init__(self, cfg: Dict):
        from chipbench.weights import load_detector, make_mlp
        self.light = load_detector(cfg["detectors"]["light"])
        self.server = load_detector(cfg["detectors"]["server"])
        self.mlp = make_mlp(int(cfg["control"]["utility_mlp_key"]))

    def ref(self) -> reference.Weights:
        return reference.Weights(self.light, self.server, self.mlp)


def cell_config(cell: Cell) -> Dict:
    """The configuration as served: one chip keeps the fleet unsharded,
    several shard its cameras over a mesh of every chip."""
    cfg = dict(cell.config)
    cfg["system"] = dict(cfg["system"],
                         shard="auto" if cell.chips > 1 else "off")
    cfg["serving"] = dict(cfg["serving"],
                          window_slots=int(cell.mix["window_slots"]))
    return cfg


@dataclass
class Served:
    """A cell's system under test, fed by its loop."""
    cfg: Dict
    weights: Weights
    runner: object
    stream: Stream
    loop: Loop
    ckpt_dir: Optional[Path]
    accepted: List[int] = field(default_factory=list)

    @classmethod
    def build(cls, cell: Cell, seed: int, build: Callable = build_runner
              ) -> "Served":
        cfg = cell_config(cell)
        weights = Weights(cfg)
        ckpt = (SCRATCH / "ckpt" / cell.name) \
            if cfg["serving"]["checkpoint"] else None
        runner = build(cfg, seed, weights, ckpt)
        stream = Stream(cell.mix, cfg["num_cameras"], seed)
        return cls(cfg, weights, runner, stream, Loop(runner, stream), ckpt)

    def warm_up(self, clock, log: Callable[[str], None]) -> int:
        """Serve windows until one compiles nothing at all; returns how many
        were served."""
        for i in range(WARMUP_WINDOWS_MAX):
            events = clock.count
            self.loop.window()
            log(f"warm-up window {i} done, {clock.count - events} compile "
                "events")
            if i > 0 and clock.count == events:
                return i + 1
        raise RuntimeError(f"still compiling after {WARMUP_WINDOWS_MAX} "
                           "warm-up windows")

    def close(self) -> Tuple[Dict[str, List[float]], int]:
        """Flush the runner; returns its logs and how many slots were
        offered.  The program's state is dropped."""
        self.runner.close()
        logs = {k: list(v) for k, v in self.runner.logs.items()}
        offered = self.loop.t_next
        self.accepted = list(self.loop.accepted)
        self.runner = self.loop = None
        gc.collect()
        return logs, offered


def check(served: Served, seed: int, logs: Dict[str, List[float]]
          ) -> Tuple[Dict, Dict]:
    """The plain reference over every served slot, from the same seed and
    the stream's rows the runner accepted, in order; returns the comparison
    and the reference's rows."""
    idx = np.asarray(served.accepted[:len(logs["utility"])], np.int64)
    ref = reference.run(served.cfg, served.weights.ref(), seed,
                        served.stream.bandwidth[idx], served.stream.live[idx])
    return compare(logs, ref), ref


def checkpoint_behind(ckpt_dir: Path, windows: int) -> int:
    """Windows served after the newest committed checkpoint under
    ``ckpt_dir``, read from the directory and its manifests as they lie on
    disk (the program's restore is never called).  A generation counts
    when its commit marker is there and its manifest names the window it
    was saved after and holds the log rows of every slot served to then."""
    newest = 0
    for d in sorted(ckpt_dir.glob("window_*")) if ckpt_dir.is_dir() else []:
        if d.name.endswith(".tmp") or not (d / "COMMITTED").is_file():
            continue
        try:
            m = json.loads((d / "manifest.json").read_text())
            step, meta = int(m["step"]), m["metadata"]
            rows = len(meta["logs"]["utility"])
        except (OSError, ValueError, KeyError, TypeError):
            continue
        if step == int(meta["window"]) and rows == int(meta["t_next"]) \
                - int(meta["dropped_slots"]):
            newest = max(newest, step)
    return windows - newest


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, clock,
             t_start: float, keep_trace: Optional[str] = None,
             build: Callable = build_runner) -> Tuple[Dict, Dict]:
    import jax

    limits = load_limits(cell.name)
    wrote0 = written_bytes()

    def log(msg: str) -> None:
        print(f"# {cell.name}: {msg} at {time.perf_counter() - t_start:.3f} "
              "s", file=sys.stderr, flush=True)
    sv = Served.build(cell, seed, build)
    C = sv.cfg["num_cameras"]
    log("runner built")
    warm = sv.warm_up(clock, log)
    setup_s = time.perf_counter() - t_start
    setup_compile = dict(clock.secs)
    log(f"set-up {setup_s:.3f} s, {warm} warm-up windows, compile "
        f"{setup_compile}")

    # -- the measured window
    loop = sv.loop
    p0, events0, w0 = probe(), clock.count, sv.runner.window
    s0 = len(loop.done_at)
    loop.run_for(seconds)
    p1, events1 = probe(), clock.count
    n_win = sv.runner.window - w0
    arrive, done = loop.arrive_at[s0:len(loop.done_at)], loop.done_at[s0:]
    lat_ms = [(d - a) * 1e3 for a, d in zip(arrive, done)]
    elapsed = done[-1] - arrive[0]
    n_served = len(done)
    q = max(1, n_served // 4)
    log(f"window: {n_win} windows, {n_served} slots in {elapsed:.4f} s; "
        f"episode compiles inside {p1[0] - p0[0]}, compile events inside "
        f"{events1 - events0}, harvest fetches per window "
        f"{(p1[1] - p0[1]) / n_win!r}, rung {sv.runner.stats()['rung']}, "
        f"dropped {loop.dropped}")
    log(f"decision ms p50/p95 first quarter {quantile(lat_ms[:q], 50):.3f}/"
        f"{quantile(lat_ms[:q], 95):.3f}, last quarter "
        f"{quantile(lat_ms[-q:], 50):.3f}/{quantile(lat_ms[-q:], 95):.3f}")

    metrics: Dict[str, Dict] = {}
    breakdown = None
    device_extra: Dict[str, float] = {}
    if not trace:
        values = {"segments_per_s": n_served * C / elapsed,
                  "decision_p95_ms": quantile(lat_ms, 95),
                  "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        tdir = SCRATCH / "trace" / cell.name
        shutil.rmtree(tdir, ignore_errors=True)
        d0, t0 = len(loop.done_at), loop.t_next
        with jax.profiler.trace(str(tdir)):
            loop.run_windows(TRACE_WINDOWS)
        traced_slots = len(loop.done_at) - d0
        live_cam_slots = int(sv.stream.live[t0:loop.t_next].sum())
        log(f"traced {traced_slots} slots")
        pd = trace_mod.load(tdir)
        extract = trace_mod.extract(pd)
        summary = trace_mod.reduce(extract)
        log("trace read")
        if keep_trace:
            # a few windows of the extract, small enough to keep as a fixture
            trace_mod.describe(pd, sys.stderr)
            with gzip.open(keep_trace, "wt") as f:
                json.dump(trace_mod.trim(extract, 2), f)
        del pd, extract
        shutil.rmtree(tdir, ignore_errors=True)
        kind = jax.devices()[0].device_kind
        ctx = trace_mod.Context(
            trace=summary, config=sv.cfg, cell=cell, peaks=load_peaks(kind),
            chips=cell.chips, slots=traced_slots,
            windows=max(1, traced_slots // sv.stream.window_slots),
            live_cam_slots=live_cam_slots,
            compile=setup_compile, work=work)
        for m in cell.per_layer:
            reader = load_metric(m["name"])
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
            elif getattr(reader, "SILENT_WHEN", None):
                log(f"per-layer metric {m['name']} found nothing to read, "
                    f"left out ({reader.SILENT_WHEN})")
            else:
                raise RuntimeError(f"per-layer metric {m['name']} found "
                                   "nothing to read in this run's trace")
        device_extra = {"busy_s": summary.busy_s, "window_s": summary.window_s}
        breakdown = {"device_ops": summary.top_ops(10),
                     "idle_gaps": summary.top_gaps(10)}

    # -- close the window: peak memory, then free the program's state
    windows_served = sv.runner.window
    logs, offered = sv.close()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:cell.chips])
    wrote1 = written_bytes()
    if wrote0 is not None and wrote1 is not None:
        log(f"wrote {wrote1 - wrote0} bytes to storage")
    T = len(logs["utility"])
    nonfinite = sum(1 for i in range(T)
                    if not all(np.isfinite(logs[k][i]) for k in logs))

    # -- the check: the reference recomputes every served slot
    t_ref = time.perf_counter()
    cmp, _ = check(sv, seed, logs)
    log(f"reference: {T} slots in {time.perf_counter() - t_ref:.3f} s; rows "
        f"off {cmp['rows_off_pct']!r}%, widest gap {cmp['widest']!r}, first "
        f"rows off (slot, key, served, reference) {cmp['first']}")
    checks = {"rows_off_pct": {"value": cmp["rows_off_pct"],
                               "limit": limits["rows_off_pct"]},
              "unserved_slots": {"value": offered - loop.dropped - T,
                                 "limit": limits["unserved_slots"]}}
    if sv.ckpt_dir is not None:
        checks["ckpt_windows_behind"] = {
            "value": checkpoint_behind(sv.ckpt_dir, windows_served),
            "limit": limits["ckpt_windows_behind"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    # every slot offered after warm-up: dropped or non-finite ones failed
    attempted = offered - warm * sv.stream.window_slots
    dev = jax.devices()[0]
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(offered - T + nonfinite), "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": peak, **device_extra}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    log("run ends")
    return result, checks
