"""From a profiler trace to numbers: device busy and idle time, the time of
named device modules and operations, and the harness's own host spans.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
one event per executed operation and the ``XLA Modules`` line one per
executed program.  The harness wraps every window in ``bench.window`` and
its two calls in ``bench.offer`` / ``bench.serve``
(``jax.profiler.TraceAnnotation``); those spans sit on a host plane, on the
same clock as the device events.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."

CONTAINERS = ("%while", "%conditional", "%call")

Event = Tuple[str, int, int]           # (name, start ns, duration ns)


def short_name(op: str) -> str:
    """An XLA op event's name is its HLO text; keep the instruction name,
    and mark Mosaic kernel launches."""
    head = op.split(" = ", 1)[0]
    return head + (" [tpu_custom_call]" if "tpu_custom_call" in op else "")


def load(trace_dir: Path):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ``ProfileData``."""
    import jax

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(str(files[-1]))


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclass
class Summary:
    """A reduced trace.  ``devices`` maps a device plane to its lines, each
    a list of events; ``spans`` are the harness's host spans."""
    devices: Dict[str, Dict[str, List[Event]]]
    spans: List[Event]
    window: Tuple[int, int]
    busy: Dict[str, List[Tuple[int, int]]] = field(default_factory=dict)

    def __post_init__(self):
        w0, w1 = self.window
        for dev, lines in self.devices.items():
            iv = [(max(s, w0), min(s + d, w1)) for _, s, d in
                  lines.get(OPS_LINE, []) if s + d > w0 and s < w1]
            self.busy[dev] = _merge([i for i in iv if i[1] > i[0]])

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.busy:
            return 0.0
        return sum(sum(e - s for s, e in iv) for iv in self.busy.values()) \
            * 1e-9 / len(self.busy)

    def _events(self, line: str, pattern: str) -> List[Event]:
        rx = re.compile(pattern)
        w0, w1 = self.window
        return [ev for lines in self.devices.values()
                for ev in lines.get(line, [])
                if rx.search(ev[0]) and w0 <= ev[1] < w1]

    def module_s(self, pattern: str) -> Optional[float]:
        """Device seconds of the programs whose name matches, averaged over
        the devices; None when no program matches."""
        evs = self._events(MODULES_LINE, pattern)
        if not evs:
            return None
        return sum(d for _, _, d in evs) * 1e-9 / len(self.devices)

    def op_s(self, pattern: str) -> Optional[float]:
        """Device seconds of the operations whose name matches, summed over
        the devices; None when no operation matches."""
        evs = self._events(OPS_LINE, pattern)
        if not evs:
            return None
        return sum(d for _, _, d in evs) * 1e-9

    def span_s(self, name: str) -> List[Tuple[int, int]]:
        return [(s, s + d) for n, s, d in self.spans if n == name]

    def busy_within(self, s: int, e: int) -> float:
        """Device busy seconds inside [s, e), averaged over the devices."""
        tot = 0
        for iv in self.busy.values():
            tot += sum(max(0, min(b, e) - max(a, s)) for a, b in iv)
        return tot * 1e-9 / max(1, len(self.busy))

    def top_ops(self, n: int) -> List[List]:
        """The operations that took the most device time, control-flow
        containers (whose time is their body's) left out."""
        by: Dict[str, int] = defaultdict(int)
        w0, w1 = self.window
        for lines in self.devices.values():
            for name, s, d in lines.get(OPS_LINE, []):
                short = short_name(name)
                if w0 <= s < w1 and not short.startswith(CONTAINERS):
                    by[short] += d
        k = max(1, len(self.devices))
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[name, d * 1e-9 / k] for name, d in top]

    def top_gaps(self, n: int) -> List[List]:
        """The longest idle gaps of the first device, each named by the
        innermost harness span open at its middle."""
        if not self.busy:
            return []
        iv = self.busy[sorted(self.busy)[0]]
        w0, w1 = self.window
        edges = [w0] + [x for a, b in iv for x in (a, b)] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = sorted(self.spans, key=lambda sp: sp[2])   # innermost first

        def host(mid: int) -> str:
            for name, s, d in spans:
                if s <= mid < s + d:
                    return name
            return "between windows"

        gaps.sort(key=lambda g: g[0] - g[1])
        return [[host((a + b) // 2), (b - a) * 1e-9] for a, b in gaps[:n]]


def extract(pd) -> Dict:
    """The part of a trace the reduction reads, as plain data: the device
    planes' op and module events and the harness's host spans."""
    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices[plane.name] = {
                line.name: [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events]
                for line in plane.lines if line.name in (OPS_LINE,
                                                          MODULES_LINE)}
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)))
    return {"devices": devices, "spans": spans}


def trim(ex: Dict, windows: int) -> Dict:
    """The extract cut to its first ``windows`` harness windows."""
    ws = sorted((s, s + d) for n, s, d in ex["spans"]
                if n == SPAN_PREFIX + "window")[:windows]
    t0, t1 = ws[0][0], ws[-1][1]
    keep = lambda evs: [list(e) for e in evs if t0 <= e[1] < t1]
    return {"devices": {dev: {ln: keep(evs) for ln, evs in lines.items()}
                        for dev, lines in ex["devices"].items()},
            "spans": keep(ex["spans"])}


def reduce(ex: Dict) -> Summary:
    """Summary of an ``extract``."""
    devices = {dev: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
               for dev, lines in ex["devices"].items()}
    spans = [tuple(e) for e in ex["spans"]]
    windows = [(s, s + d) for n, s, d in spans if n == SPAN_PREFIX + "window"]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    window = (min(s for s, _ in windows), max(e for _, e in windows))
    return Summary(devices=devices, spans=spans, window=window)


def describe(pd, out) -> None:
    """Planes, lines and the most frequent event names: for reading a new
    trace by hand."""
    for plane in pd.planes:
        for line in plane.lines:
            names: Dict[str, int] = defaultdict(int)
            n = 0
            for ev in line.events:
                names[ev.name] += 1
                n += 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            print(f"# plane {plane.name!r} line {line.name!r}: {n} events; "
                  f"{top}", file=out)


@dataclass
class Context:
    """What a per-layer metric's reader may read."""
    trace: Summary
    config: Dict
    cell: object
    peaks: Dict[str, float]
    chips: int
    slots: int
    windows: int
    live_cam_slots: int
    compile: Dict[str, float]
    work: object
