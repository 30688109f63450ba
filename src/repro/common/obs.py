"""Host spans and transfer counters of the serving path.

``span(name)`` opens a ``jax.profiler.TraceAnnotation``, so a profiler
trace shows the span on the host plane, on the same clock as the device's
operations, and it keeps the span's most recent durations (at most
``RECENT`` per name) so that an always-on stream can say where its host
time goes without a profiler attached (``StreamingFleetRunner.stats()``
reports the median of each ``SERVE_SPANS`` entry; the record is per
process).  The names are the serving loop's phases: ``serve.prep`` /
``serve.device`` / ``serve.harvest`` for one episode dispatch,
``serve.logs`` / ``serve.supervise`` / ``serve.checkpoint`` for the rest of
a window, and ``ckpt.*`` inside a checkpoint save.

``count_fetch(kind)`` counts device-to-host fetches per category
(``D2H_CATEGORIES``), so the zero-per-slot-transfer contracts are checkable
on backends where the transfer guard never fires.  ``count_put(kind)``
counts host-to-device puts per category (``H2D_CATEGORIES``) and
``count_operand_cache(kind)`` the episode operand cache's builds and reuses
(``OPERAND_CACHE_EVENTS``); ``StreamingFleetRunner.stats()`` reports both.
``record_kernel_grid(kernel, grid, block, tile)`` records, when a fleet
kernel's wrapper traces, the grid, input block and in-kernel tile it chose
(``kernel_grid_counts()``), and ``record_detector_stem(path, fold, shape)``
which path the detector's stem traced on which frames
(``detector_stem_counts()``).
"""
from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager
from typing import Deque, Dict, Iterator, List, Tuple

import jax

RECENT = 1024          # durations kept per span name
SERVE_SPANS = ("serve.prep", "serve.device", "serve.harvest", "serve.logs",
               "serve.supervise", "serve.checkpoint")

_recent: Dict[str, Deque[float]] = {}


@contextmanager
def span(name: str) -> Iterator[None]:
    """A named host span: a profiler annotation plus a bounded record of
    its wall time (seconds)."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        record = _recent.get(name)
        if record is None:
            record = _recent[name] = deque(maxlen=RECENT)
        record.append(time.perf_counter() - t0)


def recent(name: str) -> List[float]:
    """The last (at most ``RECENT``) durations of span ``name`` in seconds,
    oldest first."""
    return list(_recent.get(name, ()))


class _Counts:
    """Per-category event counts since process start."""

    def __init__(self, categories: Tuple[str, ...], what: str):
        self.categories = categories
        self.what = what
        self._n: Dict[str, int] = {}

    def add(self, kind: str) -> None:
        if kind not in self.categories:
            raise ValueError(f"unknown {self.what} category {kind!r}")
        self._n[kind] = self._n.get(kind, 0) + 1

    def snapshot(self) -> Dict[str, int]:
        """Every category, zero-initialized."""
        return {k: self._n.get(k, 0) for k in self.categories}


# Categories: 'harvest' (packed log fetches), 'keep' (reducto keep-flag
# fetches, sequential mode only), 'control' (the host control path's (a, c)
# sync), 'checkpoint' (one per carry leaf a checkpoint save copies to the
# host).  Episode runs leave 'keep' and 'control' at zero and add exactly
# two 'harvest' fetches per run.
D2H_CATEGORIES = ("harvest", "keep", "control", "checkpoint")
_fetches = _Counts(D2H_CATEGORIES, "fetch")
count_fetch = _fetches.add


def d2h_fetch_counts() -> Dict[str, int]:
    """Snapshot of the per-category D2H fetch counters since process start
    (every category always present, zero-initialized)."""
    return _fetches.snapshot()


# Categories: 'episode' (the one batched put of a window's operands, one
# per prepared episode), 'invariants' (one per build of the operands every
# window shares; see OPERAND_CACHE_EVENTS).
H2D_CATEGORIES = ("episode", "invariants")
_puts = _Counts(H2D_CATEGORIES, "put")
count_put = _puts.add


def h2d_put_counts() -> Dict[str, int]:
    """Snapshot of the per-category H2D put counters since process start."""
    return _puts.snapshot()


# The episode operand cache ('built': the window invariants were placed
# anew, 'reused': a window took the cached ones).
OPERAND_CACHE_EVENTS = ("built", "reused")
_operand_cache = _Counts(OPERAND_CACHE_EVENTS, "operand-cache")
count_operand_cache = _operand_cache.add


def operand_cache_counts() -> Dict[str, int]:
    """Snapshot of the episode operand cache's builds and reuses."""
    return _operand_cache.snapshot()


# Fleet kernel tilings as traced: kernel -> {(grid, input block, tile):
# traces}.  The tile is the (rows, columns) one step of the kernel body
# works on, halo included.
Tiling = Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]]
_kernel_grids: Dict[str, Dict[Tiling, int]] = {}


def record_kernel_grid(kernel: str, grid: Tuple[int, ...],
                       block: Tuple[int, ...], tile: Tuple[int, ...]
                       ) -> None:
    """Count one trace of ``kernel`` launched over ``grid`` with input
    blocks of shape ``block``, its body working on ``tile``s."""
    seen = _kernel_grids.setdefault(kernel, {})
    key = tuple(tuple(int(d) for d in x) for x in (grid, block, tile))
    seen[key] = seen.get(key, 0) + 1


def kernel_grid_counts() -> Dict[str, Dict[Tiling, int]]:
    """Snapshot of every (grid, input block, tile) each fleet kernel was
    traced with, and how many times."""
    return {k: dict(v) for k, v in _kernel_grids.items()}


# Detector stem paths as traced: (path, fold, frames shape) -> traces.  The
# path is 'folded' (the stem on frames folded fold x fold into channels) or
# 'plain' (fold 1).
Stem = Tuple[str, int, Tuple[int, ...]]
_detector_stems: Dict[Stem, int] = {}


def record_detector_stem(path: str, fold: int, shape: Tuple[int, ...]
                         ) -> None:
    """Count one trace of the detector stem on ``path`` over frames of
    ``shape``."""
    key = (path, int(fold), tuple(int(d) for d in shape))
    _detector_stems[key] = _detector_stems.get(key, 0) + 1


def detector_stem_counts() -> Dict[Stem, int]:
    """Snapshot of every (path, fold, frames shape) the detector stem was
    traced with, and how many times."""
    return dict(_detector_stems)
