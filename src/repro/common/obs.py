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
on backends where the transfer guard never fires.
"""
from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager
from typing import Deque, Dict, Iterator, List

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


# Categories: 'harvest' (packed log fetches), 'keep' (reducto keep-flag
# fetches, sequential mode only), 'control' (the host control path's (a, c)
# sync), 'checkpoint' (one per carry leaf a checkpoint save copies to the
# host).  Episode runs leave 'keep' and 'control' at zero and add exactly
# two 'harvest' fetches per run.
D2H_CATEGORIES = ("harvest", "keep", "control", "checkpoint")
_fetches: Dict[str, int] = {}


def count_fetch(kind: str) -> None:
    if kind not in D2H_CATEGORIES:
        raise ValueError(f"unknown fetch category {kind!r}")
    _fetches[kind] = _fetches.get(kind, 0) + 1


def d2h_fetch_counts() -> Dict[str, int]:
    """Snapshot of the per-category D2H fetch counters since process start
    (every category always present, zero-initialized)."""
    return {k: _fetches.get(k, 0) for k in D2H_CATEGORIES}
