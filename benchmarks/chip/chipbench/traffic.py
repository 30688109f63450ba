"""The one traffic generator: a mix file of parameters -> the offered stream.

A mix (``traffic/<name>.json``) is data only.  It fixes how the fleet's
uplink bandwidth moves, which cameras are live, how offers are grouped into
windows and whether the loop is closed or open.  Every family of traffic is
a setting of the same parameters, so a new mix is a new file and never new
code.

The stream is built in consecutive periods of ``period_slots`` slots.
Period ``k`` of seed ``s`` draws from its own generators keyed on
``(s, k, part)``, so a run sees the same slots however far it gets, and a
period's shape (a regime, a sinusoid, a churn stretch) never stretches with
the run's length.

Bandwidth (Kbps for the whole fleet), per period::

    mean  = regimes[k % len(regimes)]            # {"mean_kbps", "sd_kbps"}
    mu(t) = mean_kbps + swing_kbps * sin(2 pi t / period_slots + phase)
            + ramp_kbps * ((t % r) / (r - 1)),   r drawn from ramp_slots
    x     = AR(1) around mu, stationary sd sd_kbps, coefficient rho
    x     = a spike_kbps draw on a spike_share of slots
    x     = min(x, ceiling_kbps)                   # the link's top rate
    x     = max(floor_kbps, max(floor_kbps, x) * num_cams / reference_cams)

The paper's FCC regimes (section 7.1) are three ``regimes`` with no swing,
ramp or spikes; a diurnal load curve is one regime with a swing; a sawtooth
is a ramp with ``rho`` 0.  ``ceiling_kbps`` (no ceiling when absent) is the
link's top rate: a served fleet's allocator is sized for a fixed top
bandwidth (``w_cap_kbps``, less the largest elastic borrow) and refuses a
trace above it, so a mix whose tail reaches past that sets a ceiling.  Liveness: the first ``anchor_cams`` cameras are
live throughout; every other camera is live over one contiguous stretch
whose length is a share of the period drawn from ``live_share``
(``[1, 1]``: every camera live throughout).

The loop: ``{"kind": "closed"}`` offers one window and serves it, again and
again; ``{"kind": "open", "slots_per_s": r}`` offers slot ``i`` at ``i / r``
seconds and serves whenever a window's worth is queued.

The FCC parameters and the AR(1) draw order are copied from the program's
``data/synthetic.py`` (``FCC_PARAMS``, ``ar1_trace``), the diurnal, churn
and sawtooth shapes from ``data/scenarios.py``, so that a change there
cannot move the benchmark's traffic.
"""
from __future__ import annotations

import json
import zlib
from pathlib import Path
from typing import Dict, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"
LOOPS = ("closed", "open")
# every parameter a mix may set; a name outside these is a typo, refused
KEYS = {
    "": {"name", "about", "loop", "window_slots", "period_slots",
         "bandwidth", "liveness"},
    "bandwidth": {"regimes", "swing_kbps", "ramp_kbps", "ramp_slots", "rho",
                  "spike_share", "spike_kbps", "floor_kbps", "ceiling_kbps",
                  "reference_cams"},
    "liveness": {"anchor_cams", "live_share"},
    "loop": {"kind", "slots_per_s"},
}


def load_mix(name: str, directory: Path = None) -> Dict:
    path = (directory or TRAFFIC_DIR) / f"{name}.json"
    if not path.is_file():
        raise ValueError(f"unknown traffic mix {name!r}: no {path}")
    mix = json.loads(path.read_text())
    for part, allowed in KEYS.items():
        unknown = set(mix[part] if part else mix) - allowed
        if unknown:
            raise ValueError(f"mix {name!r}: unknown parameter(s) "
                             f"{sorted(unknown)} in {part or 'the mix'}")
    kind = mix["loop"]["kind"]
    if kind not in LOOPS:
        raise ValueError(f"mix {name!r}: loop kind {kind!r} is not one of "
                         f"{LOOPS}")
    if kind == "open" and not float(mix["loop"]["slots_per_s"]) > 0:
        raise ValueError(f"mix {name!r}: an open loop needs slots_per_s > 0")
    return mix


def _rng(seed: int, period: int, part: str) -> np.random.Generator:
    return np.random.default_rng((int(seed), int(period),
                                  zlib.crc32(part.encode())))


def ar1(rng: np.random.Generator, mu: np.ndarray, sd: float,
        rho: float) -> np.ndarray:
    """AR(1) around a per-slot mean: innovations first, then x[0]."""
    n = len(mu)
    eps = rng.normal(0, sd * np.sqrt(1 - rho ** 2), n)
    x = np.empty(n)
    x[0] = mu[0] + rng.normal(0, sd)
    for t in range(1, n):
        x[t] = mu[t] + rho * (x[t - 1] - mu[t]) + eps[t]
    return x


def bandwidth_period(p: Dict, period_slots: int, num_cams: int, seed: int,
                     period: int) -> np.ndarray:
    """One period of the bandwidth trace, in Kbps for the whole fleet."""
    n = int(period_slots)
    rng = _rng(seed, period, "bandwidth")
    regime = p["regimes"][period % len(p["regimes"])]
    t = np.arange(n)
    mu = np.full(n, float(regime["mean_kbps"]))
    phase = rng.uniform(0, 2 * np.pi)
    mu += float(p.get("swing_kbps", 0.0)) * np.sin(2 * np.pi * t / n + phase)
    lo, hi = p.get("ramp_slots", [2, 2])
    r = int(rng.integers(int(lo), int(hi) + 1))
    mu += float(p.get("ramp_kbps", 0.0)) * ((t % r) / max(r - 1, 1))
    x = ar1(rng, mu, float(regime["sd_kbps"]), float(p["rho"]))
    spikes = rng.uniform(size=n) < float(p.get("spike_share", 0.0))
    s_lo, s_hi = p.get("spike_kbps", [0.0, 0.0])
    x = np.where(spikes, rng.uniform(float(s_lo), float(s_hi), n), x)
    x = np.minimum(x, float(p.get("ceiling_kbps", np.inf)))
    floor = float(p["floor_kbps"])
    scaled = np.maximum(x, floor) * (num_cams / float(p["reference_cams"]))
    return np.maximum(scaled, floor)


def liveness_period(p: Dict, period_slots: int, num_cams: int, seed: int,
                    period: int) -> np.ndarray:
    """One period of the (slots, cameras) liveness mask, True = live."""
    n = int(period_slots)
    rng = _rng(seed, period, "liveness")
    anchors = max(1, min(int(p["anchor_cams"]), num_cams))
    live = np.zeros((n, num_cams), bool)
    live[:, :anchors] = True
    lo_share, hi_share = (float(v) for v in p["live_share"])
    lo = max(1, int(round(n * lo_share)))
    hi = max(lo, int(round(n * hi_share)))
    for c in range(anchors, num_cams):
        width = int(rng.integers(lo, hi + 1))
        t0 = int(rng.integers(0, n - width + 1))
        live[t0:t0 + width, c] = True
    return live


class Stream:
    """The offered stream of one run: slots are generated a period at a
    time, on demand, and never change once generated."""

    def __init__(self, mix: Dict, num_cams: int, seed: int):
        self.mix = mix
        self.num_cams = int(num_cams)
        self.seed = int(seed)
        self.window_slots = int(mix["window_slots"])
        self.period_slots = int(mix["period_slots"])
        self.bandwidth = np.zeros((0,))
        self.live = np.zeros((0, self.num_cams), bool)
        self._periods = 0

    def ensure(self, n_slots: int) -> None:
        """Generate whole periods until slot ``n_slots - 1`` exists."""
        while len(self.bandwidth) < n_slots:
            k = self._periods
            args = (self.period_slots, self.num_cams, self.seed, k)
            self.bandwidth = np.concatenate(
                [self.bandwidth, bandwidth_period(self.mix["bandwidth"], *args)])
            self.live = np.concatenate(
                [self.live, liveness_period(self.mix["liveness"], *args)])
            self._periods += 1

    def slots(self, t0: int, t1: int) -> Tuple[np.ndarray, np.ndarray]:
        """(bandwidth (t1-t0,), liveness (t1-t0, C)) of slots [t0, t1)."""
        self.ensure(t1)
        return self.bandwidth[t0:t1], self.live[t0:t1]
