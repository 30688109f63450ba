"""The one traffic generator: pure in (seed, period), the same slots
however long a run gets, the shapes of the committed mixes, a new family
from a data file alone, and the open loop's arrivals."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP_DIR))

from chipbench.serve import Loop  # noqa: E402
from chipbench.traffic import (Stream, bandwidth_period,  # noqa: E402
                               liveness_period, load_mix)

SOAK = load_mix("soak")
FCC = load_mix("fcc")
BIG_SEED = 2 ** 31 + 12345


def _bw(mix, cams, seed, k):
    return bandwidth_period(mix["bandwidth"], mix["period_slots"], cams, seed,
                            k)


def _live(mix, cams, seed, k):
    return liveness_period(mix["liveness"], mix["period_slots"], cams, seed,
                           k)


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_periods_are_pure(seed):
    for mix in (SOAK, FCC):
        for k in (0, 3):
            np.testing.assert_array_equal(_bw(mix, 5, seed, k),
                                          _bw(mix, 5, seed, k))
            np.testing.assert_array_equal(_live(mix, 5, seed, k),
                                          _live(mix, 5, seed, k))
        assert not np.array_equal(_bw(mix, 5, seed, 0), _bw(mix, 5, seed, 3))


def test_first_period_does_not_depend_on_run_length():
    short, long_ = Stream(SOAK, 5, 11), Stream(SOAK, 5, 11)
    bw_s, lv_s = short.slots(0, 8)
    long_.slots(2992, 3000)            # reaches into the third period first
    bw_l, lv_l = long_.slots(0, 1000)
    np.testing.assert_array_equal(bw_s, bw_l[:8])
    np.testing.assert_array_equal(lv_s, lv_l[:8])
    ref = Stream(SOAK, 5, 11)
    np.testing.assert_array_equal(ref.slots(0, 1000)[0], bw_l)
    assert len(long_.bandwidth) == 3000


def test_stream_differs_by_seed():
    for mix in (SOAK, FCC):
        a, b = Stream(mix, 5, 1), Stream(mix, 5, 2)
        assert not np.array_equal(a.slots(0, 100)[0], b.slots(0, 100)[0])


@pytest.mark.parametrize("cams", [5, 25])
def test_soak_shape(cams):
    bw, live = _bw(SOAK, cams, 3, 0), _live(SOAK, cams, 3, 0)
    assert bw.shape == (1000,) and live.shape == (1000, cams)
    assert bw.min() >= SOAK["bandwidth"]["floor_kbps"]
    # sized for 5 cameras, rescaled linearly with the fleet
    mean = SOAK["bandwidth"]["regimes"][0]["mean_kbps"] * cams / 5
    assert 0.6 * mean < bw.mean() < 1.4 * mean
    assert live[:, 0].all() and live.any(axis=1).all()
    for c in range(1, cams):           # one contiguous live window each
        on = np.flatnonzero(live[:, c])
        assert len(on) >= 500 and on[-1] - on[0] + 1 == len(on)


@pytest.mark.parametrize("cams", [5, 25])
def test_fcc_shape(cams):
    """The paper's three regimes in turn, every camera live, the 64 Kbps
    floor of the 5-camera traces kept through the rescale."""
    st = Stream(FCC, cams, BIG_SEED)
    n = FCC["period_slots"]
    bw, live = st.slots(0, 30 * n)
    assert live.all()
    assert bw.min() >= 64.0 * max(1, cams / 5) - 1e-9
    regimes = FCC["bandwidth"]["regimes"]
    assert [r["mean_kbps"] for r in regimes] == [521.0, 1134.0, 2305.0]
    for i, r in enumerate(regimes):
        x = np.concatenate([bw[k * n:(k + 1) * n] for k in range(i, 30, 3)])
        # clipping at the floor lifts the low regime's mean a little
        assert x.mean() == pytest.approx(r["mean_kbps"] * cams / 5, rel=0.15)


@pytest.mark.parametrize("config", ["s01-deepstream", "s01-reducto",
                                    "s04-deepstream"])
def test_fcc_stays_under_the_allocator_pin(config):
    """The served allocator refuses a trace whose top slot plus the largest
    elastic borrow exceeds its pin: the ceiling keeps every seed's stream
    under it, while the high regime's tail still reaches the ceiling."""
    cfg = json.loads((CHIP_DIR / "configs" / f"{config}.json").read_text())
    cams = cfg["num_cameras"]
    borrow = cfg["elastic"]["budget_kbits"] / cfg["elastic"]["slot_seconds"]
    top = max(Stream(FCC, cams, seed).slots(0, 30 * FCC["period_slots"])[0]
              .max() for seed in (3, 2971234567, BIG_SEED))
    assert top + borrow <= cfg["system"]["w_cap_kbps"]
    ceiling = FCC["bandwidth"]["ceiling_kbps"] * cams / 5
    assert top == ceiling


def test_unknown_family_is_refused(tmp_path):
    """A mix is parameters only: an unknown loop, or a parameter the
    generator does not know, is refused rather than ignored."""
    for name, change in (("loop", {"loop": {"kind": "burst"}}),
                         ("typo", {"bandwidth": dict(SOAK["bandwidth"],
                                                     swing_kpbs=1.0)})):
        (tmp_path / f"{name}.json").write_text(json.dumps({**SOAK, **change}))
    with pytest.raises(ValueError, match="loop kind"):
        load_mix("loop", tmp_path)
    with pytest.raises(ValueError, match="unknown parameter"):
        load_mix("typo", tmp_path)


def test_a_new_family_is_only_a_data_file(tmp_path):
    """The program's adversarial sawtooth with link spikes, every camera
    live, served open loop: a new mix file, read by the same generator."""
    saw = {"name": "sawtooth", "about": "ramp and crash", "window_slots": 8,
           "loop": {"kind": "open", "slots_per_s": 100.0},
           "period_slots": 60,
           "bandwidth": {"regimes": [{"mean_kbps": 150.0, "sd_kbps": 60.0}],
                         "ramp_kbps": 3050.0, "ramp_slots": [3, 5],
                         "rho": 0.0, "spike_share": 0.1,
                         "spike_kbps": [2500.0, 6000.0], "floor_kbps": 64.0,
                         "reference_cams": 5},
           "liveness": {"anchor_cams": 1, "live_share": [1.0, 1.0]}}
    (tmp_path / "sawtooth.json").write_text(json.dumps(saw))
    mix = load_mix("sawtooth", tmp_path)
    bw, live = Stream(mix, 5, BIG_SEED).slots(0, 600)
    assert live.all() and bw.min() >= 64.0
    assert bw.max() > 2500.0 and np.percentile(bw, 10) < 600.0


class _Runner:
    """Takes offers into a queue and serves whole windows, as the fleet's
    runner does, with a fixed cost per window."""

    def __init__(self, window, queue, cost):
        self.window, self.queue, self.cost = window, queue, cost
        self.queued, self.logs = 0, {"utility": []}

    def offer(self, bw, faults=None):
        take = min(len(bw), self.queue - self.queued)
        self.queued += take
        return take

    def serve(self, flush=False):
        import time
        while self.queued >= self.window or (flush and self.queued):
            n = min(self.window, self.queued)
            time.sleep(self.cost)
            self.queued -= n
            self.logs["utility"] += [0.0] * n


def test_open_loop_offers_at_its_rate():
    mix = dict(FCC, loop={"kind": "open", "slots_per_s": 400.0})
    loop = Loop(_Runner(8, 64, 0.002), Stream(mix, 5, 3))
    loop.run_for(0.5)
    assert loop.t_next == 200 and len(loop.done_at) == 200
    assert loop.accepted == list(range(200)) and loop.dropped == 0
    gaps = np.diff(loop.arrive_at)
    assert gaps == pytest.approx(np.full(199, 1 / 400.0))
    lat = np.subtract(loop.done_at, loop.arrive_at)
    assert (lat > 0).all() and lat.max() < 0.2


def test_open_loop_above_capacity_sheds():
    """Offered faster than it is served, the queue fills and slots are
    dropped; the slots served are the ones accepted, in order."""
    mix = dict(FCC, loop={"kind": "open", "slots_per_s": 2000.0})
    loop = Loop(_Runner(8, 16, 0.02), Stream(mix, 5, 3))
    loop.run_for(0.4)
    assert loop.dropped > 0
    assert len(loop.done_at) == len(loop.accepted) == loop.t_next - loop.dropped
    assert loop.accepted == sorted(loop.accepted)
