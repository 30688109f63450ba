"""The reader of the detectors' ``stem`` scope (``stem_us_per_slot``) on the
chip fixtures: their program predates the scope, so the reader leaves the
metric out and ``detect_us_per_slot`` reads what it read before; with some
``detect`` operations relabelled as a nested ``stem``, the reader gives
their op time per slot and ``detect`` still counts them."""
import gzip
import json
import sys
from pathlib import Path

import pytest

CHIP_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP_DIR.parents[1] / "src"))
sys.path.insert(0, str(CHIP_DIR))

from chipbench import scopes, spec, trace, work  # noqa: E402
from chipbench.harness import load_peaks, load_reader  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SLOTS = 16                                   # 2 windows of 8 slots
# detect_us_per_slot on the fixtures, as read before the stem scope
DETECT = {"s01-deepstream": 152.28418750000003, "s01-reducto": 236.628625}


@pytest.fixture(params=sorted(DETECT))
def fixture(request):
    with gzip.open(DATA / f"trace_{request.param}.spans.json.gz", "rt") as f:
        fx = json.load(f)
    scopes._memo.clear()
    yield request.param, fx
    scopes._memo.clear()


def _ctx(config, fx):
    return trace.Context(
        trace=trace.reduce(fx), config=spec.load_config(config), cell=None,
        peaks=load_peaks("TPU v5 lite"), chips=1, slots=SLOTS, windows=2,
        live_cam_slots=SLOTS * 5, compile={}, work=work)


def test_stem_reader_reads_nothing_without_the_scope(fixture, monkeypatch):
    config, fx = fixture
    monkeypatch.setattr(scopes, "live_scope_maps", lambda: [fx["scopes"]])
    ctx = _ctx(config, fx)
    assert load_reader("stem_us_per_slot")(ctx) is None
    assert load_reader("detect_us_per_slot")(ctx) == pytest.approx(
        DETECT[config], rel=1e-12)


def test_stem_reader_reads_the_nested_scope(fixture, monkeypatch):
    """The server detector's convolutions relabelled as ``stem``."""
    config, fx = fixture
    base = fx["scopes"]
    nested = {n: ("stem" if s == "detect" and n.startswith("convolution")
                  else None, t) for n, (s, t) in base.items()}
    assert any(s == "stem" for s, _ in nested.values())
    monkeypatch.setattr(
        scopes, "live_scope_maps",
        lambda: [nested if scopes.SCOPES == ("stem",) else base])
    ctx = _ctx(config, fx)
    want = {n for n, (s, _) in nested.items() if s == "stem"}
    ops = [d for n, _, d in scopes.episode_ops(ctx.trace) if n in want]
    got = load_reader("stem_us_per_slot")(ctx)
    assert got == pytest.approx(1e6 * sum(ops) * 1e-9 / SLOTS)
    assert 0 < got < load_reader("detect_us_per_slot")(ctx) == \
        pytest.approx(DETECT[config], rel=1e-12)
    assert scopes.SCOPES[0] == "scene"          # the patch is undone
