"""The trace reduction and the per-layer readers, on two windows of a
profiler trace recorded on a v5e chip (``data/trace_<config>.json.gz``: the
harness's extract of the device planes and its host spans, trimmed)."""
import gzip
import json
import sys
from pathlib import Path

import pytest

CHIP_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP_DIR))

from chipbench import spec, trace, work  # noqa: E402
from chipbench.harness import load_peaks, load_reader  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
CONFIGS = ["s01-deepstream", "s01-reducto"]
SLOTS = 16                                   # 2 windows of 8 slots


def _extract(config):
    with gzip.open(DATA / f"trace_{config}.json.gz", "rt") as f:
        return json.load(f)


def _ctx(config, summary, live_cam_slots=SLOTS * 5, compile_=None):
    return trace.Context(
        trace=summary, config=spec.load_config(config), cell=None,
        peaks=load_peaks("TPU v5 lite"), chips=1, slots=SLOTS, windows=2,
        live_cam_slots=live_cam_slots, compile=compile_ or {}, work=work)


def _ops(ex):
    (dev,) = ex["devices"].values()
    return dev[trace.OPS_LINE]


def _union_ns(intervals):
    """Covered length of a set of intervals, by a sweep over the edges."""
    edges = sorted([(s, 1) for s, _ in intervals]
                   + [(e, -1) for _, e in intervals])
    total, depth, last = 0, 0, None
    for x, d in edges:
        if depth > 0:
            total += x - last
        depth += d
        last = x
    return total


@pytest.mark.parametrize("config", CONFIGS)
def test_window_busy_and_idle(config):
    ex = _extract(config)
    s = trace.reduce(ex)
    wins = [(st, st + d) for n, st, d in ex["spans"] if n == "bench.window"]
    assert len(wins) == 2 and s.window == (min(wins)[0], max(wins)[1])
    w0, w1 = s.window
    busy = _union_ns([(max(st, w0), min(st + d, w1)) for _, st, d in _ops(ex)
                      if st + d > w0 and st < w1])
    assert s.busy_s == pytest.approx(busy * 1e-9, rel=1e-12)
    assert 0 < s.busy_s < s.window_s
    idle = load_reader("device_idle_share")(_ctx(config, s))
    assert idle == pytest.approx(100 * (1 - busy / (w1 - w0)), rel=1e-12)
    assert 5 < idle < 95


@pytest.mark.parametrize("config", CONFIGS)
def test_episode_time_per_slot(config):
    ex = _extract(config)
    (dev,) = ex["devices"].values()
    episode = [d for n, _, d in dev[trace.MODULES_LINE]
               if n.startswith("jit_counted(")]
    assert len(episode) == 2                       # one per window
    got = load_reader("episode_device_ms_per_slot")(_ctx(config,
                                                         trace.reduce(ex)))
    assert got == pytest.approx(1e-6 * sum(episode) / SLOTS, rel=1e-12)
    # the episode is most of what the device runs
    s = trace.reduce(ex)
    assert 0.9 * s.busy_s < 1e-9 * sum(episode) < s.window_s


@pytest.mark.parametrize("config", CONFIGS)
def test_kernel_time_per_slot(config):
    ex = _extract(config)
    s = trace.reduce(ex)
    launches = [(n, d) for n, _, d in _ops(ex) if "tpu_custom_call" in n]
    codec = [d for n, d in launches if n.startswith("%encode_fleet")]
    motion = [d for n, d in launches if not n.startswith("%encode_fleet")]
    # one launch of each kernel per slot, and nothing else is Mosaic
    assert len(codec) == SLOTS and len(motion) == SLOTS
    assert all(",162]" in n for n, _ in launches
               if not n.startswith("%encode_fleet"))
    ctx = _ctx(config, s)
    assert load_reader("tx_codec_us_per_slot")(ctx) == pytest.approx(
        1e-3 * sum(codec) / SLOTS, rel=1e-12)
    assert load_reader("edge_motion_us_per_slot")(ctx) == pytest.approx(
        1e-3 * sum(motion) / SLOTS, rel=1e-12)


@pytest.mark.parametrize("config", CONFIGS)
def test_host_time_per_window(config):
    ex = _extract(config)
    s = trace.reduce(ex)
    spans = [(st, st + d) for n, st, d in ex["spans"] if n == "bench.window"]
    want = []
    for a, b in spans:
        inside = [(max(st, a), min(st + d, b)) for _, st, d in _ops(ex)
                  if st + d > a and st < b]
        want.append((b - a - _union_ns(inside)) * 1e-6)
    got = load_reader("host_ms_per_window")(_ctx(config, s))
    assert got == pytest.approx(sum(want) / len(want), rel=1e-9)
    assert 0 < got < 1e-6 * max(b - a for a, b in spans)


@pytest.mark.parametrize("config", CONFIGS)
def test_breakdown(config):
    s = trace.reduce(_extract(config))
    ops = s.top_ops(10)
    assert 0 < len(ops) <= 10
    assert all(ops[i][1] >= ops[i + 1][1] for i in range(len(ops) - 1))
    assert not any(n.startswith(trace.CONTAINERS) for n, _ in ops)
    gaps = s.top_gaps(10)
    assert 0 < len(gaps) <= 10
    assert all(g[1] > 0 for g in gaps)
    assert {n for n, _ in gaps} <= {"bench.window", "bench.offer",
                                    "bench.serve", "between windows"}
    assert sum(g for _, g in gaps) <= s.window_s - s.busy_s + 1e-9


def test_step_mfu_and_compile_readers():
    s = trace.reduce(_extract("s01-deepstream"))
    ctx = _ctx("s01-deepstream", s, live_cam_slots=60,
               compile_={"jaxpr_trace_duration": 4.0,
                         "backend_compile_duration": 0.5,
                         "cache_retrieval_time_sec": 0.25})
    flops = work.step_flops(ctx.config, 60)
    mfu = load_reader("step_mfu")(ctx)
    assert mfu == pytest.approx(100 * flops / (s.window_s * 197e12))
    assert 0 < mfu < 100
    assert load_reader("compile_trace_s")(ctx) == 4.0
    assert load_reader("compile_backend_s")(ctx) == 0.75


def test_readers_with_nothing_to_read_return_nothing():
    ex = _extract("s01-reducto")
    empty = {"devices": {}, "spans": ex["spans"]}
    ctx = _ctx("s01-reducto", trace.reduce(empty), live_cam_slots=0)
    for name in ("device_idle_share", "episode_device_ms_per_slot",
                 "tx_codec_us_per_slot", "edge_motion_us_per_slot",
                 "step_mfu", "compile_trace_s", "compile_backend_s"):
        assert load_reader(name)(ctx) is None, name
    with pytest.raises(ValueError, match="no bench.window"):
        trace.reduce({"devices": ex["devices"], "spans": []})
