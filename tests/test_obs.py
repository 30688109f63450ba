"""Host spans, device scopes and transfer/write counters of the served
fleet stream (``common.obs``, ``ckpt.write_counts``).

A checkpointing stream serves two windows under the profiler: each window's
``serve.*`` spans lie on the host plane in order on one thread, the
``ckpt.*`` spans of the save nest inside ``serve.checkpoint``, the write
runs on another thread, and the program's own record of each span agrees
with the trace.  The ``checkpoint`` fetch category and ``write_counts()``
advance once per checkpoint and not at all in memory-only serving; the
runner's per-window history is bounded; the compiled episode names its
layers."""

import jax
import pytest

import harness
from repro.ckpt import checkpoint as ckpt
from repro.common import obs
from repro.data.scenarios import make_scene, make_trace
from repro.data.synthetic import DeviceScene
from repro.serve import stream as stream_mod
from repro.serve.stream import StreamConfig, StreamingFleetRunner

WIN = 8
WINDOW_SPANS = obs.SERVE_SPANS
SAVE_SPANS = ("ckpt.meta", "ckpt.wait", "ckpt.fetch")
ALL_SPANS = WINDOW_SPANS + SAVE_SPANS + ("ckpt.write",)
LAYERS = ("scene", "roidet", "control", "compact", "encode", "detect")


def _runner(detectors, ckpt_dir=None):
    scfg = make_scene("urban_mid", 33)
    s = harness.build_system(detectors, "episode", scfg)
    s._key = jax.random.PRNGKey(1234)
    runner = StreamingFleetRunner(
        s, DeviceScene(scfg), method="deepstream",
        cfg=StreamConfig(window_slots=WIN, degrade=False,
                         ckpt_dir=None if ckpt_dir is None else str(ckpt_dir)))
    trace = make_trace("fcc_medium", 8 * WIN, seed=8,
                       num_cams=scfg.num_cameras)
    return runner, trace


def _serve(runner, trace, windows):
    t = runner.t_next
    runner.offer(trace[t:t + windows * WIN])
    assert runner.serve() == windows


@pytest.fixture(scope="module")
def traced(detectors, tmp_path_factory):
    """A checkpointing stream: one warm-up window, then two windows under
    the profiler, with the counters and span records around them."""
    tmp = tmp_path_factory.mktemp("obs")
    runner, trace = _runner(detectors, tmp / "ckpt")
    _serve(runner, trace, 1)
    runner.saver.wait()
    d0, w0 = obs.d2h_fetch_counts(), ckpt.write_counts()
    rec0 = {n: len(obs.recent(n)) for n in ALL_SPANS}
    with jax.profiler.trace(str(tmp / "trace")):
        _serve(runner, trace, 2)
        runner.saver.wait()
    d1, w1 = obs.d2h_fetch_counts(), ckpt.write_counts()
    xplane, = (tmp / "trace").rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(xplane))
    host = sorted(((ev.name, int(ev.start_ns), int(ev.duration_ns),
                    (plane.name, i))       # one line per thread
                   for plane in pd.planes
                   for i, line in enumerate(plane.lines)
                   for ev in line.events if ev.name in ALL_SPANS),
                  key=lambda e: e[1])
    recent = {n: obs.recent(n)[rec0[n]:] for n in ALL_SPANS}
    leaves = [x for x in jax.tree.leaves(runner._carry_tree())
              if isinstance(x, jax.Array)]
    runner.close()
    return dict(host=host, recent=recent, d0=d0, d1=d1, w0=w0, w1=w1,
                leaves=len(leaves), ckpt_dir=tmp / "ckpt")


def _by_name(host, name):
    return [e for e in host if e[0] == name]


def test_spans_nest_in_order_on_the_host_plane(traced):
    host = traced["host"]
    for name in ALL_SPANS:
        assert len(_by_name(host, name)) == 2, name
    (thread,) = {e[3] for e in host if e[0] in WINDOW_SPANS + SAVE_SPANS}
    assert {e[3] for e in _by_name(host, "ckpt.write")} != {thread}
    for w in range(2):
        spans = [_by_name(host, n)[w] for n in WINDOW_SPANS]
        for a, b in zip(spans, spans[1:]):
            assert a[1] + a[2] <= b[1], (a[0], b[0])
        _, c0, cd, _ = spans[-1]
        save = [_by_name(host, n)[w] for n in SAVE_SPANS]
        for a, b in zip(save, save[1:]):
            assert a[1] + a[2] <= b[1], (a[0], b[0])
        assert all(c0 <= s and s + d <= c0 + cd for _, s, d, _ in save)
        fetch, write = save[-1], _by_name(host, "ckpt.write")[w]
        assert write[1] >= fetch[1] + fetch[2]


def test_program_record_agrees_with_the_trace(traced):
    for name in ALL_SPANS:
        rec = traced["recent"][name]
        seen = [d * 1e-9 for _, _, d, _ in _by_name(traced["host"], name)]
        assert len(rec) == len(seen) == 2, name
        for r, s in zip(rec, seen):
            assert r == pytest.approx(s, rel=0.1, abs=2e-4), name


def test_checkpoint_counters_advance_once_per_checkpoint(traced):
    d0, d1, w0, w1 = traced["d0"], traced["d1"], traced["w0"], traced["w1"]
    assert d1["checkpoint"] - d0["checkpoint"] == 2 * traced["leaves"]
    assert d1["harvest"] - d0["harvest"] == 2 * 2
    assert d1["keep"] == d0["keep"] and d1["control"] == d0["control"]
    assert w1["generations"] - w0["generations"] == 2
    on_disk = sum(f.stat().st_size
                  for g in ("window_00000002", "window_00000003")
                  for f in (traced["ckpt_dir"] / g).iterdir())
    assert w1["bytes"] - w0["bytes"] == on_disk


def test_memory_only_serving_counts_no_checkpoint(detectors):
    runner, trace = _runner(detectors)
    _serve(runner, trace, 1)
    d0, w0 = obs.d2h_fetch_counts(), ckpt.write_counts()
    n0 = {n: len(obs.recent(n)) for n in ALL_SPANS}
    _serve(runner, trace, 2)
    d1, w1 = obs.d2h_fetch_counts(), ckpt.write_counts()
    assert d1["checkpoint"] == d0["checkpoint"] and w1 == w0
    assert d1["harvest"] - d0["harvest"] == 2 * 2
    for name in ALL_SPANS:
        grew = len(obs.recent(name)) - n0[name]
        assert grew == (2 if name in WINDOW_SPANS[:-1] else 0), name


def test_window_history_is_bounded(detectors, monkeypatch):
    monkeypatch.setattr(stream_mod, "HISTORY", 3)
    runner, trace = _runner(detectors)
    _serve(runner, trace, 5)
    assert len(runner.window_walls) == 3
    assert runner.window_walls.maxlen == runner.events.maxlen == 3
    st = runner.stats()
    assert st["windows"] == 5 and st["slots"] == 5 * WIN
    assert st["slots_per_s"] == pytest.approx(5 * WIN / runner.wall_s_total)
    assert runner.wall_s_total > sum(runner.window_walls)


def test_stats_say_where_the_host_time_goes(detectors, monkeypatch):
    monkeypatch.setattr(obs, "_recent", {})
    runner, trace = _runner(detectors)
    assert runner.stats()["host_ms"] == {}
    _serve(runner, trace, 2)
    host = runner.stats()["host_ms"]
    # a memory-only stream takes no checkpoint
    assert set(host) == set(WINDOW_SPANS) - {"serve.checkpoint"}
    assert all(v >= 0 for v in host.values())
    device = obs.recent("serve.device")
    assert len(device) == 2
    assert host["serve.device"] == pytest.approx(1e3 * sum(device) / 2)


def test_span_record_is_bounded(monkeypatch):
    monkeypatch.setattr(obs, "_recent", {})
    for _ in range(obs.RECENT + 5):
        with obs.span("serve.test"):
            pass
    assert len(obs.recent("serve.test")) == obs.RECENT
    assert all(d >= 0 for d in obs.recent("serve.test"))
    with pytest.raises(ValueError, match="unknown fetch category"):
        obs.count_fetch("nothing")


def test_the_episode_names_its_layers(traced):
    """The compiled episode carries each layer's scope in its metadata."""
    texts = [m.to_string()
             for ex in jax.devices()[0].client.live_executables()
             for m in ex.hlo_modules() if m.name == "jit_counted"]
    named = {layer for t in texts for layer in LAYERS
             if f"/{layer}/" in t}
    assert named == set(LAYERS)
