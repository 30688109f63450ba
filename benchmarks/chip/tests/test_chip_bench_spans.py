"""The readers of the program's device scopes, host spans and checkpoint
counter (``chipbench/scopes.py`` and the metrics that use it).

The chip fixtures ``data/trace_<config>.spans.json.gz`` are two traced
windows of the program that names its scopes and opens its spans, recorded
on a v5e chip by ``record_spans.py``: the harness's extract plus the
episode's scope map, the ``serve.*``/``ckpt.*`` host events and the
program's own record of them.  The older fixtures, of a program without
either, must still give every earlier reader the value it gave."""
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
CONFIGS = ["s01-deepstream", "s01-reducto"]
SLOTS, WINDOWS = 16, 2                       # 2 windows of 8 slots
SCOPE_READERS = {f"{s}_us_per_slot": s for s in
                 ("scene", "roidet", "control", "compact", "encode",
                  "detect")}
WINDOW_SPANS = ("serve.prep", "serve.device", "serve.harvest", "serve.logs",
                "serve.supervise")
SAVE_SPANS = ("ckpt.meta", "ckpt.wait", "ckpt.fetch")


def _load(name):
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


def _ctx(config, summary):
    return trace.Context(
        trace=summary, config=spec.load_config(config), cell=None,
        peaks=load_peaks("TPU v5 lite"), chips=1, slots=SLOTS,
        windows=WINDOWS, live_cam_slots=SLOTS * 5, compile={}, work=work)


# -- the older fixtures read as before -----------------------------------------

OLD = {
    "s01-deepstream": {
        "device_idle_share": 50.34310999097089,
        "step_mfu": 0.0373467756129167,
        "episode_device_ms_per_slot": 2.7671776875000003,
        "tx_codec_us_per_slot": 5.8765625,
        "edge_motion_us_per_slot": 58.348000000000006,
        "host_ms_per_window": 22.432162000000005},
    "s01-reducto": {
        "device_idle_share": 37.041006556633285,
        "step_mfu": 0.05707321704625114,
        "episode_device_ms_per_slot": 2.605514625,
        "tx_codec_us_per_slot": 5.2230625,
        "edge_motion_us_per_slot": 64.72775,
        "host_ms_per_window": 12.253774999999997},
}


@pytest.mark.parametrize("config", CONFIGS)
def test_earlier_readers_read_the_older_fixtures_as_before(config):
    s = trace.reduce(_load(f"trace_{config}.json.gz"))
    ctx = _ctx(config, s)
    for name, want in OLD[config].items():
        assert load_reader(name)(ctx) == pytest.approx(want, rel=1e-12), name


# -- scope maps from HLO text ----------------------------------------------------

HLO = """HloModule jit_counted

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %sine.1 = f32[4]{0} sine(%p), metadata={op_name="jit(counted)/encode/sin"}
}

%body.2 (c: f32[4]) -> f32[4] {
  %c = f32[4]{0} parameter(0)
  %dynamic-update-slice.3 = f32[4]{0} dynamic-update-slice(%c, %c)
  ROOT %fusion.4 = f32[4]{0} fusion(%c), kind=kLoop, calls=%fused_computation.1
}

%cond.5 (c: f32[4]) -> pred[] {
  ROOT %constant.6 = pred[] constant(false)
}

ENTRY %main.7 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %while.8 = f32[4]{0} while(%x), condition=%cond.5, body=%body.2, metadata={op_name="jit(counted)/while/body/scene/while"}
  %copy.9 = f32[4]{0} copy(%while.8), metadata={op_name="jit(counted)/while/body/copy"}
  ROOT %add.10 = f32[4]{0} add(%copy.9, %x), metadata={op_name="jit(counted)/detect/roidet/add"}
}
"""


def test_scope_map_inherits_from_the_enclosing_loop():
    m = {n: scope for n, (scope, _) in scopes.scope_map(HLO).items()}
    # the loop XLA built takes the scope of the instruction that runs it
    assert m["while.8"] == "scene"
    assert m["dynamic-update-slice.3"] == "scene"
    assert m["constant.6"] == "scene"
    # an instruction's own scope comes first; the outermost listed scope
    assert m["sine.1"] == "encode"
    assert m["add.10"] == "detect"
    assert m["copy.9"] is None and m["x"] is None
    assert scopes.scope_map(HLO)["while.8"][1] == "f32[4]{0}"


# -- the chip fixtures of the program with scopes and spans ---------------------

@pytest.fixture(params=CONFIGS)
def spans_fixture(request, monkeypatch):
    fx = _load(f"trace_{request.param}.spans.json.gz")
    monkeypatch.setattr(scopes, "live_scope_maps", lambda: [fx["scopes"]])
    scopes._memo.clear()
    return request.param, fx


def _episode_op_time(fx):
    """Op time inside the episode module, by a plain pass over the events."""
    (dev,) = fx["devices"].values()
    mods = [(s, s + d) for n, s, d in dev[trace.MODULES_LINE]
            if n.startswith("jit_counted(")]
    ops = [(n, d) for n, s, d in dev[trace.OPS_LINE]
           if any(a <= s < b for a, b in mods)
           and not n.startswith(trace.CONTAINERS)]
    return sum(d for _, d in ops) * 1e-9, sum(b - a for a, b in mods) * 1e-9


def test_scopes_and_the_rest_make_up_the_episode(spans_fixture):
    config, fx = spans_fixture
    secs = scopes.layer_seconds(trace.reduce(fx), [fx["scopes"]])
    op_time, module_time = _episode_op_time(fx)
    scoped = sum(secs[s] for s in scopes.SCOPES)
    assert scoped + secs["unscoped"] == pytest.approx(op_time, rel=0.01)
    assert secs["unmatched"] <= 0.01 * op_time
    assert 0.9 * module_time < op_time < 1.05 * module_time
    # what each configuration runs, and what it leaves out
    assert secs["scene"] > 0 and secs["detect"] > 0 and secs["encode"] > 0
    assert secs["control"] > 0 and secs["compact"] > 0
    if config == "s01-deepstream":
        assert secs["roidet"] > 0 and secs["keep"] == 0
    else:
        assert secs["keep"] > 0 and secs["roidet"] == 0


def test_scope_readers_read_the_split_per_slot(spans_fixture):
    config, fx = spans_fixture
    s = trace.reduce(fx)
    secs = scopes.layer_seconds(s, [fx["scopes"]])
    ctx = _ctx(config, s)
    for name, scope in SCOPE_READERS.items():
        got = load_reader(name)(ctx)
        assert got == pytest.approx(1e6 * secs[scope] / SLOTS,
                                    rel=1e-12), name


def test_kernel_readers_read_the_named_launches(spans_fixture):
    config, fx = spans_fixture
    ctx = _ctx(config, trace.reduce(fx))
    (dev,) = fx["devices"].values()
    launches = [n for n, _, _ in dev[trace.OPS_LINE]
                if "tpu_custom_call" in n]
    # the motion kernel's launch carries its own name now
    assert any(n.startswith("%edge_motion") for n in launches)
    assert not any(n.startswith(("%_unknown_", "%_branch_1_fun"))
                   for n in launches)
    assert load_reader("tx_codec_us_per_slot")(ctx) > 0
    assert load_reader("edge_motion_us_per_slot")(ctx) > 0


def test_host_spans_nest_in_order(spans_fixture):
    config, fx = spans_fixture
    host = fx["host"]
    checkpointing = spec.load_config(config)["serving"]["checkpoint"]
    window = WINDOW_SPANS + (("serve.checkpoint",) if checkpointing else ())
    named = lambda n: [e for e in host if e[0] == n]      # noqa: E731
    for n in window + (SAVE_SPANS if checkpointing else ()):
        assert len(named(n)) == WINDOWS, n
    if not checkpointing:
        assert not [e for e in host if e[0].startswith("ckpt.")]
    (thread,) = {e[3] for e in host if e[0] in window}
    benches = sorted(e[1:3] for e in [tuple(x) for x in fx["spans"]]
                     if e[0] == "bench.serve")
    for w in range(WINDOWS):
        spans = [named(n)[w] for n in window]
        b0, bd = benches[w]
        assert all(b0 <= s and s + d <= b0 + bd for _, s, d, _ in spans)
        for a, b in zip(spans, spans[1:]):
            assert a[1] + a[2] <= b[1], (a[0], b[0])
        if checkpointing:
            _, c0, cd, _ = spans[-1]
            save = [named(n)[w] for n in SAVE_SPANS]
            assert all(c0 <= s and s + d <= c0 + cd for _, s, d, _ in save)
            for a, b in zip(save, save[1:]):
                assert a[1] + a[2] <= b[1], (a[0], b[0])
    if checkpointing:
        writes = {e[3] for e in named("ckpt.write")}
        assert writes and thread not in writes


def test_span_readers_read_the_program_record(spans_fixture, monkeypatch):
    config, fx = spans_fixture
    from repro.common import obs

    monkeypatch.setattr(obs, "recent", lambda n: list(fx["recent"].get(n, [])))
    ctx = _ctx(config, trace.reduce(fx))
    for metric, span in (("episode_prep_ms_per_window", "serve.prep"),
                         ("ckpt_stall_ms_per_window", "serve.checkpoint")):
        seen = [d * 1e-6 for n, _, d, _ in fx["host"] if n == span]
        if not seen:
            # a cell that keeps no checkpoint does not list the metric; a
            # reader that finds no span there fails rather than reading 0
            with pytest.raises(scopes.Unread):
                load_reader(metric)(ctx)
            continue
        got = load_reader(metric)(ctx)
        # the program's record and the profiler's clock agree
        assert got == pytest.approx(sum(seen) / len(seen), rel=0.05,
                                    abs=0.05), metric


# -- a program without scopes, spans or the write counter ----------------------

def test_readers_of_a_program_without_them_read_nothing(monkeypatch):
    """The program before ``repro.common.obs``: every new reader returns
    None, which the harness leaves out of the line."""
    import repro.ckpt.checkpoint as ckpt_mod
    import repro.common

    s = trace.reduce(_load("trace_s01-deepstream.json.gz"))
    ctx = _ctx("s01-deepstream", s)
    monkeypatch.setattr(scopes, "live_scope_maps", lambda: [])
    scopes._memo.clear()
    monkeypatch.setitem(sys.modules, "repro.common.obs", None)
    monkeypatch.delattr(repro.common, "obs", raising=False)
    monkeypatch.delattr(ckpt_mod, "write_counts")
    for name in list(SCOPE_READERS) + ["episode_prep_ms_per_window",
                                       "ckpt_stall_ms_per_window",
                                       "ckpt_bytes_per_window"]:
        assert load_reader(name)(ctx) is None, name


def test_a_map_of_another_executable_fails_the_reader(spans_fixture,
                                                     monkeypatch):
    """Scopes are read only through the executable that ran: a map whose
    instructions do not match the traced operations fails the run, as does
    a program whose executable names no scope at all."""
    config, fx = spans_fixture
    s = trace.reduce(fx)
    other = {n: (scope, "f32[1]{0}") for n, (scope, _) in
             fx["scopes"].items()}
    with pytest.raises(scopes.Unread, match="matches no instruction"):
        scopes.layer_seconds(s, [other])
    for maps in ([other], []):
        monkeypatch.setattr(scopes, "live_scope_maps", lambda: maps)
        scopes._memo.clear()
        with pytest.raises(scopes.Unread):
            load_reader("scene_us_per_slot")(_ctx(config, s))


def test_a_missing_span_fails_the_reader(monkeypatch):
    from repro.common import obs

    ctx = _ctx("s01-deepstream",
               trace.reduce(_load("trace_s01-deepstream.json.gz")))
    monkeypatch.setattr(obs, "recent", lambda n: [0.004])   # 1 of 2 windows
    with pytest.raises(scopes.Unread, match="serve.prep"):
        load_reader("episode_prep_ms_per_window")(ctx)


def test_checkpoint_bytes_per_generation(monkeypatch):
    import repro.ckpt.checkpoint as ckpt_mod

    read = load_reader("ckpt_bytes_per_window")
    monkeypatch.setattr(ckpt_mod, "_writes",
                        {"generations": 0, "bytes": 0})
    with pytest.raises(scopes.Unread):
        read(None)
    monkeypatch.setattr(ckpt_mod, "_writes",
                        {"generations": 4, "bytes": 4_000_000})
    assert read(None) == 1_000_000
