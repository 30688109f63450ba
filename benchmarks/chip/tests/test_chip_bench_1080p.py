"""The CityFlow-size configuration on the CPU, cut in this test only to two
cameras at 72x128 (a height 16 and 32 do not divide, so the detector's
SAME grid and the kernels' row bands are exercised): served through the
harness it comes out correct against the plain reference, and with the
connected-component fixpoint cut to one sweep, or with the edge-motion row
bands cut without their one-row halo, it comes out not correct.  Then the
new readers: the kernels' bytes and FLOPs at 1080x1920 against hand counts,
the roofline shares and the ``cc`` scope on the chip fixtures."""
import dataclasses
import gzip
import json
import sys
import time
from pathlib import Path

import pytest

CHIP_DIR = Path(__file__).resolve().parents[1]
ROOT = CHIP_DIR.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(CHIP_DIR))

from chipbench import kernel_work, scopes, spec, trace, work  # noqa: E402
from chipbench.harness import load_peaks, load_reader, run_cell  # noqa: E402
from chipbench.serve import CompileClock  # noqa: E402

CELL = "s01-deepstream-1080p.fcc"
SEED = 2 ** 31 + 172
DATA = Path(__file__).resolve().parent / "data"


def small():
    cell = spec.find_cell(CELL, spec.load_benchmark(ROOT))
    cfg = cell.config
    scene = {**cfg["scene"], "height": 72, "width": 128}
    return dataclasses.replace(cell, config={**cfg, "num_cameras": 2,
                                             "scene": scene})


@pytest.fixture()
def fresh(monkeypatch):
    """Every program traced anew, before and after the test: a fault put
    into the program must not reach, or come from, another test's trace."""
    import jax
    from repro.core import fleet

    monkeypatch.setattr(fleet, "_EXEC_CACHE", {})
    jax.clear_caches()
    yield monkeypatch
    monkeypatch.undo()
    jax.clear_caches()


def serve():
    result, checks = run_cell(small(), seed=SEED, seconds=0.5, trace=False,
                              clock=CompileClock(),
                              t_start=time.perf_counter())
    return result, checks


def test_cut_config_is_correct(fresh):
    result, checks = serve()
    assert result["correct"] is True, checks
    assert checks["rows_off_pct"]["value"] == 0.0
    assert result["failed"] == 0 and result["attempted"] > 0
    # the served episode names its connected components, inside ROIDet
    fresh.setattr(scopes, "SCOPES", ("cc",))
    assert any(s == "cc" for m in scopes.live_scope_maps()
               for s, _ in m.values())


def one_sweep(monkeypatch):
    """Connected components stop after one propagation sweep."""
    from repro.core import cc
    monkeypatch.setattr(cc, "_fixpoint",
                        lambda mask, labels, bounded: cc._sweep(mask, labels))


def bands_without_halo(monkeypatch):
    """Each edge-motion row band is edge-padded on its own, instead of
    carrying the rows above and below it."""
    import jax.numpy as jnp
    from repro.kernels.edge_motion import ops as em_ops

    def tiles(frames, tile_rows):
        N, H, W = frames.shape
        bands = frames.reshape(N, H // tile_rows, tile_rows, W)
        return jnp.pad(bands, ((0, 0), (0, 0), (1, 1), (1, 1)), mode="edge")
    monkeypatch.setattr(em_ops, "_make_tiles", tiles)


@pytest.mark.parametrize("fault", [one_sweep, bands_without_halo])
def test_cut_config_with_a_fault_is_not_correct(fresh, fault):
    fault(fresh)
    result, checks = serve()
    assert result["correct"] is False, checks
    assert checks["rows_off_pct"]["value"] > checks["rows_off_pct"]["limit"]


# -- bytes and FLOPs of the kernels at 1080x1920 --------------------------------

# edge_motion over 5 cameras x 10 frames: 45 pairs, 9 bands of 120 rows,
# each read with its halo as 122 x 1922 floats for both frames of a pair;
# scores 45 x 9 x 15 lane tiles x 15 x 16 floats; 38 FLOPs per pixel pair
EM_BYTES = 2 * 45 * 9 * 122 * 1922 * 4 + 45 * 9 * 15 * 15 * 16 * 4
EM_FLOPS = 45 * 1080 * 1920 * 38
# tx_codec over 5 x 10 frames: frames and noise read, output written, three
# 5-float scalar vectors; 7 FLOPs per pixel
TX_BYTES = 3 * 5 * 10 * 1080 * 1920 * 4 + 3 * 5 * 4
TX_FLOPS = 5 * 10 * 1080 * 1920 * 7

EM_LAUNCH = ('%edge_motion.3 = f32[45,9,15,15,16]{4,3,2,1,0} custom-call('
             'f32[50,9,122,1922]{3,2,1,0:T(8,128)} %gather.1, '
             'f32[50,9,122,1922]{3,2,1,0:T(8,128)} %gather.1), '
             'custom_call_target="tpu_custom_call", operand_layout_'
             'constraints={f32[50,9,122,1922]{3,2,1,0}}')
TX_LAUNCH = ('%encode_fleet.3 = f32[5,10,1080,1920]{3,2,1,0:T(8,128)} '
             'custom-call(f32[5]{0} %a, f32[5]{0} %b, s32[5]{0} %c, '
             'f32[5,10,1080,1920]{3,2,1,0} %d, f32[5,10,1080,1920]{3,2,1,0} '
             '%e), custom_call_target="tpu_custom_call"')


def test_kernel_work_at_1080p_matches_hand_counts():
    assert EM_BYTES == 765_560_160 and EM_FLOPS == 3_545_856_000
    assert TX_BYTES == 1_244_160_060 and TX_FLOPS == 725_760_000
    assert kernel_work.edge_motion_work((50, 9, 122, 1922),
                                        (45, 9, 15, 15, 16)) \
        == (EM_BYTES, EM_FLOPS)
    assert kernel_work.tx_codec_work((5, 10, 1080, 1920)) \
        == (TX_BYTES, TX_FLOPS)
    assert kernel_work.launch_work("edge_motion", EM_LAUNCH) \
        == (EM_BYTES, EM_FLOPS)
    assert kernel_work.launch_work("tx_codec", TX_LAUNCH) \
        == (TX_BYTES, TX_FLOPS)


def test_roofline_share_is_the_bound_over_the_time():
    """Two launches of 2 ms each on one device: bytes over 819 GB/s bound
    both kernels."""
    peaks = load_peaks("TPU v5 lite")
    s = trace.Summary(devices={"/device:TPU:0": {trace.OPS_LINE: [
        (EM_LAUNCH, 0, 2_000_000), (EM_LAUNCH, 3_000_000, 2_000_000),
        (TX_LAUNCH, 6_000_000, 2_000_000), ("%fusion.1 = f32[4]", 9, 5)]}},
        spans=[], window=(0, 10_000_000))
    em = kernel_work.roofline_pct("edge_motion", kernel_work.EDGE_MOTION, s,
                                  peaks)
    tx = kernel_work.roofline_pct("tx_codec", kernel_work.TX_CODEC, s, peaks)
    assert em == pytest.approx(100 * EM_BYTES / 819e9 / 2e-3)
    assert tx == pytest.approx(100 * TX_BYTES / 819e9 / 2e-3)
    assert 0 < em < 100 and 0 < tx < 100
    empty = trace.Summary(devices={"/device:TPU:0": {trace.OPS_LINE: []}},
                          spans=[], window=(0, 1))
    assert kernel_work.roofline_pct("tx_codec", kernel_work.TX_CODEC, empty,
                                    peaks) is None


# -- the readers on the chip fixtures --------------------------------------------

def _ctx(fx):
    return trace.Context(
        trace=trace.reduce(fx), config=spec.load_config("s01-deepstream"),
        cell=None, peaks=load_peaks("TPU v5 lite"), chips=1, slots=16,
        windows=2, live_cam_slots=80, compile={}, work=work)


@pytest.fixture()
def deepstream_fixture(monkeypatch):
    with gzip.open(DATA / "trace_s01-deepstream.spans.json.gz", "rt") as f:
        fx = json.load(f)
    scopes._memo.clear()
    yield fx
    scopes._memo.clear()


def test_roofline_readers_read_the_named_launches(deepstream_fixture):
    ctx = _ctx(deepstream_fixture)
    for metric, kernel, pattern in (
            ("edge_motion_roofline", "edge_motion", kernel_work.EDGE_MOTION),
            ("tx_codec_roofline", "tx_codec", kernel_work.TX_CODEC)):
        evs = kernel_work.launches(ctx.trace, pattern)
        assert len(evs) == 16, metric                  # one per slot
        got = load_reader(metric)(ctx)
        assert got == kernel_work.roofline_pct(kernel, pattern, ctx.trace,
                                               ctx.peaks) > 0


def test_cc_reader_reads_nothing_without_the_scope(deepstream_fixture,
                                                   monkeypatch):
    """The program of the fixture has no ``cc`` scope: the reader leaves the
    metric out."""
    monkeypatch.setattr(scopes, "live_scope_maps",
                        lambda: [deepstream_fixture["scopes"]])
    assert load_reader("cc_us_per_slot")(_ctx(deepstream_fixture)) is None


def test_cc_reader_reads_the_nested_scope(deepstream_fixture, monkeypatch):
    """The fixture's ROIDet pad fusions (the label propagation pads its
    neighbourhood) relabelled as ``cc``: the reader gives their op time per
    slot."""
    fx = deepstream_fixture
    base = fx["scopes"]
    nested = {n: ("cc" if s == "roidet" and n.startswith("pad_") else None,
                  t) for n, (s, t) in base.items()}
    assert any(s == "cc" for s, _ in nested.values())
    monkeypatch.setattr(
        scopes, "live_scope_maps",
        lambda: [nested if scopes.SCOPES == ("cc",) else base])
    ctx = _ctx(fx)
    want = {n for n, (s, _) in nested.items() if s == "cc"}
    ops = [d for n, _, d in scopes.episode_ops(ctx.trace) if n in want]
    got = load_reader("cc_us_per_slot")(ctx)
    assert got == pytest.approx(1e6 * sum(ops) * 1e-9 / 16)
    assert scopes.SCOPES[0] == "scene"          # the patch is undone


def test_scope_map_nests_cc_in_roidet(monkeypatch):
    hlo = """HloModule jit_counted

%body.2 (c: s32[4]) -> s32[4] {
  %c = s32[4]{0} parameter(0)
  ROOT %minimum.3 = s32[4]{0} minimum(%c, %c)
}

ENTRY %main.7 (x: s32[4]) -> s32[4] {
  %x = s32[4]{0} parameter(0)
  %while.8 = s32[4]{0} while(%x), body=%body.2, metadata={op_name="jit(counted)/while/body/roidet/cc/while"}
  ROOT %add.10 = s32[4]{0} add(%while.8, %x), metadata={op_name="jit(counted)/while/body/roidet/add"}
}
"""
    outer = {n: s for n, (s, _) in scopes.scope_map(hlo).items()}
    assert outer["minimum.3"] == outer["add.10"] == "roidet"
    monkeypatch.setattr(scopes, "SCOPES", ("cc",))
    inner = {n: s for n, (s, _) in scopes.scope_map(hlo).items()}
    assert inner["minimum.3"] == inner["while.8"] == "cc"
    assert inner["add.10"] is None
