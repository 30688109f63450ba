"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.edge_motion import ops as em_ops
from repro.kernels.edge_motion import ref as em_ref
from repro.kernels.flash_decode import ops as fd_ops
from repro.kernels.flash_decode import ref as fd_ref
from repro.kernels.knapsack_dp import ops as dp_ops
from repro.kernels.knapsack_dp import ref as dp_ref


# ---------------------------------------------------------------------------
# edge_motion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,bs,tr", [
    ((4, 64, 128), 8, 32), ((3, 96, 160), 16, 32), ((2, 32, 64), 8, 16),
    ((5, 48, 96), 8, 48), ((2, 128, 256), 32, 64),
])
def test_edge_motion_matches_oracle(shape, bs, tr, rng):
    frames = jnp.asarray(rng.uniform(0, 1, shape).astype(np.float32))
    got = em_ops.segment_motion(frames, block_size=bs, tile_rows=tr,
                                use_kernel=True)
    want = em_ops.segment_motion(frames, block_size=bs, tile_rows=tr,
                                 use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 4), hmul=st.integers(1, 3), wmul=st.integers(1, 3),
       seed=st.integers(0, 10))
def test_edge_motion_hypothesis(n, hmul, wmul, seed):
    H, W = 32 * hmul, 32 * wmul
    r = np.random.default_rng(seed)
    frames = jnp.asarray(r.uniform(0, 1, (n, H, W)).astype(np.float32))
    got = em_ops.segment_motion(frames, block_size=8, tile_rows=32,
                                use_kernel=True)
    want = em_ops.segment_motion(frames, block_size=8, tile_rows=32,
                                 use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0)


def test_edge_motion_detects_motion(rng):
    """Moving square produces block scores; static scene stays quiet."""
    H, W = 64, 64
    f0 = np.full((H, W), 0.4, np.float32)
    f1 = f0.copy()
    f1[16:32, 16:32] = 0.9       # object appears
    frames = jnp.asarray(np.stack([f0, f1]))
    sc = np.asarray(em_ops.segment_motion(frames, block_size=8, use_kernel=True))
    assert sc[0, 2:4, 2:4].max() > 4       # blocks at the object boundary fire
    static = jnp.asarray(np.stack([f0, f0]))
    sc0 = np.asarray(em_ops.segment_motion(static, block_size=8, use_kernel=True))
    assert sc0.max() == 0


# ---------------------------------------------------------------------------
# knapsack_dp
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(I=st.integers(2, 5), J=st.integers(2, 4), W=st.integers(6, 40),
       seed=st.integers(0, 100))
def test_knapsack_dp_optimal(I, J, W, seed):
    r = np.random.default_rng(seed)
    util = r.uniform(0, 1, (I, J)).astype(np.float32)
    costs = r.integers(1, max(W // I, 2) + 1, J).astype(np.int32)
    costs[0] = 1   # guarantee feasibility (min total = I <= W)
    pk, vk = dp_ops.solve(util, costs, W, use_kernel=True)
    pr, vr = dp_ops.solve(util, costs, W, use_kernel=False)
    pe, ve = dp_ref.exhaustive_oracle(util, costs, W)
    assert vk == pytest.approx(ve, abs=1e-5)
    assert vr == pytest.approx(ve, abs=1e-5)
    # the backtracked picks must be feasible and achieve the optimum
    assert costs[pk].sum() <= W
    assert util[np.arange(I), pk].sum() == pytest.approx(ve, abs=1e-5)


def test_knapsack_kernel_matches_ref_large(rng):
    util = rng.uniform(0, 1, (32, 6)).astype(np.float32)
    costs = np.array([1, 2, 4, 8, 16, 20], np.int32)
    Wcap = 200
    vk, ck = dp_ops.solve_values(jnp.asarray(util), jnp.asarray(costs), Wcap, True)
    vr, cr = dp_ops.solve_values(jnp.asarray(util), jnp.asarray(costs), Wcap, False)
    np.testing.assert_allclose(np.asarray(vk), np.asarray(vr), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))


# ---------------------------------------------------------------------------
# flash_decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd,bs,dt", [
    (2, 256, 8, 2, 64, 64, jnp.float32),
    (1, 512, 16, 4, 128, 128, jnp.float32),
    (3, 128, 8, 8, 32, 64, jnp.float32),
    (2, 256, 8, 2, 64, 64, jnp.bfloat16),
])
def test_flash_decode_matches_oracle(B, S, H, KV, hd, bs, dt, rng):
    q = jnp.asarray(rng.normal(0, 1, (B, 1, H, hd))).astype(dt)
    k = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd))).astype(dt)
    v = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd))).astype(dt)
    vl = jnp.int32(S * 3 // 4)
    got = fd_ops.flash_decode(q, k, v, kv_valid_len=vl, block_s=bs,
                              force_kernel=True)
    want = fd_ref.flash_decode_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                                   v.astype(jnp.float32), kv_valid_len=vl)
    tol = 1e-5 if dt == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@settings(max_examples=10, deadline=None)
@given(B=st.integers(1, 3), nkv=st.integers(1, 3), G=st.sampled_from([4, 8]),
       sb=st.integers(2, 6), vl_frac=st.floats(0.2, 1.0), seed=st.integers(0, 20))
def test_flash_decode_hypothesis(B, nkv, G, sb, vl_frac, seed):
    hd, bs = 32, 64
    S = bs * sb
    H = nkv * G
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(0, 1, (B, 1, H, hd)).astype(np.float32))
    k = jnp.asarray(r.normal(0, 1, (B, S, nkv, hd)).astype(np.float32))
    v = jnp.asarray(r.normal(0, 1, (B, S, nkv, hd)).astype(np.float32))
    vl = jnp.int32(max(1, int(S * vl_frac)))
    got = fd_ops.flash_decode(q, k, v, kv_valid_len=vl, block_s=bs,
                              force_kernel=True)
    want = fd_ref.flash_decode_ref(q, k, v, kv_valid_len=vl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_flash_decode_with_new_token(rng):
    """Old-cache + fresh-token merge == update-then-attend oracle."""
    from repro.models.attention import decode_attention_with_new
    B, S, H, KV, hd, vl = 2, 256, 8, 2, 64, 100
    mk = lambda *s: jnp.asarray(rng.normal(0, 1, s).astype(np.float32))
    q, k, v = mk(B, 1, H, hd), mk(B, S, KV, hd), mk(B, S, KV, hd)
    k1, v1 = mk(B, 1, KV, hd), mk(B, 1, KV, hd)
    kc = k.at[:, vl].set(k1[:, 0])
    vc = v.at[:, vl].set(v1[:, 0])
    want = fd_ref.flash_decode_ref(q, kc, vc, kv_valid_len=jnp.int32(vl + 1))
    got_ref = decode_attention_with_new(q, k, v, k1, v1, kv_valid_len=jnp.int32(vl))
    got_kern = fd_ops.flash_decode_with_new(q, k, v, k1, v1,
                                            kv_valid_len=jnp.int32(vl),
                                            force_kernel=True)
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_kern), np.asarray(want), atol=1e-5)


# ---------------------------------------------------------------------------
# tx_codec (fused transmission/codec kernel)
# ---------------------------------------------------------------------------

from repro.core import codec as codec_mod
from repro.core.codec import CodecConfig
from repro.kernels.tx_codec import ops as tx_ops
from repro.kernels.tx_codec import ref as tx_ref

# kernel-vs-oracle tolerance: XLA may fuse `x + sigma * noise` into an FMA
# on one side of the pallas boundary and not the other, so decoded frames
# agree to ~1 float32 ulp, not bitwise (see the kernel package docstring);
# SIZES are scalar math outside the kernel and must match exactly.
_TX_TOL = 1e-6
_TX_CFG = CodecConfig()


def _tx_inputs(r, C, N, H, W):
    frames = jnp.asarray(r.uniform(0, 1, (C, N, H, W)).astype(np.float32))
    pix = jnp.asarray(
        r.uniform(H * W * 0.2, H * W, C).astype(np.float32))
    b = jnp.asarray(r.choice(_TX_CFG.bitrates_kbps, C).astype(np.float32))
    res = jnp.asarray(r.choice(_TX_CFG.resolutions, C).astype(np.float32))
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(int(r.integers(0, 2**31))), jnp.arange(C))
    return frames, pix, b, res, keys


@pytest.mark.parametrize("C,N,H,W", [
    (3, 4, 64, 64), (5, 2, 48, 96), (2, 6, 96, 64), (8, 3, 32, 32),
])
def test_tx_codec_matches_oracle(C, N, H, W, rng):
    frames, pix, b, res, keys = _tx_inputs(rng, C, N, H, W)
    dk, sk = tx_ops.encode_fleet(_TX_CFG, frames, pix, b, res, keys,
                                 use_kernel=True)
    dr, sr = tx_ops.encode_fleet(_TX_CFG, frames, pix, b, res, keys,
                                 use_kernel=False)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), atol=_TX_TOL)
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))


def test_tx_codec_num_frames_override(rng):
    """The reducto path's traced kept-frame count: n_eff != shape N must
    recharge effective pixels identically on both sides."""
    C, N, H, W = 4, 6, 64, 64
    frames, pix, b, res, keys = _tx_inputs(rng, C, N, H, W)
    n_eff = jnp.asarray(rng.integers(1, N + 1, C).astype(np.float32))
    dk, sk = tx_ops.encode_fleet(_TX_CFG, frames, pix, b, res, keys,
                                 num_frames=n_eff, use_kernel=True)
    dr, sr = tx_ops.encode_fleet(_TX_CFG, frames, pix, b, res, keys,
                                 num_frames=n_eff, use_kernel=False)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), atol=_TX_TOL)
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
    # the override must matter where bpp is rate-sensitive: at the lowest
    # bitrate over the full frame, a 1-frame charge quantizes much finer
    # than the full-N charge (bitrate-mode sizes depend only on b, so the
    # observable is the decoded frames)
    pix_full = jnp.full((C,), H * W, jnp.float32)
    b_low = jnp.full((C,), float(_TX_CFG.bitrates_kbps[0]), jnp.float32)
    d_one, _ = tx_ops.encode_fleet(_TX_CFG, frames, pix_full, b_low, res,
                                   keys, num_frames=jnp.ones((C,)),
                                   use_kernel=True)
    d_full, _ = tx_ops.encode_fleet(_TX_CFG, frames, pix_full, b_low, res,
                                    keys, use_kernel=True)
    assert not np.allclose(np.asarray(d_one), np.asarray(d_full), atol=1e-4)


@pytest.mark.parametrize("with_res", [False, True])
def test_tx_codec_crf_matches_oracle(with_res, rng):
    """CRF mode: res=None skips the blur select on both sides; a res
    vector routes the same blur branches and charges the r^2 term."""
    C, N, H, W = 4, 3, 64, 96
    frames, pix, _, res, keys = _tx_inputs(rng, C, N, H, W)
    n_eff = jnp.asarray(rng.integers(1, N + 1, C).astype(np.float32))
    kw = dict(res=res if with_res else None, num_frames=n_eff)
    dk, sk = tx_ops.encode_fleet_crf(_TX_CFG, frames, pix, keys,
                                     use_kernel=True, **kw)
    dr, sr = tx_ops.encode_fleet_crf(_TX_CFG, frames, pix, keys,
                                     use_kernel=False, **kw)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), atol=_TX_TOL)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)


@settings(max_examples=12, deadline=None)
@given(C=st.integers(1, 6), N=st.integers(1, 6), hmul=st.integers(1, 3),
       wmul=st.integers(1, 3), override=st.integers(0, 1),
       seed=st.integers(0, 50))
def test_tx_codec_hypothesis(C, N, hmul, wmul, override, seed):
    """Parity over frame counts / non-multiple-of-8 resolutions / the
    num_frames override path — every camera drawing its own resolution so
    all three blur branches (and the identity) are exercised."""
    H, W = 24 * hmul, 24 * wmul     # 24: not divisible by the k=8 pool
    r = np.random.default_rng(seed)
    frames, pix, b, res, keys = _tx_inputs(r, C, N, H, W)
    n_eff = (jnp.asarray(r.integers(1, N + 1, C).astype(np.float32))
             if override else None)
    dk, sk = tx_ops.encode_fleet(_TX_CFG, frames, pix, b, res, keys,
                                 num_frames=n_eff, use_kernel=True)
    dr, sr = tx_ops.encode_fleet(_TX_CFG, frames, pix, b, res, keys,
                                 num_frames=n_eff, use_kernel=False)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr), atol=_TX_TOL)
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))


def test_tx_codec_oracle_is_scalar_codec(rng):
    """The ref module IS the vmapped scalar codec: spot-check one camera
    against a direct ``codec.encode_segment`` call, bitwise."""
    from repro.core import codec as codec_mod
    C, N, H, W = 3, 4, 48, 48
    frames, pix, b, res, keys = _tx_inputs(rng, C, N, H, W)
    dr, sr = tx_ref.encode_fleet_ref(_TX_CFG, frames, pix, b, res, keys)
    for i in (0, C - 1):
        d1, s1 = codec_mod.encode_segment(_TX_CFG, frames[i], pix[i], b[i],
                                          res[i], keys[i])
        np.testing.assert_array_equal(np.asarray(dr[i]), np.asarray(d1))
        np.testing.assert_array_equal(np.asarray(sr[i]), np.asarray(s1))


# ---------------------------------------------------------------------------
# frame tiling: row bands and lane tiles, at a height 32 does not divide
# ---------------------------------------------------------------------------

from repro import kernels
from repro.common import obs
from repro.kernels.tx_codec import tx_codec as tx_kernel

# 72 rows: three bands of 24; 256 columns: two lane tiles of 128
_TILED_H, _TILED_W = 72, 256


def _new_tilings(kernel, before):
    """The (grid, input block, tile)s ``kernel`` was traced with since
    ``before`` (an ``obs.kernel_grid_counts()`` snapshot)."""
    seen = before.get(kernel, {})
    return {k for k, n in obs.kernel_grid_counts().get(kernel, {}).items()
            if n > seen.get(k, 0)}


def test_default_tiling_at_cityflow_size():
    assert em_ops.band_rows(1080, 8) == 120 and em_ops.lane_cols(1920, 8) == 128
    assert em_ops.band_rows(96, 8) == 32 and em_ops.lane_cols(160, 8) == 160
    assert em_ops.band_rows(_TILED_H, 8) == 24
    assert em_ops.lane_cols(_TILED_W, 8) == 128
    res = CodecConfig().resolutions
    assert tx_kernel.tiling(10, 1080, 1920, res) == (1, 120, 128)
    # the 96x160 segment stays one block per camera
    assert tx_kernel.tiling(10, 96, 160, res) == (10, 96, 160)


def test_edge_motion_bands_and_lane_tiles_match_oracle(rng):
    C, N = 2, 4
    frames = jnp.asarray(
        rng.uniform(0, 1, (C, N, _TILED_H, _TILED_W)).astype(np.float32))
    before = obs.kernel_grid_counts()
    got = em_ops.segment_motion_fleet(frames, block_size=8)
    want = jax.vmap(lambda f: em_ref.segment_motion_ref(f, block_size=8))(
        frames)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # (pairs, bands) grid; whole-width haloed bands in, 24+2 x 128+2 tiles
    assert _new_tilings("edge_motion", before) == {
        ((C * (N - 1), 3), (1, 1, 26, _TILED_W + 2), (26, 130))}


@pytest.mark.parametrize("res", [1.0, 0.75, 0.5, 0.25])
def test_tx_codec_tiles_are_the_frame_blur(res, rng, monkeypatch):
    """Bands of 24 rows and lane tiles of 128 columns blur exactly as
    ``codec._resolution_blur`` blurs the whole frame: 2**40 quantization
    levels and no noise leave the blurred value as it is (frames >= 0.25
    times 2**40 are whole numbers), so the output is the blur, bitwise.
    The block budget is cut to one 24-row band of one frame, so the small
    frame is tiled as a 1080p one is."""
    C, N = 2, 3
    resolutions = (1.0, 0.75, 0.5, 0.25)
    monkeypatch.setattr(tx_kernel, "BLOCK_BYTES", 24 * _TILED_W * 4)
    assert tx_kernel.tiling(N, _TILED_H, _TILED_W, resolutions) \
        == (1, 24, 128)
    frames = jnp.asarray(rng.uniform(
        0.25, 1.0, (C, N, _TILED_H, _TILED_W)).astype(np.float32))
    before = obs.kernel_grid_counts()
    out = tx_kernel.tx_codec_pallas(
        frames, jnp.zeros_like(frames), jnp.full((C,), 2.0 ** 40),
        jnp.zeros((C,)), jnp.full((C,), resolutions.index(res), jnp.int32),
        resolutions=resolutions, interpret=kernels.pallas_interpret())
    want = jax.vmap(lambda f: codec_mod._resolution_blur(f, res))(frames)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert _new_tilings("tx_codec", before) == {
        ((C, N, 3), (1, 1, 24, _TILED_W), (24, 128))}
