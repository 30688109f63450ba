"""Rate-distortion codec simulator (replaces libx264 — no codec silicon here).

What the paper needs from H.264 (section 2.2, 7.3):
  * bitrate-mode encoding: a segment compressed at bitrate b spreads b*T bits
    over the encoded pixels -> fewer bits/pixel = more distortion;
  * **cropping interaction**: ROI cropping shrinks the encoded area, so the
    same bitrate buys more bits per ROI pixel (Fig. 4's mechanism);
  * resolution scaling (r in R) trades pixel count for per-pixel fidelity;
  * temporal redundancy: inter-frame coding makes N-frame segments cost far
    less than N intra frames (the reason Reducto's frame filtering is
    redundant with a codec, section 7.2);
  * CRF mode: constant quality, content-proportional size (Fig. 5).

Model: effective coded pixels P = roi_pixels * r^2 * (1 + rho*(N-1));
bpp = b*T*1000 / P; distortion = additive Gaussian (sigma0 * exp(-bpp/beta))
+ value quantization with step q(bpp) + resolution blur (avg-pool + nearest
upsample).  Constants calibrated so the detector's accuracy-vs-bitrate curve
saturates inside the paper's 50..1000 Kbps range.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class CodecConfig:
    bitrates_kbps: Tuple[int, ...] = (50, 100, 200, 400, 800, 1000)
    resolutions: Tuple[float, ...] = (1.0, 0.75, 0.5)
    slot_seconds: float = 1.0
    temporal_rho: float = 0.25        # inter-frame residual cost fraction
    sigma0: float = 0.35              # noise at bpp -> 0
    beta: float = 1.6                 # bpp decay constant
    quant_scale: float = 10.0         # quantization levels per unit bpp
    crf_bpp: float = 4.0              # "visually lossless" CRF-18 analogue


def effective_pixels(cfg: CodecConfig, roi_pixels: float, num_frames: int,
                     res: float) -> float:
    return roi_pixels * res * res * (1.0 + cfg.temporal_rho * (num_frames - 1))


def _avg_pool(frames: jax.Array, k: int) -> jax.Array:
    """(k x k) average pool over H/W, cropping the non-divisible tail.

    The window sum has a FIXED association — rows i = 0..k-1 left to right,
    then columns j = 0..k-1 left to right — so every implementation that
    adds the same selected elements in this order (the ``tx_codec`` kernel
    selects them with exact 0/1 matmuls) is bit-identical to this one on
    any backend; a reshape-reduce leaves the order to the compiler."""
    N, H, W = frames.shape
    x = frames[:, :H // k * k, :W // k * k]
    rows = x[:, 0::k]
    for i in range(1, k):
        rows = rows + x[:, i::k]
    out = rows[:, :, 0::k]
    for j in range(1, k):
        out = out + rows[:, :, j::k]
    return out / (k * k)


def _resolution_blur(frames: jax.Array, res: float) -> jax.Array:
    """Downscale->upscale loss for res < 1 (factor-of-2 pooling approx)."""
    if res >= 0.999:
        return frames
    k = 2 if res > 0.6 else 4 if res > 0.3 else 8
    small = _avg_pool(frames, k)
    up = jnp.kron(small, jnp.ones((1, k, k), frames.dtype))
    N, H, W = frames.shape
    # H/W not divisible by k: pooling cropped the tail; extend with edge rows
    up = jnp.pad(up, ((0, 0), (0, max(H - up.shape[1], 0)),
                      (0, max(W - up.shape[2], 0))), mode="edge")
    return up[:, :H, :W]


def _select_resolution(cfg: CodecConfig, frames: jax.Array, res: jax.Array
                       ) -> jax.Array:
    """Traced nearest-resolution blur select (static unroll over the small
    resolution set) — the ONE branching both encode modes share."""
    outs = jnp.stack([_resolution_blur(frames, r) for r in cfg.resolutions])
    ridx = jnp.argmin(jnp.abs(jnp.array(cfg.resolutions) - res))
    return outs[ridx]


@functools.partial(jax.jit, static_argnames=("cfg",))
def encode_segment(cfg: CodecConfig, frames: jax.Array, roi_pixels: jax.Array,
                   bitrate_kbps: jax.Array, res: jax.Array, key: jax.Array,
                   num_frames: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Simulate encode+decode.  frames (N,H,W) already ROI-masked (or full).
    ``num_frames`` (traced scalar) overrides the shape-derived frame count for
    effective-pixel accounting — the fleet reducto path encodes fixed-shape
    segments whose *kept* frame count varies per camera.
    Returns (decoded frames (N,H,W), size_bytes scalar)."""
    N = frames.shape[0]
    n_eff = jnp.float32(N) if num_frames is None else num_frames.astype(jnp.float32)
    pix = roi_pixels * res * res * (1.0 + cfg.temporal_rho * (n_eff - 1))
    bits = bitrate_kbps * 1000.0 * cfg.slot_seconds
    bpp = bits / jnp.maximum(pix, 1.0)

    x = _select_resolution(cfg, frames, res)

    # quantization: step shrinks as bpp grows
    levels = jnp.clip(cfg.quant_scale * bpp, 4.0, 256.0)
    x = jnp.round(x * levels) / levels
    # additive coding noise
    sigma = cfg.sigma0 * jnp.exp(-bpp / cfg.beta)
    x = x + sigma * jax.random.normal(key, x.shape)
    size_bytes = bits / 8.0
    return jnp.clip(x, 0.0, 1.0), size_bytes


@functools.partial(jax.jit, static_argnames=("cfg",))
def encode_segment_crf(cfg: CodecConfig, frames: jax.Array,
                       roi_pixels: jax.Array, key: jax.Array,
                       res: Optional[jax.Array] = None,
                       num_frames: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """CRF ('constant quality') mode: fixed bpp, content-proportional size.

    ``num_frames`` and ``res`` have the SAME semantics as in
    ``encode_segment``: a traced kept-frame count overriding the shape-
    derived N (fleet reducto's fixed-shape segments), and the resolution
    scale whose r^2 term ``effective_pixels`` charges — so CRF sizes are
    P * crf_bpp / 8 for exactly P = effective_pixels(cfg, roi_pixels, n, r).
    ``res`` also routes through the same resolution-blur branches."""
    N = frames.shape[0]
    n_eff = (jnp.float32(N) if num_frames is None
             else num_frames.astype(jnp.float32))
    r = jnp.float32(1.0) if res is None else jnp.asarray(res, jnp.float32)
    pix = roi_pixels * r * r * (1.0 + cfg.temporal_rho * (n_eff - 1.0))
    bpp = jnp.asarray(cfg.crf_bpp, jnp.float32)
    x = frames if res is None else _select_resolution(cfg, frames, r)
    levels = jnp.clip(cfg.quant_scale * bpp, 4.0, 256.0)
    x = jnp.round(x * levels) / levels
    sigma = cfg.sigma0 * jnp.exp(-bpp / cfg.beta)
    x = x + sigma * jax.random.normal(key, x.shape)
    return jnp.clip(x, 0.0, 1.0), pix * bpp / 8.0


def encode_fleet_segment(cfg: CodecConfig, frames: jax.Array,
                         roi_pixels: jax.Array, bitrate_kbps: jax.Array,
                         res: jax.Array, keys: jax.Array,
                         num_frames: Optional[jax.Array] = None, *,
                         use_kernel: bool = True
                         ) -> Tuple[jax.Array, jax.Array]:
    """Camera-batched ``encode_segment``: frames (C, N, H, W), per-camera
    scalars (C,), keys (C, 2) -> (decoded (C, N, H, W), size_bytes (C,)).

    ``use_kernel=True`` routes the per-frame transform through the fused
    pallas transmission kernel (``kernels.tx_codec``) — one VMEM pass per
    camera computing ONLY the selected resolution-blur branch instead of
    the scalar path's all-branches unroll; ``use_kernel=False`` is the
    vmapped per-camera ``encode_segment`` (the pre-kernel fleet path).
    The two agree to float32 ulp (see the kernel package docstring)."""
    from repro.kernels.tx_codec import ops as tx_ops
    return tx_ops.encode_fleet(cfg, frames, roi_pixels, bitrate_kbps, res,
                               keys, num_frames, use_kernel=use_kernel)


def encode_fleet_segment_crf(cfg: CodecConfig, frames: jax.Array,
                             roi_pixels: jax.Array, keys: jax.Array,
                             res: Optional[jax.Array] = None,
                             num_frames: Optional[jax.Array] = None, *,
                             use_kernel: bool = True
                             ) -> Tuple[jax.Array, jax.Array]:
    """Camera-batched ``encode_segment_crf`` with the same kernel routing
    (and ``res=None`` skipping the blur select) as
    ``encode_fleet_segment``."""
    from repro.kernels.tx_codec import ops as tx_ops
    return tx_ops.encode_fleet_crf(cfg, frames, roi_pixels, keys, res,
                                   num_frames, use_kernel=use_kernel)
