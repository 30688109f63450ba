"""jit'd wrapper: scalar rate-distortion terms + kernel/oracle dispatch.

Splits the fleet codec step the way the kernel wants it: the per-camera
SCALAR terms (effective pixels, bits, bpp, quantization levels, noise
sigma, nearest-resolution branch index, size_bytes) are computed here as
(C,) vectors — elementwise float32 ops in the exact order of the scalar
``codec.encode_segment`` math, so they are bit-identical to the vmapped
reference — and the heavy per-frame transform (ONE selected blur branch +
quantize + noise + clip) runs as a single camera-batched pallas launch.

The PRNG draw also stays here: ``jax.vmap(jax.random.normal)`` over the
per-camera keys produces the same bits as the reference's per-camera
draws (vmap == loop semantics), keeping the kernel deterministic.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.tx_codec import ref
from repro.kernels.tx_codec.tx_codec import tx_codec_pallas


def _noise(keys: jax.Array, shape) -> jax.Array:
    """Per-camera coding noise, same bits as the reference's serial
    per-camera ``jax.random.normal`` draws."""
    return jax.vmap(lambda k: jax.random.normal(k, shape))(keys)


def _nearest_resolution(resolutions, res: jax.Array) -> jax.Array:
    """Per-camera nearest-resolution branch index — the batched form of
    ``codec._select_resolution``'s argmin (same tie-breaking)."""
    return jnp.argmin(
        jnp.abs(jnp.array(resolutions)[None, :] - res[:, None]),
        axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("cfg", "use_kernel"))
def encode_fleet(cfg, frames: jax.Array, roi_pixels: jax.Array,
                 bitrate_kbps: jax.Array, res: jax.Array, keys: jax.Array,
                 num_frames: Optional[jax.Array] = None, *,
                 use_kernel: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Bitrate-mode fleet encode: frames (C, N, H, W), per-camera scalars
    (C,), keys (C, 2) -> (decoded (C, N, H, W), size_bytes (C,)).
    ``use_kernel=False`` runs the vmapped ``codec.encode_segment`` oracle
    (the pre-kernel fleet path, also the parity reference)."""
    if not use_kernel:
        return ref.encode_fleet_ref(cfg, frames, roi_pixels, bitrate_kbps,
                                    res, keys, num_frames)
    C, N = frames.shape[0], frames.shape[1]
    n_eff = (jnp.full((C,), N, jnp.float32) if num_frames is None
             else num_frames.astype(jnp.float32))
    pix = roi_pixels * res * res * (1.0 + cfg.temporal_rho * (n_eff - 1))
    bits = bitrate_kbps * 1000.0 * cfg.slot_seconds
    bpp = bits / jnp.maximum(pix, 1.0)
    levels = jnp.clip(cfg.quant_scale * bpp, 4.0, 256.0)
    sigma = cfg.sigma0 * jnp.exp(-bpp / cfg.beta)
    dec = tx_codec_pallas(frames, _noise(keys, frames.shape[1:]), levels,
                          sigma, _nearest_resolution(cfg.resolutions, res),
                          resolutions=cfg.resolutions,
                          interpret=kernels.pallas_interpret())
    return dec, bits / 8.0


@functools.partial(jax.jit, static_argnames=("cfg", "use_kernel", "blur"))
def encode_fleet_crf(cfg, frames: jax.Array, roi_pixels: jax.Array,
                     keys: jax.Array, res: Optional[jax.Array] = None,
                     num_frames: Optional[jax.Array] = None, *,
                     blur: bool = True,
                     use_kernel: bool = True) -> Tuple[jax.Array, jax.Array]:
    """CRF-mode fleet encode: fixed bpp, content-proportional sizes.
    ``res=None`` (or ``blur=False``) skips the blur select exactly like the
    scalar ``encode_segment_crf``; the r^2 term still charges when a
    resolution vector is given."""
    if res is None:
        blur = False
    if not use_kernel:
        return ref.encode_fleet_crf_ref(cfg, frames, roi_pixels, keys, res,
                                        num_frames)
    C, N = frames.shape[0], frames.shape[1]
    n_eff = (jnp.full((C,), N, jnp.float32) if num_frames is None
             else num_frames.astype(jnp.float32))
    r = jnp.ones((C,), jnp.float32) if res is None else res.astype(jnp.float32)
    pix = roi_pixels * r * r * (1.0 + cfg.temporal_rho * (n_eff - 1.0))
    bpp = jnp.full((C,), cfg.crf_bpp, jnp.float32)
    levels = jnp.clip(cfg.quant_scale * bpp, 4.0, 256.0)
    sigma = cfg.sigma0 * jnp.exp(-bpp / cfg.beta)
    ridx = (_nearest_resolution(cfg.resolutions, r) if blur
            else jnp.zeros((C,), jnp.int32))
    resolutions = cfg.resolutions if blur else (1.0,)
    dec = tx_codec_pallas(frames, _noise(keys, frames.shape[1:]), levels,
                          sigma, ridx, resolutions=resolutions,
                          interpret=kernels.pallas_interpret())
    return dec, pix * bpp / 8.0
