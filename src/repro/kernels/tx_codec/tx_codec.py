"""Pallas kernel: fused transmission/codec frame transform, camera-batched.

The rate-distortion codec simulator (``core.codec``) is the episode's
measured transmission hot spot: per camera it (1) computes ALL THREE
resolution-blur variants of the segment and indexes the nearest one
(``_select_resolution`` — a static unroll whose two losing branches are
pure dead work), then (2) quantizes, (3) adds coding noise and (4) clips —
four full-segment passes whose intermediates round-trip HBM between ops.

This kernel is that transform as ONE VMEM pass per camera: the segment
tile loads once, ``lax.switch`` computes ONLY the selected blur branch
(eliminating the 2/3 dead blur work), and quantize+noise+clip happen in
registers before the single write-back.  The per-camera SCALAR
rate-distortion terms (bpp, quantization levels, noise sigma, branch
index) and the PRNG noise draw stay in the caller (``ops.py``) — scalars
are free, and drawing ``jax.random.normal`` outside keeps the kernel
deterministic data-in/data-out with the exact bits the vmapped reference
draws.

Grid = (C,): one program per camera, each consuming its whole (N, H, W)
segment plus the matching noise tile; the three (C,) scalar vectors ride
in SMEM as scalar-prefetch operands (Mosaic refuses (1, 1) VMEM blocks of
a (C, 1) array).  VMEM per program: 2 x N x H x W x 4B (~1.2 MB for
N=10, 96x160 frames).

The blur is frame-by-frame 2-D matmuls with 0/1 matrices on the MXU —
Mosaic lowers neither the 5-D pooling reshape nor ``jnp.repeat``/edge
``pad``.  Every matrix is a SELECTION (one 1 per output element), so each
product is an exact copy in any accumulation order at fp32 contract
precision; the k x k window sum is then plain adds in the fixed order of
``codec._avg_pool`` (rows, then columns), and the upsample matrix folds
the nearest-neighbour repeat and the edge pad of the cropped tail into
one exact copy.  So the blur is bit-identical to the oracle's.

Parity vs the oracle (``ref.py`` == vmapped ``codec.encode_segment``):
the ONE permitted deviation is float32-ulp scale: XLA may fuse
``x + sigma * noise`` into an FMA on one side of the pallas boundary and
not the other, so outputs agree to ~1 ulp (<= 1e-6, asserted by the
parity tests), not bitwise — far inside every 1e-5 log contract.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import f32_dot


def _iotas(shape) -> Tuple[jax.Array, jax.Array]:
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _select(n_out: int, n_in: int, k: int, i: int) -> jax.Array:
    """(n_out, n_in) 0/1 matrix picking input row a*k + i for output a."""
    a, r = _iotas((n_out, n_in))
    return (r == a * k + i).astype(jnp.float32)


def _upsample(n_out: int, n_in: int, k: int) -> jax.Array:
    """(n_out, n_in) 0/1 matrix: output row r copies input row r // k, and
    rows past the pooled extent copy the last one (the edge pad)."""
    r, a = _iotas((n_out, n_in))
    inside = (r >= a * k) & (r < a * k + k)
    tail = (a == n_in - 1) & (r >= n_in * k)
    return (inside | tail).astype(jnp.float32)


def _blur_frame(x: jax.Array, *, res: float) -> jax.Array:
    """``codec._resolution_blur`` of one (H, W) frame for one STATIC
    resolution, as exact selection matmuls plus fixed-order adds."""
    if res >= 0.999:
        return x
    k = 2 if res > 0.6 else 4 if res > 0.3 else 8
    H, W = x.shape
    h, w = H // k, W // k
    rows = f32_dot(_select(h, H, k, 0), x)              # (h, W)
    for i in range(1, k):
        rows = rows + f32_dot(_select(h, H, k, i), x)
    small = f32_dot(rows, _select(w, W, k, 0).T)        # (h, w)
    for j in range(1, k):
        small = small + f32_dot(rows, _select(w, W, k, j).T)
    small = small / (k * k)
    up = f32_dot(_upsample(H, h, k), small)             # (H, w)
    return f32_dot(up, _upsample(W, w, k).T)


def _tx_codec_kernel(lv_ref, sg_ref, ri_ref, fr_ref, nz_ref, out_ref, *,
                     resolutions: Tuple[float, ...]):
    c = pl.program_id(0)
    lv = lv_ref[c]                       # quantization levels
    sg = sg_ref[c]                       # coding-noise sigma
    ri = ri_ref[c]                       # selected resolution branch
    branches = [functools.partial(_blur_frame, res=r) for r in resolutions]

    def frame(n, carry):
        # ONE blur branch, selected at runtime — not all three
        x = jax.lax.switch(ri, branches, fr_ref[0, n])
        x = jnp.round(x * lv) / lv       # quantization
        x = x + sg * nz_ref[0, n]        # additive coding noise
        out_ref[0, n] = jnp.clip(x, 0.0, 1.0)
        return carry

    jax.lax.fori_loop(0, fr_ref.shape[1], frame, 0)


def tx_codec_pallas(frames: jax.Array, noise: jax.Array, levels: jax.Array,
                    sigma: jax.Array, ridx: jax.Array, *,
                    resolutions: Tuple[float, ...],
                    interpret: bool) -> jax.Array:
    """frames/noise (C, N, H, W); levels/sigma (C,) f32; ridx (C,) int32.
    Returns the decoded segments (C, N, H, W)."""
    C, N, H, W = frames.shape
    kernel = functools.partial(_tx_codec_kernel,
                               resolutions=tuple(resolutions))
    seg = pl.BlockSpec((1, N, H, W), lambda c, *_: (c, 0, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(C,), in_specs=[seg, seg],
            out_specs=seg),
        out_shape=jax.ShapeDtypeStruct((C, N, H, W), jnp.float32),
        interpret=interpret,
    )(levels.astype(jnp.float32), sigma.astype(jnp.float32),
      ridx.astype(jnp.int32), frames, noise)
