"""Pallas TPU kernel: fused Sobel-edge + temporal-diff + block-sum.

This is ROIDet's per-frame hot loop (Algorithm 1 lines 3-9) as ONE VMEM pass:
the frame pair tile is loaded once from HBM; edges, XOR-difference and the
(bs x bs) block reduction all happen in registers/VMEM; only the tiny
(rows/bs, cols/bs) score tile is written back.  A separate-op formulation
would round-trip the full-resolution edge maps through HBM twice.

Tiling: the wrapper (ops.py) pre-slices each padded frame into overlapping
row bands of shape (TH+2, W+2) — the +2 halo makes every band's Sobel
stencil self-contained, so kernel output is bit-identical to the global
oracle.  Grid = (num_pairs, num_row_bands); each program reads one band of
both frames of a pair straight from the ONE banded frame array (the pair's
second frame is the next frame of the same camera, so no per-pair copy is
made).  Inside a band the kernel walks lane tiles of TW columns, each read
with its own one-pixel halo (TW + 2 columns), so the block-sum pooling
matrix is one tile's (TW, TW/bs) and not the frame's (W, W/bs): work is
linear in pixels and a program's VMEM is 2 x (TH+2) x (W+2) x 4B double
buffered (~1 MB per band at TH=120, W=1920).

The block sum runs rows then columns: the row sum splits only the sublane
axis ((TH, TW) -> (TH/bs, bs, TW), tile-aligned for bs a multiple of 8) and
the column sum is a matmul with a 0/1 pooling matrix ``(TH/bs, TW) @ Pw
(TW, TW/bs)`` — Mosaic cannot lower the reshape that splits the lane axis.
``d`` is 0/1 and every block sum is an integer <= bs*bs, so both are exact
in any MXU pass precision and accumulation order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common import obs
from repro.kernels import f32_dot


def pool_matrix(n_out: int, n_in: int, k: int) -> jax.Array:
    """(n_out, n_in) 0/1 matrix summing each run of ``k`` consecutive input
    rows: P[a, r] = 1 iff a*k <= r < a*k + k.  Built from 2-D iotas so it
    lowers inside a Mosaic kernel."""
    a = jax.lax.broadcasted_iota(jnp.int32, (n_out, n_in), 0) * k
    r = jax.lax.broadcasted_iota(jnp.int32, (n_out, n_in), 1)
    return ((r >= a) & (r < a + k)).astype(jnp.float32)


def _sobel_mag2(x):
    tl = x[:-2, :-2]; tc = x[:-2, 1:-1]; tr = x[:-2, 2:]
    ml = x[1:-1, :-2]; mr = x[1:-1, 2:]
    bl = x[2:, :-2]; bc = x[2:, 1:-1]; br = x[2:, 2:]
    gx = (tr + 2.0 * mr + br) - (tl + 2.0 * ml + bl)
    gy = (bl + 2.0 * bc + br) - (tl + 2.0 * tc + tr)
    return gx * gx + gy * gy


def _edge_motion_kernel(f0_ref, f1_ref, out_ref, *, block_size: int,
                        edge_thresh: float, lane_cols: int):
    t2 = edge_thresh * edge_thresh
    th = f0_ref.shape[2] - 2
    bs, tw = block_size, lane_cols
    pool = pool_matrix(tw // bs, tw, bs).T
    for lane in range(out_ref.shape[2]):
        cols = pl.ds(lane * tw, tw + 2)       # the tile and its halo
        e0 = _sobel_mag2(f0_ref[0, 0, :, cols]) > t2
        e1 = _sobel_mag2(f1_ref[0, 0, :, cols]) > t2
        d = jnp.logical_xor(e0, e1).astype(jnp.float32)     # (TH, TW)
        rows = d.reshape(th // bs, bs, tw).sum(axis=1)       # (TH/bs, TW)
        out_ref[0, 0, lane] = f32_dot(rows, pool)


def edge_motion_pallas(bands: jax.Array, *, frames_per_camera: int,
                       block_size: int, edge_thresh: float, lane_cols: int,
                       interpret: bool) -> jax.Array:
    """bands: (C*N, T, TH+2, W+2) pre-haloed row bands of C cameras' N
    frames each.  Returns (P, T, L, TH/bs, TW/bs) block scores of the
    P = C*(N-1) consecutive frame pairs, L = W/TW lane tiles of TW =
    ``lane_cols`` columns."""
    F, T, THp2, Wp2 = bands.shape
    TH, W, N, bs, TW = THp2 - 2, Wp2 - 2, frames_per_camera, block_size, \
        lane_cols
    assert F % N == 0 and N >= 2, (F, N)
    assert TH % bs == 0 and TW % bs == 0 and W % TW == 0, (TH, W, TW, bs)
    P, L = F // N * (N - 1), W // TW
    grid = (P, T)
    obs.record_kernel_grid("edge_motion", grid, (1, 1, THp2, Wp2),
                           (THp2, TW + 2))

    def frame(p):
        """Pair p's first frame: camera p // (N-1), frame p % (N-1)."""
        return p // (N - 1) * N + p % (N - 1)

    kernel = functools.partial(_edge_motion_kernel, block_size=bs,
                               edge_thresh=edge_thresh, lane_cols=TW)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, THp2, Wp2), lambda p, t: (frame(p), t, 0, 0)),
            pl.BlockSpec((1, 1, THp2, Wp2),
                         lambda p, t: (frame(p) + 1, t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, L, TH // bs, TW // bs),
                               lambda p, t: (p, t, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((P, T, L, TH // bs, TW // bs),
                                       jnp.float32),
        interpret=interpret,
        name="edge_motion",
    )(bands, bands)
