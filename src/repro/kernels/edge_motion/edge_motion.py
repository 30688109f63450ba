"""Pallas TPU kernel: fused Sobel-edge + temporal-diff + block-sum.

This is ROIDet's per-frame hot loop (Algorithm 1 lines 3-9) as ONE VMEM pass:
the frame pair tile is loaded once from HBM; edges, XOR-difference and the
(bs x bs) block reduction all happen in registers/VMEM; only the tiny
(rows/bs, cols/bs) score tile is written back.  A separate-op formulation
would round-trip the full-resolution edge maps through HBM twice.

Tiling: the wrapper (ops.py) pre-slices each padded frame into overlapping
row bands of shape (TH+2, W+2) — the +2 halo makes every tile's Sobel stencil
self-contained, so kernel output is bit-identical to the global oracle.
Grid = (num_pairs, num_row_tiles); each program consumes one band of one
frame pair.  VMEM per program: 2 x (TH+2) x (W+2) x 4B  (~0.5 MB for TH=32,
W=1920) — well inside the ~16 MB budget.

The block sum runs rows then columns: the row sum splits only the sublane
axis ((TH, W) -> (TH/bs, bs, W), tile-aligned for bs a multiple of 8) and
the column sum is a matmul with a 0/1 pooling matrix ``(TH/bs, W) @ Pw (W,
W/bs)`` — Mosaic cannot lower the (TH, W) -> (TH/bs, bs, W/bs, bs) reshape
that splits the lane axis.  ``d`` is 0/1 and every block sum is an integer
<= bs*bs, so both are exact in any MXU pass precision and accumulation
order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import f32_dot


def pool_matrix(n_out: int, n_in: int, k: int) -> jax.Array:
    """(n_out, n_in) 0/1 matrix summing each run of ``k`` consecutive input
    rows: P[a, r] = 1 iff a*k <= r < a*k + k.  Built from 2-D iotas so it
    lowers inside a Mosaic kernel."""
    a = jax.lax.broadcasted_iota(jnp.int32, (n_out, n_in), 0) * k
    r = jax.lax.broadcasted_iota(jnp.int32, (n_out, n_in), 1)
    return ((r >= a) & (r < a + k)).astype(jnp.float32)


def _edge_motion_kernel(f0_ref, f1_ref, out_ref, *, block_size: int,
                        edge_thresh: float):
    f0 = f0_ref[0, 0]                       # (TH+2, W+2)
    f1 = f1_ref[0, 0]
    t2 = edge_thresh * edge_thresh

    def sobel_mag2(x):
        tl = x[:-2, :-2]; tc = x[:-2, 1:-1]; tr = x[:-2, 2:]
        ml = x[1:-1, :-2]; mr = x[1:-1, 2:]
        bl = x[2:, :-2]; bc = x[2:, 1:-1]; br = x[2:, 2:]
        gx = (tr + 2.0 * mr + br) - (tl + 2.0 * ml + bl)
        gy = (bl + 2.0 * bc + br) - (tl + 2.0 * tc + tr)
        return gx * gx + gy * gy

    e0 = sobel_mag2(f0) > t2
    e1 = sobel_mag2(f1) > t2
    d = jnp.logical_xor(e0, e1).astype(jnp.float32)   # (TH, W)
    th, w = d.shape
    bs = block_size
    rows = d.reshape(th // bs, bs, w).sum(axis=1)       # (TH/bs, W)
    out_ref[0, 0] = f32_dot(rows, pool_matrix(w // bs, w, bs).T)


def edge_motion_pallas(f0_tiles: jax.Array, f1_tiles: jax.Array, *,
                       block_size: int, edge_thresh: float,
                       interpret: bool) -> jax.Array:
    """f*_tiles: (P, T, TH+2, W+2) pre-haloed row bands for P frame pairs.
    Returns (P, T, TH/bs, W/bs) block scores."""
    P, T, THp2, Wp2 = f0_tiles.shape
    TH, W = THp2 - 2, Wp2 - 2
    bs = block_size
    assert TH % bs == 0 and W % bs == 0

    kernel = functools.partial(_edge_motion_kernel, block_size=bs,
                               edge_thresh=edge_thresh)
    return pl.pallas_call(
        kernel,
        grid=(P, T),
        in_specs=[
            pl.BlockSpec((1, 1, THp2, Wp2), lambda p, t: (p, t, 0, 0)),
            pl.BlockSpec((1, 1, THp2, Wp2), lambda p, t: (p, t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, TH // bs, W // bs),
                               lambda p, t: (p, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((P, T, TH // bs, W // bs), jnp.float32),
        interpret=interpret,
        name="edge_motion",
    )(f0_tiles, f1_tiles)
