"""jit'd wrapper: tiling + halo construction + kernel/oracle dispatch."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import kernels
from repro.kernels.edge_motion import ref
from repro.kernels.edge_motion.edge_motion import edge_motion_pallas
from repro.sharding.rules import cached_sharded_jit, pad_cameras, pad_leading

LANE_TILE = 128     # lane-tile width when the frame width is a multiple


def band_rows(H: int, block_size: int) -> int:
    """Default row-band height: the tallest multiple of ``block_size`` that
    divides H and is at most max(32, H // 8) rows — 32-row bands on small
    frames, about nine bands on tall ones (120 rows at 1080).  The same in
    compiled and interpret mode."""
    cap = max(32, H // 8)
    for th in range(min(cap, H) // block_size * block_size, 0, -block_size):
        if H % th == 0:
            return th
    raise ValueError(f"no row band of a multiple of {block_size} rows "
                     f"divides H={H}")


def lane_cols(W: int, block_size: int) -> int:
    """Default lane-tile width: ``LANE_TILE`` columns when it divides W (and
    is a multiple of the block), else the whole width."""
    if W > LANE_TILE and W % LANE_TILE == 0 and LANE_TILE % block_size == 0:
        return LANE_TILE
    return W


def _make_tiles(frames: jax.Array, tile_rows: int) -> jax.Array:
    """frames (N, H, W) -> (N, T, TH+2, W+2) edge-padded overlapping bands."""
    N, H, W = frames.shape
    assert H % tile_rows == 0, (H, tile_rows)
    x = jnp.pad(frames, ((0, 0), (1, 1), (1, 1)), mode="edge")  # (N, H+2, W+2)
    T = H // tile_rows
    if T == 1:
        # full-height band: the halo IS the padding — skip the row gather
        return x[:, None]
    # strided gather: band t covers padded rows [t*TH, t*TH + TH + 2)
    rows = (jnp.arange(T) * tile_rows)[:, None] + jnp.arange(tile_rows + 2)[None, :]
    return x[:, rows, :]                                        # (N, T, TH+2, W+2)


def _motion(frames: jax.Array, *, block_size: int, edge_thresh: float,
            tile_rows: Optional[int]) -> jax.Array:
    """frames (C, N, H, W) -> (C, N-1, H/bs, W/bs) through ONE kernel grid
    over every (camera, frame pair, row band)."""
    C, N, H, W = frames.shape
    th = tile_rows or band_rows(H, block_size)
    tw = lane_cols(W, block_size)
    tiles = _make_tiles(frames.reshape(C * N, H, W), th)
    out = edge_motion_pallas(tiles, frames_per_camera=N,
                             block_size=block_size, edge_thresh=edge_thresh,
                             lane_cols=tw,
                             interpret=kernels.pallas_interpret())
    n_pairs, T, L, th_b, tw_b = out.shape
    out = jnp.transpose(out, (0, 1, 3, 2, 4))        # (P, T, th_b, L, tw_b)
    return out.reshape(C, N - 1, T * th_b, L * tw_b)


@functools.partial(jax.jit, static_argnames=(
    "block_size", "tile_rows", "use_kernel", "edge_thresh"))
def segment_motion(frames: jax.Array, *, block_size: int = 8,
                   edge_thresh: float = 0.35,
                   tile_rows: Optional[int] = None,
                   use_kernel: bool = True) -> jax.Array:
    """frames (N, H, W) float32 -> (N-1, H/bs, W/bs) block motion scores.
    ``tile_rows`` overrides the band height (``band_rows``)."""
    if not use_kernel:
        return ref.segment_motion_ref(frames, block_size=block_size,
                                      edge_thresh=edge_thresh)
    return _motion(frames[None], block_size=block_size,
                   edge_thresh=edge_thresh, tile_rows=tile_rows)[0]


def _segment_motion_fleet_impl(frames: jax.Array, *, block_size: int,
                               edge_thresh: float, tile_rows: Optional[int],
                               use_kernel: bool) -> jax.Array:
    if not use_kernel:
        return jax.vmap(lambda f: ref.segment_motion_ref(
            f, block_size=block_size, edge_thresh=edge_thresh))(frames)
    return _motion(frames, block_size=block_size, edge_thresh=edge_thresh,
                   tile_rows=tile_rows)


def segment_motion_fleet(frames: jax.Array, *, block_size: int = 8,
                         edge_thresh: float = 0.35,
                         tile_rows: Optional[int] = None,
                         use_kernel: bool = True,
                         mesh: Optional[Mesh] = None) -> jax.Array:
    """Camera-batched variant: frames (C, N, H, W) -> (C, N-1, H/bs, W/bs).

    Folds the camera axis into the kernel's pair axis so the whole fleet is
    ONE pallas grid launch (C*(N-1), T) instead of C vmapped launches.
    Bit-identical to vmapping ``segment_motion`` over cameras: each (pair,
    band) program is independent.  With ``mesh`` (a ("camera",) mesh) the
    grid is shard_map'd over cameras — each device launches the kernel on its
    C/D-camera shard (C padded with zero cameras when not divisible).
    """
    fn = cached_sharded_jit(
        _segment_motion_fleet_impl,
        dict(block_size=block_size, edge_thresh=edge_thresh,
             tile_rows=tile_rows, use_kernel=use_kernel),
        mesh, in_specs=P("camera"), out_specs=P("camera"))
    C = frames.shape[0]
    C_pad = pad_cameras(C, mesh)
    out = fn(pad_leading(frames, C_pad))
    return out[:C] if C_pad != C else out
