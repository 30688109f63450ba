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


def _resolve_tile_rows(tile_rows: Optional[int], H: int) -> int:
    """Default row-band height: 32 compiled (VMEM-bounded), FULL frame in
    interpret mode (``kernels.pallas_interpret``, read at trace time).
    Interpret-mode pallas unrolls one kernel body per grid program at
    trace time, so a (P, T) grid costs P*T interpreter passes —
    collapsing the tile axis (T=1) cuts them H/32-fold per frame pair with
    bit-identical output (tiling is halo-exact by construction), which is
    what bounds the fleet motion path on one device."""
    if tile_rows is None:
        tile_rows = H if kernels.pallas_interpret() else 32
    return min(tile_rows, H)


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


@functools.partial(jax.jit, static_argnames=("block_size", "tile_rows", "use_kernel", "edge_thresh"))
def segment_motion(frames: jax.Array, *, block_size: int = 8,
                   edge_thresh: float = 0.35,
                   tile_rows: Optional[int] = None,
                   use_kernel: bool = True) -> jax.Array:
    """frames (N, H, W) float32 -> (N-1, H/bs, W/bs) block motion scores."""
    N, H, W = frames.shape
    tile_rows = _resolve_tile_rows(tile_rows, H)
    if not use_kernel:
        return ref.segment_motion_ref(frames, block_size=block_size,
                                      edge_thresh=edge_thresh)
    tiles = _make_tiles(frames, tile_rows)                       # (N,T,TH+2,W+2)
    out = edge_motion_pallas(tiles[:-1], tiles[1:], block_size=block_size,
                             edge_thresh=edge_thresh,
                             interpret=kernels.pallas_interpret())
    P, T, th_b, w_b = out.shape
    return out.reshape(P, T * th_b, w_b)


def _segment_motion_fleet_impl(frames: jax.Array, *, block_size: int,
                               edge_thresh: float, tile_rows: Optional[int],
                               use_kernel: bool) -> jax.Array:
    C, N, H, W = frames.shape
    tile_rows = _resolve_tile_rows(tile_rows, H)
    if not use_kernel:
        return jax.vmap(lambda f: ref.segment_motion_ref(
            f, block_size=block_size, edge_thresh=edge_thresh))(frames)
    tiles = _make_tiles(frames.reshape(C * N, H, W), tile_rows)
    tiles = tiles.reshape(C, N, *tiles.shape[1:])     # (C,N,T,TH+2,W+2)
    pair_shape = (C * (N - 1),) + tiles.shape[2:]
    out = edge_motion_pallas(tiles[:, :-1].reshape(pair_shape),
                             tiles[:, 1:].reshape(pair_shape),
                             block_size=block_size, edge_thresh=edge_thresh,
                             interpret=kernels.pallas_interpret())
    n_pairs, T, th_b, w_b = out.shape
    return out.reshape(C, N - 1, T * th_b, w_b)


def segment_motion_fleet(frames: jax.Array, *, block_size: int = 8,
                         edge_thresh: float = 0.35,
                         tile_rows: Optional[int] = None,
                         use_kernel: bool = True,
                         mesh: Optional[Mesh] = None) -> jax.Array:
    """Camera-batched variant: frames (C, N, H, W) -> (C, N-1, H/bs, W/bs).

    Folds the camera axis into the kernel's pair axis so the whole fleet is
    ONE pallas grid launch (C*(N-1), T) instead of C vmapped launches.
    Bit-identical to vmapping ``segment_motion`` over cameras: each (pair,
    tile) program is independent.  With ``mesh`` (a ("camera",) mesh) the
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
