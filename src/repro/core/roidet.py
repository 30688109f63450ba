"""ROIDet — Regions-of-Interest detection (paper section 4, Algorithm 1).

Per video segment (N frames from a static camera):
  1. stationary objects: the *light* conv detector runs ONCE per segment on
     the first frame, at a low confidence threshold (paper: reduced model +
     low threshold to avoid misses);
  2. moving objects: fused Sobel-edge + temporal-diff + block-sum
     (Pallas ``edge_motion`` kernel), thresholded into the binary matrix D,
     OR-ed across all consecutive pairs of the segment;
  3. connected components of D (min-label propagation) -> moving boxes;
  4. ROI = union of both box sets; a block-grid coverage mask is returned
     for cropping/masked encoding, plus the content features the server
     consumes: a = ROI-area ratio, c = mean on-camera detection confidence.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import cc
from repro.kernels.edge_motion import ops as em_ops
from repro.models import detector as det
from repro.sharding.rules import cached_sharded_jit, pad_cameras, pad_leading


# shared defaults for EVERY ROIDet entry point — the single-camera path,
# the fleet path and the episode scan must stay numerically identical, so
# they all read these instead of restating literals
MOTION_THRESH = 16.0
EDGE_THRESH = 0.35
CONF_THRESH = 0.25
MAX_BOXES = 16


class ROIResult(NamedTuple):
    mask: jax.Array        # (M, N) bool — block-grid ROI coverage
    area_ratio: jax.Array  # scalar in [0,1] — feature `a`
    confidence: jax.Array  # scalar in [0,1] — feature `c`
    motion_boxes: jax.Array    # (K, 4) block coords
    motion_valid: jax.Array    # (K,)
    det_boxes: jax.Array       # (Kd, 4) pixel coords
    det_valid: jax.Array       # (Kd,)


def _boxes_to_mask(boxes: jax.Array, valid: jax.Array, M: int, N: int,
                   scale: float = 1.0) -> jax.Array:
    """Rasterize (K,4) xyxy boxes (optionally pixel->block scaled) onto (M,N).

    Accumulates box-by-box with a ``fori_loop`` | OR instead of vmapping to a
    (K, M, N) stack + ``jnp.any`` — the stack was the C-batched path's
    peak-memory hotspot ((C, K, M, N) live at once under vmap)."""
    rows = jnp.arange(M)[:, None]
    colsg = jnp.arange(N)[None, :]

    def body(i, acc):
        x0, y0, x1, y1 = [boxes[i, j].astype(jnp.float32) * scale
                          for j in range(4)]
        m = ((rows >= jnp.floor(y0)) & (rows < jnp.ceil(y1)) &
             (colsg >= jnp.floor(x0)) & (colsg < jnp.ceil(x1)))
        return acc | (m & valid[i])

    return jax.lax.fori_loop(0, boxes.shape[0], body,
                             jnp.zeros((M, N), bool))


def _roi_mask(mboxes: jax.Array, mvalid: jax.Array, dboxes: jax.Array,
              dvalid: jax.Array, M: int, N: int, block_size: int):
    """One camera's ROI from its motion components' boxes and the detector
    boxes (Alg.1 l.12): their union on the block grid, dilated by one block
    (box-boundary pixels carry the object's edges — without the halo,
    cropped encodes clip object borders and detection recall drops at high
    bitrates).  Returns (mask, area_ratio)."""
    motion_mask = _boxes_to_mask(mboxes, mvalid, M, N, scale=1.0)
    det_mask = _boxes_to_mask(dboxes, dvalid, M, N, scale=1.0 / block_size)
    mask = motion_mask | det_mask
    p = jnp.pad(mask, 1)
    mask = (p[1:-1, 1:-1] | p[:-2, 1:-1] | p[2:, 1:-1]
            | p[1:-1, :-2] | p[1:-1, 2:])
    return mask, jnp.mean(mask.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=(
    "block_size", "use_kernel", "max_boxes", "motion_thresh", "edge_thresh",
    "conf_thresh"))
def roidet(frames: jax.Array, det_params: Any, *, block_size: int = 8,
           motion_thresh: float = MOTION_THRESH,
           edge_thresh: float = EDGE_THRESH,
           conf_thresh: float = CONF_THRESH, use_kernel: bool = True,
           max_boxes: int = MAX_BOXES) -> ROIResult:
    """frames: (N, H, W) float32 in [0,1] — one camera's segment."""
    N_f, H, W = frames.shape
    M, N = H // block_size, W // block_size

    # ---- stationary objects: light detector on the first + last frame
    # (paper Alg.1 l.1 runs once per segment; the second run catches objects
    # that enter mid-segment and still fits the Pi budget — the paper's
    # YoloL takes ~0.4 s/run vs the 1 s slot, Fig. 6)
    grid = det.forward(det_params, jnp.stack([frames[0], frames[-1]]))
    b2, s2, v2 = det.decode_boxes(grid, conf_thresh=conf_thresh)
    dboxes = jnp.concatenate([b2[0], b2[1]], axis=0)
    dscores = jnp.concatenate([s2[0], s2[1]], axis=0)
    dvalid = jnp.concatenate([v2[0], v2[1]], axis=0)
    conf = jnp.sum(jnp.where(dvalid, dscores, 0.0)) / jnp.maximum(
        jnp.sum(dvalid), 1)

    # ---- moving objects: edge-diff blocks (Alg.1 l.2-10)
    scores = em_ops.segment_motion(frames, block_size=block_size,
                                   edge_thresh=edge_thresh,
                                   use_kernel=use_kernel)   # (N-1, M, N)
    D = jnp.any(scores > motion_thresh, axis=0)             # (M, N) bool

    # ---- connected components + union ROI (Alg.1 l.11-12)
    with jax.named_scope("cc"):
        mboxes, mvalid, _ = cc.label_and_boxes(D, max_boxes=max_boxes)
    mask, area = _roi_mask(mboxes, mvalid, dboxes, dvalid, M, N, block_size)
    return ROIResult(mask=mask, area_ratio=area, confidence=conf,
                     motion_boxes=mboxes, motion_valid=mvalid,
                     det_boxes=dboxes, det_valid=dvalid)


@jax.named_scope("roidet")
def _roidet_fleet_impl(frames: jax.Array, det_params: Any, *, block_size: int,
                       motion_thresh: float, edge_thresh: float,
                       conf_thresh: float, use_kernel: bool,
                       max_boxes: int, bounded_cc: bool = False) -> ROIResult:
    C, N_f, H, W = frames.shape
    M, N = H // block_size, W // block_size

    # ---- stationary objects: light detector on first + last frame, all cams
    grid = det.forward(det_params,
                       jnp.concatenate([frames[:, 0], frames[:, -1]]))
    b2, s2, v2 = det.decode_boxes(grid, conf_thresh=conf_thresh)  # (2C,K,..)
    dboxes = jnp.concatenate([b2[:C], b2[C:]], axis=1)            # (C,2K,4)
    dscores = jnp.concatenate([s2[:C], s2[C:]], axis=1)
    dvalid = jnp.concatenate([v2[:C], v2[C:]], axis=1)
    conf = (jnp.sum(jnp.where(dvalid, dscores, 0.0), axis=1)
            / jnp.maximum(jnp.sum(dvalid, axis=1), 1))

    # ---- moving objects: one kernel grid over every (camera, frame pair)
    scores = em_ops.segment_motion_fleet(frames, block_size=block_size,
                                         edge_thresh=edge_thresh,
                                         use_kernel=use_kernel)  # (C,N-1,M,N)
    D = jnp.any(scores > motion_thresh, axis=1)                  # (C,M,N)

    # ---- connected components (scope ``cc``, opened outside the camera
    # vmap so the profile names it) + union ROI (Alg.1 l.11-12)
    with jax.named_scope("cc"):
        mboxes, mvalid, _ = jax.vmap(functools.partial(
            cc.label_and_boxes, max_boxes=max_boxes, bounded=bounded_cc))(D)
    mask, area = jax.vmap(
        lambda mb, mv, db, dv: _roi_mask(mb, mv, db, dv, M, N, block_size)
    )(mboxes, mvalid, dboxes, dvalid)
    return ROIResult(mask=mask, area_ratio=area, confidence=conf,
                     motion_boxes=mboxes, motion_valid=mvalid,
                     det_boxes=dboxes, det_valid=dvalid)


def roidet_fleet(frames: jax.Array, det_params: Any, *, block_size: int = 8,
                 motion_thresh: float = MOTION_THRESH,
                 edge_thresh: float = EDGE_THRESH,
                 conf_thresh: float = CONF_THRESH, use_kernel: bool = True,
                 max_boxes: int = MAX_BOXES, mesh: Optional[Mesh] = None
                 ) -> ROIResult:
    """Fleet ROIDet: frames (C, N, H, W) -> camera-batched ROIResult.

    Same math as vmapping ``roidet`` over cameras, restructured so the light
    detector runs ONE (2C,H,W) forward and motion runs ONE pallas grid over
    all C*(N-1) frame pairs (``segment_motion_fleet``) — a single dispatch
    per slot for the whole camera side.

    With ``mesh`` (a ("camera",) mesh), the whole thing is shard_map'd over
    the camera axis: each device runs the identical per-camera program on its
    C/D shard, bit-stable vs the single-device path (C padded with inert
    zero cameras when not divisible, sliced back off).
    """
    cam = P("camera")
    fn = cached_sharded_jit(
        _roidet_fleet_impl,
        dict(block_size=block_size, motion_thresh=motion_thresh,
             edge_thresh=edge_thresh, conf_thresh=conf_thresh,
             use_kernel=use_kernel, max_boxes=max_boxes),
        mesh, in_specs=(cam, P()), out_specs=ROIResult(*(cam,) * 7))
    C = frames.shape[0]
    C_pad = pad_cameras(C, mesh)
    out = fn(pad_leading(frames, C_pad), det_params)
    if C_pad != C:
        out = ROIResult(*(x[:C] for x in out))
    return out


def full_frame_mask(num_cameras: int, H: int, W: int, block_size: int
                    ) -> jax.Array:
    """All-ones block mask batch: encodes 'no cropping' for the fleet path
    (crop_to_mask with an all-ones mask is the identity, and its pixel count
    is exactly H*W)."""
    return jnp.ones((num_cameras, H // block_size, W // block_size), bool)


def crop_to_mask(frames: jax.Array, mask: jax.Array, block_size: int) -> jax.Array:
    """Masked encoding: non-ROI blocks are replaced by the frame mean (flat
    background costs ~no bits in a codec and — unlike zero-fill — introduces
    no artificial high-contrast edges at ROI boundaries that would perturb
    the downstream detector)."""
    up = jnp.kron(mask.astype(frames.dtype),
                  jnp.ones((block_size, block_size), frames.dtype))[None]
    fill = jnp.mean(frames, axis=(1, 2), keepdims=True)
    return frames * up + fill * (1.0 - up)
