"""Connected-components labeling + box extraction on the block-motion grid.

The paper uses Spaghetti labeling (Bolelli et al.) — a DAG-driven two-pass
CPU algorithm with branchy per-pixel decisions.  That control flow has no
TPU analogue, so we use the classic data-parallel equivalent: **iterative
min-label propagation** (each active cell takes the min label of its
4-neighbourhood until fixpoint, O(component diameter) sweeps, all-vector
ops).  Outputs are identical components; DESIGN.md records the divergence.

The grid is small (H/bs x W/bs, e.g. 68x120 for 1080p @ 16px blocks) so the
whole thing lives in registers/VMEM and box extraction is a segment-min/max
over at most M*N segments.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# a host scalar, not a device array: traced code embeds it as a literal,
# where a captured device array would be fetched to the host when the
# program is lowered — a device-to-host transfer the episode's transfer
# guard rejects on a TPU
INF = np.int32(2 ** 30)


@functools.partial(jax.jit, static_argnames=("max_boxes", "bounded"))
def label_and_boxes(mask: jax.Array, max_boxes: int = 16,
                    bounded: bool = False
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """mask (M, N) bool -> (boxes (K,4) int32 [x0,y0,x1,y1) in block coords,
    valid (K,) bool, labels (M,N) int32).  Boxes sorted by area desc.

    ``bounded`` swaps the until-fixpoint ``while_loop`` for a fixed
    ``fori_loop`` of M*N sweeps — the while's own iteration cap, so the
    fixpoint (hence every output) is identical, at O((M*N)^2) worst-case
    work instead of O(component diameter).  It exists for
    ``jax.experimental.checkify``: the checked diagnostics lane can't
    functionalize a batched-predicate while-loop (this one is vmapped per
    camera with a data-dependent cond), while a fori_loop transforms
    cleanly.  Keep it off on hot paths."""
    M, N = mask.shape
    idx = jnp.arange(M * N, dtype=jnp.int32).reshape(M, N)
    labels = jnp.where(mask, idx, INF)

    def propagate(labels):
        p = jnp.pad(labels, 1, constant_values=INF)
        neigh = jnp.minimum(
            jnp.minimum(p[:-2, 1:-1], p[2:, 1:-1]),
            jnp.minimum(p[1:-1, :-2], p[1:-1, 2:]))
        return jnp.where(mask, jnp.minimum(labels, neigh), INF)

    if bounded:
        labels = jax.lax.fori_loop(0, M * N, lambda _, l: propagate(l),
                                   propagate(labels))
    else:
        def cond(state):
            labels, prev, it = state
            return jnp.logical_and(jnp.any(labels != prev), it < M * N)

        def body(state):
            labels, _, it = state
            return propagate(labels), labels, it + 1

        labels, _, _ = jax.lax.while_loop(
            cond, body, (propagate(labels), labels, jnp.int32(0)))

    # box extraction: segment min/max of row/col per root label
    flat = labels.reshape(-1)
    seg = jnp.where(flat == INF, M * N, flat)          # dump background to seg M*N
    rows = jnp.arange(M * N, dtype=jnp.int32) // N
    cols = jnp.arange(M * N, dtype=jnp.int32) % N
    num_seg = M * N + 1
    r0 = jax.ops.segment_min(rows, seg, num_segments=num_seg)
    r1 = jax.ops.segment_max(rows, seg, num_segments=num_seg)
    c0 = jax.ops.segment_min(cols, seg, num_segments=num_seg)
    c1 = jax.ops.segment_max(cols, seg, num_segments=num_seg)
    cnt = jax.ops.segment_sum(jnp.ones_like(seg), seg, num_segments=num_seg)
    is_comp = (cnt > 0) & (jnp.arange(num_seg) < M * N)
    area = jnp.where(is_comp, (r1 - r0 + 1) * (c1 - c0 + 1), -1)
    k = min(max_boxes, num_seg)
    top_area, top_idx = jax.lax.top_k(area, k)
    valid = top_area > 0
    boxes = jnp.stack([c0[top_idx], r0[top_idx],
                       c1[top_idx] + 1, r1[top_idx] + 1], axis=-1).astype(jnp.int32)
    boxes = jnp.where(valid[:, None], boxes, 0)
    if k < max_boxes:
        boxes = jnp.pad(boxes, ((0, max_boxes - k), (0, 0)))
        valid = jnp.pad(valid, (0, max_boxes - k))
    return boxes, valid, labels
