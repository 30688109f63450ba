"""Single-scale anchor-free conv detector (YOLOv5-Lite analogue, in JAX).

Two width variants share the code:
  * ``light``  — the on-camera detector ROIDet runs once per segment
                 (paper section 4: low confidence threshold, low resolution);
  * ``server`` — the edge-server model whose F1 is the paper's utility.

Output grid: stride-16 cells, each predicting (objectness, dx, dy, logw, logh).
Pure functions + ParamDef trees, trained with the framework's own AdamW.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import obs
from repro.common.params import ParamDef, init_params

STRIDE = 16


def _conv_def(cin: int, cout: int, k: int = 3) -> ParamDef:
    return ParamDef((k, k, cin, cout), (None, None, None, None), "normal",
                    jnp.float32, scale=1.4)


def detector_defs(variant: str = "light") -> Dict[str, Any]:
    widths = {"light": (8, 16, 32, 32), "server": (16, 32, 64, 64)}[variant]
    c1, c2, c3, c4 = widths
    return {
        "c1": _conv_def(1, c1), "b1": ParamDef((c1,), (None,), "zeros"),
        "c2": _conv_def(c1, c2), "b2": ParamDef((c2,), (None,), "zeros"),
        "c3": _conv_def(c2, c3), "b3": ParamDef((c3,), (None,), "zeros"),
        "c4": _conv_def(c3, c4), "b4": ParamDef((c4,), (None,), "zeros"),
        "head": _conv_def(c4, 5, k=1), "bh": ParamDef((5,), (None,), "zeros"),
    }


def init_detector(key: jax.Array, variant: str = "light") -> Any:
    return init_params(key, detector_defs(variant))


def _conv(x, w, b, stride=2):
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.nn.relu(y + b)


# The stem: the STEM_CONVS = log2(STEM_FOLD) stride-2 convs that open the
# detector.  On frames whose sides the fold divides, each fold x fold block of
# pixels becomes fold*fold channels and the stem's convs run stride-1 on
# that grid, so they read and write lane-dense activations instead of
# 1-32 channels; the stem's last conv gives the plain activations back.
# On a TPU v5e the folded stem is the slower one at 96x160 and the faster
# from 192x320 up (the size sweep in PERF.md section 6), so frames below
# STEM_MIN_PIXELS keep the plain stem.
STEM_FOLD = 8
STEM_CONVS = 3
STEM_MIN_PIXELS = 192 * 320


def stem_fold(shape: Tuple[int, ...]) -> int:
    """The fold the stem runs with on frames of ``shape`` (B, H, W): 1 (the
    plain convs) unless both sides are multiples of ``STEM_FOLD`` and a
    frame holds at least ``STEM_MIN_PIXELS``."""
    H, W = shape[-2:]
    if H % STEM_FOLD or W % STEM_FOLD or H * W < STEM_MIN_PIXELS:
        return 1
    return STEM_FOLD


def _fold(x: jax.Array, f: int) -> jax.Array:
    """(B, H, W, C) -> (B, H/f, W/f, f*f*C): each f x f block as channels,
    in (row, column, channel) order."""
    B, H, W, C = x.shape
    x = x.reshape(B, H // f, f, W // f, f, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, H // f, W // f, f * f * C)


def _folded_kernel(w: jax.Array, fold_in: int) -> jax.Array:
    """3x3 HWIO weights of a stride-2 SAME conv -> the 2x2 stride-1 HWIO
    kernel of the same conv on its input folded by ``fold_in``, giving its
    output folded by ``fold_in // 2``.

    Output phase q of a folded cell reads input phases fold_in*a + p - 2q
    (a the cell tap, p the input phase) of the 3x3 kernel; every weight
    lands in one place per output phase, every other entry is an exact
    zero."""
    fo = fold_in // 2
    r = (fold_in * np.arange(2)[:, None, None] + np.arange(fold_in)[:, None]
         - 2 * np.arange(fo))                              # (tap, p, q)
    r = np.where((r >= 0) & (r <= 2), r, 3)                # 3: a zero row
    wz = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
    k = wz[r[:, None, :, None, :, None], r[None, :, None, :, None, :]]
    ci, co = w.shape[2:]
    # (ty, tx, py, px, qy, qx, ci, co) -> HWIO: (py, px, ci), (qy, qx, co)
    return k.transpose(0, 1, 2, 3, 6, 4, 5, 7).reshape(
        2, 2, fold_in * fold_in * ci, fo * fo * co)


def _stem(params, frames: jax.Array) -> jax.Array:
    """The stem on (B, H, W) frames -> the plain output of its last conv."""
    f = stem_fold(frames.shape)
    obs.record_detector_stem("folded" if f > 1 else "plain", f, frames.shape)
    x = frames[..., None]
    if f == 1:
        for i in range(1, STEM_CONVS + 1):
            x = _conv(x, params[f"c{i}"], params[f"b{i}"])
        return x
    x = _fold(x, f)
    for i in range(1, STEM_CONVS + 1):
        y = jax.lax.conv_general_dilated(
            x, _folded_kernel(params[f"c{i}"], f), (1, 1), ((0, 1), (0, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        f //= 2
        x = jax.nn.relu(y + jnp.tile(params[f"b{i}"], f * f))
    return x


def forward(params, frames: jax.Array) -> jax.Array:
    """frames: (B, H, W) in [0,1] -> raw grid (B, H/16, W/16, 5)."""
    with jax.named_scope("stem"):
        x = _stem(params, frames)
    for i in range(STEM_CONVS + 1, 5):
        x = _conv(x, params[f"c{i}"], params[f"b{i}"])
    y = jax.lax.conv_general_dilated(
        x, params["head"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + params["bh"]
    return y


def decode_boxes(grid: jax.Array, conf_thresh: float = 0.3, top_k: int = 16
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """grid (B, Gy, Gx, 5) -> boxes (B, K, 4 xyxy), scores (B, K), valid (B, K)."""
    B, Gy, Gx, _ = grid.shape
    obj = jax.nn.sigmoid(grid[..., 0])
    cy = (jnp.arange(Gy)[:, None] + jax.nn.sigmoid(grid[..., 1])) * STRIDE
    cx = (jnp.arange(Gx)[None, :] + jax.nn.sigmoid(grid[..., 2])) * STRIDE
    bw = jnp.exp(jnp.clip(grid[..., 3], -4, 4)) * STRIDE
    bh = jnp.exp(jnp.clip(grid[..., 4], -4, 4)) * STRIDE
    boxes = jnp.stack([cx - bw / 2, cy - bh / 2, cx + bw / 2, cy + bh / 2], -1)
    flat_s = obj.reshape(B, -1)
    flat_b = boxes.reshape(B, -1, 4)
    k = min(top_k, flat_s.shape[1])
    scores, idx = jax.lax.top_k(flat_s, k)
    sel = jnp.take_along_axis(flat_b, idx[..., None], axis=1)
    valid = scores > conf_thresh
    # greedy NMS over the K candidates (K small, unrolled)
    iou = box_iou(sel, sel)                                   # (B,K,K)
    keep = jnp.ones((B, k), bool)
    for i in range(1, k):
        over = (iou[:, i, :i] > 0.45) & keep[:, :i] & valid[:, :i]
        keep = keep.at[:, i].set(~jnp.any(over, axis=-1))
    return sel, scores, valid & keep


def box_iou(a: jax.Array, b: jax.Array) -> jax.Array:
    """a (..., Ka, 4), b (..., Kb, 4) -> IoU (..., Ka, Kb)."""
    ax0, ay0, ax1, ay1 = [a[..., i] for i in range(4)]
    bx0, by0, bx1, by1 = [b[..., i] for i in range(4)]
    ix0 = jnp.maximum(ax0[..., :, None], bx0[..., None, :])
    iy0 = jnp.maximum(ay0[..., :, None], by0[..., None, :])
    ix1 = jnp.minimum(ax1[..., :, None], bx1[..., None, :])
    iy1 = jnp.minimum(ay1[..., :, None], by1[..., None, :])
    iw = jnp.clip(ix1 - ix0, 0)
    ih = jnp.clip(iy1 - iy0, 0)
    inter = iw * ih
    area_a = jnp.clip((ax1 - ax0) * (ay1 - ay0), 0)
    area_b = jnp.clip((bx1 - bx0) * (by1 - by0), 0)
    return inter / jnp.maximum(area_a[..., :, None] + area_b[..., None, :] - inter, 1e-6)


# ---------------------------------------------------------------------------
# training targets + loss
# ---------------------------------------------------------------------------

def encode_targets(boxes: List[Tuple[int, int, int, int]], gy: int, gx: int
                   ) -> np.ndarray:
    """GT boxes (xyxy) -> target grid (Gy, Gx, 5) [obj, dy, dx, logw, logh]."""
    t = np.zeros((gy, gx, 5), np.float32)
    for (x0, y0, x1, y1) in boxes:
        cx, cy = (x0 + x1) / 2, (y0 + y1) / 2
        gxi = int(np.clip(cx // STRIDE, 0, gx - 1))
        gyi = int(np.clip(cy // STRIDE, 0, gy - 1))
        t[gyi, gxi, 0] = 1.0
        t[gyi, gxi, 1] = cy / STRIDE - gyi
        t[gyi, gxi, 2] = cx / STRIDE - gxi
        t[gyi, gxi, 3] = np.log(max(x1 - x0, 1) / STRIDE)
        t[gyi, gxi, 4] = np.log(max(y1 - y0, 1) / STRIDE)
    return t


def detection_loss(params, frames: jax.Array, targets: jax.Array) -> jax.Array:
    grid = forward(params, frames)
    obj_t = targets[..., 0]
    obj_logit = grid[..., 0]
    bce = jnp.mean(
        jnp.maximum(obj_logit, 0) - obj_logit * obj_t +
        jnp.log1p(jnp.exp(-jnp.abs(obj_logit))))
    # box regression only on positive cells
    pos = obj_t > 0.5
    pred_off = jnp.stack([jax.nn.sigmoid(grid[..., 1]), jax.nn.sigmoid(grid[..., 2]),
                          grid[..., 3], grid[..., 4]], -1)
    tgt_off = targets[..., 1:]
    l2 = jnp.sum(jnp.where(pos[..., None], (pred_off - tgt_off) ** 2, 0.0))
    l2 = l2 / jnp.maximum(jnp.sum(pos), 1.0)
    return bce * 4.0 + l2


# ---------------------------------------------------------------------------
# F1 metric (the paper's utility)
# ---------------------------------------------------------------------------

def f1_score_padded(pred_boxes: jax.Array, pred_valid: jax.Array,
                    gt_boxes: jax.Array, gt_valid: jax.Array,
                    iou_thresh: float = 0.3) -> jax.Array:
    """Traced F1 for one frame with padded GT: (K,4),(K,),(G,4),(G,) -> scalar.

    Replicates ``f1_score``'s greedy one-to-one matching (preds visited in
    descending best-IoU order; each checks only its argmax GT) with a
    ``lax.fori_loop``, so it jits, vmaps over batched decoded segments, and
    slots into ``lax.scan`` bodies.  Tie order between equal-IoU preds cannot
    change the match count, so results agree with the numpy path.
    """
    K = pred_boxes.shape[0]
    G = gt_boxes.shape[0]
    iou = box_iou(pred_boxes, gt_boxes)                            # (K, G)
    pair_ok = pred_valid[:, None] & gt_valid[None, :]
    iou_m = jnp.where(pair_ok, iou, -1.0)
    order = jnp.argsort(-jnp.max(iou_m, axis=1))                   # best first

    def body(p, carry):
        matched, tp = carry
        i = order[p]
        row = iou_m[i]
        j = jnp.argmax(row)
        ok = pred_valid[i] & (row[j] >= iou_thresh) & (~matched[j])
        matched = matched.at[j].set(matched[j] | ok)
        return matched, tp + ok.astype(jnp.int32)

    _, tp = jax.lax.fori_loop(0, K, body, (jnp.zeros((G,), bool),
                                           jnp.int32(0)))
    n_pred = jnp.sum(pred_valid)
    n_gt = jnp.sum(gt_valid)
    tpf = tp.astype(jnp.float32)
    prec = tpf / jnp.maximum(n_pred, 1)
    rec = tpf / jnp.maximum(n_gt, 1)
    f1 = jnp.where(tp == 0, 0.0, 2 * prec * rec / jnp.maximum(prec + rec, 1e-9))
    both_empty = (n_pred == 0) & (n_gt == 0)
    either_empty = (n_pred == 0) | (n_gt == 0)
    return jnp.where(both_empty, 1.0, jnp.where(either_empty, 0.0, f1))


def f1_score_batch(pred_boxes: jax.Array, pred_valid: jax.Array,
                   gt_boxes: jax.Array, gt_valid: jax.Array,
                   iou_thresh: float = 0.3) -> jax.Array:
    """Batched F1: (B,K,4),(B,K),(B,G,4),(B,G) -> (B,)."""
    return jax.vmap(
        lambda pb, pv, gb, gv: f1_score_padded(pb, pv, gb, gv, iou_thresh)
    )(pred_boxes, pred_valid, gt_boxes, gt_valid)


def f1_score(pred_boxes: np.ndarray, pred_valid: np.ndarray,
             gt_boxes: List[Tuple[int, int, int, int]],
             iou_thresh: float = 0.3) -> float:
    """Greedy one-to-one matching F1 for one frame."""
    preds = [tuple(b) for b, v in zip(np.asarray(pred_boxes), np.asarray(pred_valid)) if v]
    if not preds and not gt_boxes:
        return 1.0
    if not preds or not gt_boxes:
        return 0.0
    a = np.array(preds, np.float32)[None]
    b = np.array(gt_boxes, np.float32)[None]
    iou = np.asarray(box_iou(jnp.asarray(a), jnp.asarray(b)))[0]
    matched_gt: set = set()
    tp = 0
    for i in np.argsort(-iou.max(axis=1)):
        j = int(np.argmax(iou[i]))
        if iou[i, j] >= iou_thresh and j not in matched_gt:
            matched_gt.add(j)
            tp += 1
    prec = tp / len(preds)
    rec = tp / len(gt_boxes)
    return 0.0 if tp == 0 else 2 * prec * rec / (prec + rec)
