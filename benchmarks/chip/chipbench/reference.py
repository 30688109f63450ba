"""Plain reference of the served fleet: slot after slot in straightforward jnp.

It imports nothing of the program.  Given a configuration file, the
detector and utility weights, the scene seed and the offered stream, it
computes every slot's log row the way the paper's pipeline defines it:

  scene synthesis -> [deepstream] ROIDet: light detector on the first and
  last frame, Sobel-edge motion blocks, connected components, ROI mask ->
  control: elastic borrow/repay, utility MLP table, knapsack DP over the
  bitrate grid ([reducto] fair split) -> [reducto] keep flags against the
  last kept frame -> ROI crop, rate-distortion codec with seeded coding
  noise -> server detector on the scored frames -> greedy one-to-one F1
  (reducto: mixed with the reused detections on the filtered frames).

The carry from slot to slot is the elastic state, reducto's reference
frames and the previous liveness row.  Slots run in fixed chunks of
``CHUNK`` through one jitted ``lax.scan``, so the reference compiles once
per configuration whatever the number of slots.

``detector_dtype`` runs both detectors in another precision: the benchmark's
control computes them in bfloat16 and must come out as not correct.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 32
STRIDE = 16
INF = np.int32(2 ** 30)
NEG = -1e30
MOTION_THRESH = 16.0      # ROIDet block-motion threshold
EDGE_THRESH = 0.35        # Sobel magnitude threshold
CONF_THRESH = 0.25        # light detector confidence threshold
MAX_BOXES = 16            # motion boxes kept per camera
KEEP_THRESH = 25.0        # reducto: block-motion mass of a changed frame
SERVER_CONF = 0.4         # server detector confidence threshold
SALT = 0x0DEC             # codec key domain separation from the scene key
LOG_KEYS = ("utility", "mean_f1", "bytes", "W", "extra", "area",
            "alloc_kbps")


# -- scene -----------------------------------------------------------------

def init_scene(cfg: Dict, seed: int) -> Dict[str, np.ndarray]:
    """Scene geometry from the seed: per-camera textured background with
    parked objects, view offsets and lags, and a pool of periodic movers."""
    sc = cfg["scene"]
    rng = np.random.default_rng(seed)
    C, H, W = cfg["num_cameras"], sc["height"], sc["width"]
    lo, hi = sc["obj_size_range"]
    bg = np.zeros((C, H, W), np.float32)
    for i in range(C):
        base = rng.uniform(0.25, 0.55, (H // 8, W // 8))
        bg[i] = np.kron(base, np.ones((8, 8)))[:H, :W]
    offsets = rng.uniform(-sc["view_jitter"], sc["view_jitter"], (C, 2))
    lags = rng.integers(0, sc["cam_lag_frames"] + 1, C)
    S = sc["num_stationary"]
    stat = np.zeros((C, S, 4), np.float32)
    for i in range(C):
        for s in range(S):
            w = int(rng.integers(lo, hi))
            h = int(rng.integers(lo, hi))
            x = int(rng.integers(0, W - w))
            y = int(rng.integers(0, H - h))
            bg[i, y:y + h, x:x + w] = float(rng.uniform(0.7, 0.95))
            stat[i, s] = (x, y, x + w, y + h)
    K = sc["max_objects"]
    period = rng.integers(140, 320, K).astype(np.float32)
    objects = np.stack([
        rng.integers(0, 2, K).astype(np.float32),             # side
        np.maximum(0.5, rng.normal(sc["mean_speed"], 1.0, K)),  # speed
        rng.uniform(0.15, 0.85, K) * H,                       # y0
        rng.normal(0, 0.2, K),                                # vy
        rng.integers(lo, hi, K).astype(np.float32),           # w
        rng.integers(lo, hi, K).astype(np.float32),           # h
        rng.uniform(0.6, 1.0, K),                             # value
        rng.uniform(0, period),                               # phase
        period,
        np.minimum(rng.integers(60, 240, K), period - 30),    # ttl
    ], axis=1).astype(np.float32)
    return {"bg": bg, "stat": stat, "offsets": offsets.astype(np.float32),
            "lags": lags.astype(np.int32), "objects": objects}


def gt_capacity(cfg: Dict) -> int:
    sc = cfg["scene"]
    n = sc["max_objects"] + sc["num_stationary"]
    return max(16, -(-n // 8) * 8)


def scene_slot(sc: Dict, scene, key, t, G: int):
    """Slot t: frames (C, N, H, W), GT boxes (C, N, G, 4), valid (C, N, G)."""
    bg, stat, offsets, lags, obj = (scene["bg"], scene["stat"],
                                    scene["offsets"], scene["lags"],
                                    scene["objects"])
    C, H, W = bg.shape
    N = int(sc["fps"] * sc["seg_seconds"])
    K, S = obj.shape[0], stat.shape[1]
    g = jnp.maximum(t * N + jnp.arange(N)[None, :] - lags[:, None], 0)
    gf = g.astype(jnp.float32)[None]                          # (1, C, N)
    side, speed, y0, vy, w, h, val, phase, period, ttl = (
        obj[:, i, None, None] for i in range(10))             # (K, 1, 1)
    u = jnp.mod(gf + phase, period)                            # (K, C, N)
    x = jnp.where(side > 0.5, (W + 20.0) - speed * u, -20.0 + speed * u)
    y = y0 + vy * u
    x0 = jnp.round(x + offsets[None, :, 0, None])
    y0r = jnp.round(y + offsets[None, :, 1, None])
    cx0, cy0 = jnp.clip(x0, 0, W), jnp.clip(y0r, 0, H)
    cx1, cy1 = jnp.clip(x0 + w, 0, W), jnp.clip(y0r + h, 0, H)
    ok = (u < ttl) & (cx1 - cx0 >= 3) & (cy1 - cy0 >= 3)       # (K, C, N)
    frames = jnp.broadcast_to(bg[:, None], (C, N, H, W))
    rows = jnp.arange(H, dtype=jnp.float32)[:, None]
    cols = jnp.arange(W, dtype=jnp.float32)[None, :]
    for k in range(K):                                         # paint in order
        e = lambda a: a[k][:, :, None, None]
        in_c = (cols >= e(cx0)) & (cols < e(cx1)) & e(ok)
        body = in_c & (rows >= e(cy0)) & (rows < e(cy1))
        s0 = e(cy0) + jnp.floor((e(cy1) - e(cy0)) / 3.0)
        s1 = e(cy0) + jnp.floor((e(cy1) - e(cy0)) / 2.0)
        stripe = in_c & (rows >= s0) & (rows < s1)
        frames = jnp.where(body, val[k, 0, 0], frames)
        frames = jnp.where(stripe, val[k, 0, 0] * 0.6, frames)
    kt = jax.random.fold_in(key, t)
    noise = jax.vmap(lambda c: jax.random.normal(
        jax.random.fold_in(kt, c), (N, H, W), jnp.float32))(
            jnp.arange(C, dtype=jnp.int32))
    frames = jnp.clip(frames + sc["noise_std"] * noise, 0.0, 1.0)
    mov = jnp.transpose(jnp.stack([cx0, cy0, cx1, cy1], -1), (1, 2, 0, 3))
    gtb = jnp.concatenate([jnp.broadcast_to(stat[:, None], (C, N, S, 4)),
                           mov], axis=2)
    gtv = jnp.concatenate([jnp.ones((C, N, S), bool),
                           jnp.transpose(ok, (1, 2, 0))], axis=2)
    gtb = jnp.where(gtv[..., None], gtb, 0.0)
    pad = G - S - K
    gtb = jnp.pad(gtb, ((0, 0), (0, 0), (0, pad), (0, 0)))
    gtv = jnp.pad(gtv, ((0, 0), (0, 0), (0, pad)))
    return frames, gtb.astype(jnp.float32), gtv


# -- detector ----------------------------------------------------------------

def detect(params, frames, dtype):
    """Conv detector: (B, H, W) -> raw grid (B, H/16, W/16, 5) in float32."""
    x = frames[..., None].astype(dtype)
    dn = ("NHWC", "HWIO", "NHWC")
    for i in (1, 2, 3, 4):
        x = jax.lax.conv_general_dilated(
            x, params[f"c{i}"].astype(dtype), (2, 2), "SAME",
            dimension_numbers=dn)
        x = jax.nn.relu(x + params[f"b{i}"].astype(dtype))
    y = jax.lax.conv_general_dilated(x, params["head"].astype(dtype), (1, 1),
                                     "SAME", dimension_numbers=dn)
    return (y + params["bh"].astype(dtype)).astype(jnp.float32)


def box_iou(a, b):
    ix0 = jnp.maximum(a[..., :, None, 0], b[..., None, :, 0])
    iy0 = jnp.maximum(a[..., :, None, 1], b[..., None, :, 1])
    ix1 = jnp.minimum(a[..., :, None, 2], b[..., None, :, 2])
    iy1 = jnp.minimum(a[..., :, None, 3], b[..., None, :, 3])
    inter = jnp.clip(ix1 - ix0, 0) * jnp.clip(iy1 - iy0, 0)
    area_a = jnp.clip((a[..., 2] - a[..., 0]) * (a[..., 3] - a[..., 1]), 0)
    area_b = jnp.clip((b[..., 2] - b[..., 0]) * (b[..., 3] - b[..., 1]), 0)
    return inter / jnp.maximum(area_a[..., :, None] + area_b[..., None, :]
                               - inter, 1e-6)


def decode(grid, conf_thresh: float, top_k: int = 16):
    """Grid -> the top-k boxes (xyxy), scores and valid after greedy NMS."""
    B, Gy, Gx, _ = grid.shape
    obj = jax.nn.sigmoid(grid[..., 0])
    cy = (jnp.arange(Gy)[:, None] + jax.nn.sigmoid(grid[..., 1])) * STRIDE
    cx = (jnp.arange(Gx)[None, :] + jax.nn.sigmoid(grid[..., 2])) * STRIDE
    bw = jnp.exp(jnp.clip(grid[..., 3], -4, 4)) * STRIDE
    bh = jnp.exp(jnp.clip(grid[..., 4], -4, 4)) * STRIDE
    boxes = jnp.stack([cx - bw / 2, cy - bh / 2, cx + bw / 2, cy + bh / 2], -1)
    k = min(top_k, Gy * Gx)
    scores, idx = jax.lax.top_k(obj.reshape(B, -1), k)
    sel = jnp.take_along_axis(boxes.reshape(B, -1, 4), idx[..., None], axis=1)
    valid = scores > conf_thresh
    iou = box_iou(sel, sel)
    keep = jnp.ones((B, k), bool)
    for i in range(1, k):
        over = (iou[:, i, :i] > 0.45) & keep[:, :i] & valid[:, :i]
        keep = keep.at[:, i].set(~jnp.any(over, axis=-1))
    return sel, scores, valid & keep


def f1_frame(pb, pv, gb, gv, iou_thresh: float = 0.3):
    """Greedy one-to-one matching F1 of one frame (predictions visited in
    descending best-IoU order, each against its best ground-truth box)."""
    K, G = pb.shape[0], gb.shape[0]
    iou = jnp.where(pv[:, None] & gv[None, :], box_iou(pb, gb), -1.0)
    order = jnp.argsort(-jnp.max(iou, axis=1))

    def body(p, carry):
        matched, tp = carry
        row = iou[order[p]]
        j = jnp.argmax(row)
        ok = pv[order[p]] & (row[j] >= iou_thresh) & (~matched[j])
        return matched.at[j].set(matched[j] | ok), tp + ok.astype(jnp.int32)

    _, tp = jax.lax.fori_loop(0, K, body, (jnp.zeros((G,), bool),
                                           jnp.int32(0)))
    n_pred, n_gt = jnp.sum(pv), jnp.sum(gv)
    tpf = tp.astype(jnp.float32)
    prec = tpf / jnp.maximum(n_pred, 1)
    rec = tpf / jnp.maximum(n_gt, 1)
    f1 = jnp.where(tp == 0, 0.0, 2 * prec * rec / jnp.maximum(prec + rec, 1e-9))
    return jnp.where((n_pred == 0) & (n_gt == 0), 1.0,
                     jnp.where((n_pred == 0) | (n_gt == 0), 0.0, f1))


f1_batch = jax.vmap(f1_frame)


# -- ROIDet ------------------------------------------------------------------

def sobel_edges(frame):
    x = jnp.pad(frame, 1, mode="edge")
    tl, tc, tr = x[:-2, :-2], x[:-2, 1:-1], x[:-2, 2:]
    ml, mr = x[1:-1, :-2], x[1:-1, 2:]
    bl, bc, br = x[2:, :-2], x[2:, 1:-1], x[2:, 2:]
    gx = (tr + 2.0 * mr + br) - (tl + 2.0 * ml + bl)
    gy = (bl + 2.0 * bc + br) - (tl + 2.0 * tc + tr)
    return gx * gx + gy * gy > EDGE_THRESH * EDGE_THRESH


def motion_blocks(frames, bs: int):
    """(N, H, W) -> (N-1, H/bs, W/bs): changed edge pixels per block, per
    consecutive frame pair."""
    N, H, W = frames.shape
    e = jax.vmap(sobel_edges)(frames)
    d = jnp.logical_xor(e[:-1], e[1:]).astype(jnp.float32)
    return d.reshape(N - 1, H // bs, bs, W // bs, bs).sum(axis=(2, 4))


def components(mask, max_boxes: int):
    """Connected components (4-neighbourhood, min-label propagation to a
    fixpoint) -> the max_boxes largest bounding boxes in block units."""
    M, N = mask.shape
    labels = jnp.where(mask, jnp.arange(M * N, dtype=jnp.int32).reshape(M, N),
                       INF)

    def step(lab):
        p = jnp.pad(lab, 1, constant_values=INF)
        nb = jnp.minimum(jnp.minimum(p[:-2, 1:-1], p[2:, 1:-1]),
                         jnp.minimum(p[1:-1, :-2], p[1:-1, 2:]))
        return jnp.where(mask, jnp.minimum(lab, nb), INF)

    labels, _, _ = jax.lax.while_loop(
        lambda s: jnp.any(s[0] != s[1]) & (s[2] < M * N),
        lambda s: (step(s[0]), s[0], s[2] + 1),
        (step(labels), labels, jnp.int32(0)))
    seg = jnp.where(labels.reshape(-1) == INF, M * N, labels.reshape(-1))
    rows = jnp.arange(M * N, dtype=jnp.int32) // N
    cols = jnp.arange(M * N, dtype=jnp.int32) % N
    n = M * N + 1
    r0 = jax.ops.segment_min(rows, seg, num_segments=n)
    r1 = jax.ops.segment_max(rows, seg, num_segments=n)
    c0 = jax.ops.segment_min(cols, seg, num_segments=n)
    c1 = jax.ops.segment_max(cols, seg, num_segments=n)
    cnt = jax.ops.segment_sum(jnp.ones_like(seg), seg, num_segments=n)
    is_comp = (cnt > 0) & (jnp.arange(n) < M * N)
    area = jnp.where(is_comp, (r1 - r0 + 1) * (c1 - c0 + 1), -1)
    top, idx = jax.lax.top_k(area, max_boxes)
    valid = top > 0
    boxes = jnp.stack([c0[idx], r0[idx], c1[idx] + 1, r1[idx] + 1], -1)
    return jnp.where(valid[:, None], boxes, 0), valid


def raster(boxes, valid, M: int, N: int, scale: float):
    """OR of box footprints on the (M, N) block grid."""
    b = boxes.astype(jnp.float32) * scale
    r = jnp.arange(M)[None, :, None]
    c = jnp.arange(N)[None, None, :]
    m = ((r >= jnp.floor(b[:, 1, None, None])) & (r < jnp.ceil(b[:, 3, None, None]))
         & (c >= jnp.floor(b[:, 0, None, None])) & (c < jnp.ceil(b[:, 2, None, None])))
    return jnp.any(m & valid[:, None, None], axis=0)


def roidet(frames, light, bs: int, dtype):
    """(C, N, H, W) -> ROI block masks (C, M, N), area ratio a (C,) and
    mean light-detector confidence c (C,)."""
    C, _, H, W = frames.shape
    M, Nb = H // bs, W // bs
    grid = detect(light, jnp.concatenate([frames[:, 0], frames[:, -1]]), dtype)
    b2, s2, v2 = decode(grid, CONF_THRESH)
    db = jnp.concatenate([b2[:C], b2[C:]], axis=1)
    ds = jnp.concatenate([s2[:C], s2[C:]], axis=1)
    dv = jnp.concatenate([v2[:C], v2[C:]], axis=1)
    conf = (jnp.sum(jnp.where(dv, ds, 0.0), axis=1)
            / jnp.maximum(jnp.sum(dv, axis=1), 1))
    D = jnp.any(jax.vmap(lambda f: motion_blocks(f, bs))(frames)
                > MOTION_THRESH, axis=1)

    def union(D_i, db_i, dv_i):
        mb, mv = components(D_i, MAX_BOXES)
        m = raster(mb, mv, M, Nb, 1.0) | raster(db_i, dv_i, M, Nb, 1.0 / bs)
        p = jnp.pad(m, 1)
        m = (p[1:-1, 1:-1] | p[:-2, 1:-1] | p[2:, 1:-1] | p[1:-1, :-2]
             | p[1:-1, 2:])
        return m, jnp.mean(m.astype(jnp.float32))

    mask, area = jax.vmap(union)(D, db, dv)
    return mask, area, conf


# -- codec -------------------------------------------------------------------

def pool_blur(x, k: int):
    """(N, H, W) k x k average pool (rows then columns, left to right) and
    nearest upsample back, edge-extended."""
    N, H, W = x.shape
    y = x[:, :H // k * k, :W // k * k]
    rows = y[:, 0::k]
    for i in range(1, k):
        rows = rows + y[:, i::k]
    out = rows[:, :, 0::k]
    for j in range(1, k):
        out = out + rows[:, :, j::k]
    up = jnp.kron(out / (k * k), jnp.ones((1, k, k), x.dtype))
    up = jnp.pad(up, ((0, 0), (0, max(H - up.shape[1], 0)),
                      (0, max(W - up.shape[2], 0))), mode="edge")
    return up[:, :H, :W]


def encode(cc: Dict, frames, roi_pixels, b, r, key, n_eff):
    """Rate-distortion codec for one camera's (N, H, W) segment: bits per
    effective pixel set the quantization step and the coding noise;
    resolution r < 1 blurs.  Returns (decoded frames, size in bytes)."""
    pix = roi_pixels * r * r * (1.0 + cc["temporal_rho"] * (n_eff - 1))
    bits = b * 1000.0 * cc["slot_seconds"]
    bpp = bits / jnp.maximum(pix, 1.0)
    res = cc["resolutions"]
    blurred = []
    for rr in res:
        k = 1 if rr >= 0.999 else 2 if rr > 0.6 else 4 if rr > 0.3 else 8
        blurred.append(frames if k == 1 else pool_blur(frames, k))
    x = jnp.stack(blurred)[jnp.argmin(jnp.abs(jnp.array(res) - r))]
    levels = jnp.clip(cc["quant_scale"] * bpp, 4.0, 256.0)
    x = jnp.round(x * levels) / levels
    sigma = cc["sigma0"] * jnp.exp(-bpp / cc["beta"])
    x = x + sigma * jax.random.normal(key, x.shape)
    return jnp.clip(x, 0.0, 1.0), bits / 8.0


# -- control -----------------------------------------------------------------

class Elastic(NamedTuple):
    a_ema: jax.Array
    a_var: jax.Array
    debt: jax.Array
    init: jax.Array


def elastic_init() -> Elastic:
    z = jnp.float32(0.0)
    return Elastic(z, z, z, jnp.asarray(False))


def elastic_step(ec: Dict, st: Elastic, area, W, tau_wl, tau_wh, reset):
    """Borrow when the ROI area is above its EMA + gamma_a sigma and the
    bandwidth under tau_wl (bounded by the budget); repay above tau_wh."""
    debt0 = jnp.where(reset, 0.0, st.debt)
    tau_a = st.a_ema + ec["gamma_a"] * jnp.sqrt(jnp.maximum(st.a_var, 1e-12))
    borrow = (area > tau_a) & (W < tau_wl)
    headroom = jnp.maximum(ec["budget_kbits"] - debt0, 0.0)
    borrowed = jnp.where(borrow, jnp.minimum(
        ec["gamma_wl"] * (tau_wl - W) * ec["slot_seconds"], headroom), 0.0)
    repay = (~borrow) & (W >= tau_wh) & (debt0 > 0.0)
    repaid = jnp.where(repay, jnp.minimum(
        debt0, (W - tau_wh) * ec["slot_seconds"]), 0.0)
    delta = area - st.a_ema
    a_ema = st.a_ema + ec["alpha"] * delta
    a_var = (1 - ec["alpha"]) * (st.a_var + ec["alpha"] * delta * delta)
    new = Elastic(jnp.where(st.init, a_ema, area),
                  jnp.where(st.init, a_var, 0.0),
                  jnp.where(st.init, debt0 + borrowed - repaid, 0.0),
                  jnp.asarray(True))
    extra = jnp.where(st.init, borrowed, 0.0) - jnp.where(st.init, repaid, 0.0)
    return new, extra


def utility_table(mlp, a, c, bitrates, resolutions):
    """(C, J) best-over-resolution predicted F1 and its resolution."""
    I, J, R = a.shape[0], len(bitrates), len(resolutions)
    aa = jnp.broadcast_to(a[:, None, None], (I, J, R)).reshape(-1)
    cc = jnp.broadcast_to(c[:, None, None], (I, J, R)).reshape(-1)
    bb = jnp.broadcast_to(jnp.asarray(bitrates, jnp.float32)[None, :, None],
                          (I, J, R)).reshape(-1)
    rr = jnp.broadcast_to(jnp.asarray(resolutions, jnp.float32)[None, None, :],
                          (I, J, R)).reshape(-1)
    x = jnp.stack([aa, cc, jnp.log(bb / 50.0) / 3.5, rr], axis=-1)
    h = jax.nn.relu(x @ mlp["w1"] + mlp["b1"])
    h = jax.nn.relu(h @ mlp["w2"] + mlp["b2"])
    u = jax.nn.sigmoid(h @ mlp["w3"] + mlp["b3"])[..., 0].reshape(I, J, R)
    best_res = jnp.asarray(resolutions, jnp.float32)[jnp.argmax(u, axis=-1)]
    return jnp.max(u, axis=-1), best_res


def knapsack(util, costs, Wg, w_cap: int):
    """Multiple-choice knapsack over the bitrate grid, then the backtrack
    from the best value at capacity <= Wg.  Returns per-camera picks."""
    I, J = util.shape
    w_idx = jnp.arange(w_cap + 1)

    def cam(v_prev, u_row):
        src = w_idx[:, None] - costs[None, :]
        cand = jnp.where(src >= 0, v_prev[jnp.clip(src, 0)] + u_row[None, :],
                         NEG)
        return jnp.max(cand, axis=1), jnp.argmax(cand, axis=1).astype(jnp.int32)

    vals, choices = jax.lax.scan(cam, jnp.zeros((w_cap + 1,), jnp.float32),
                                 util)
    w = jnp.argmax(jnp.where(w_idx <= Wg, vals, NEG)).astype(jnp.int32)
    picks = []
    for i in range(I - 1, -1, -1):
        j = choices[i, w]
        picks.append(j)
        w = jnp.maximum(w - costs[j], 0)
    return jnp.stack(picks[::-1])


def dp_capacity(cfg: Dict) -> int:
    """The DP's grid capacity: the pinned ceiling plus the minimum bitrate
    of every camera, bucketed to a multiple of 128 minus 1."""
    bitrates = cfg["codec"]["bitrates_kbps"]
    d = int(np.gcd.reduce(np.asarray(bitrates)))
    w = float(cfg["system"]["w_cap_kbps"]) + min(bitrates) * cfg["num_cameras"]
    return ((int(w // d) + 1 + 127) // 128) * 128 - 1


def allocate_dp(util, best_res, bitrates, W, live, w_cap: int):
    """Content-aware allocation: dead cameras take the cheapest option at no
    utility (and nothing is sent); W <= 0 sends nothing."""
    bitr = np.asarray(bitrates, np.int64)
    d = int(np.gcd.reduce(bitr))
    costs = (bitr // d).astype(np.int32)
    I, J = util.shape
    jmin = int(np.argmin(costs))
    cmin = int(costs[jmin])
    open_ = W > 0.0
    n_live = jnp.sum(live.astype(jnp.int32))
    util_eff = jnp.where(live[:, None], util,
                         jnp.where(jnp.arange(J)[None, :] == jmin, 0.0, -1e9))
    Wg = jnp.minimum(jnp.floor(W / d).astype(jnp.int32), w_cap)
    Wg_eff = jnp.minimum(Wg + (I - n_live) * cmin, w_cap)
    picks = knapsack(util_eff, jnp.asarray(costs), jnp.maximum(Wg_eff, cmin * I),
                     w_cap)
    tx = live & open_
    b = jnp.where(tx, jnp.asarray(bitr, jnp.float32)[picks], 0.0)
    r = jnp.where(tx, best_res[jnp.arange(I), picks], 1.0)
    return b, r


def allocate_fair(bitrates, W, live):
    """Equal split among live cameras: the largest bitrate <= W / live."""
    bitr = jnp.asarray(bitrates, jnp.float32)
    share = W / jnp.maximum(jnp.sum(live.astype(jnp.float32)), 1.0)
    ok = bitr <= share
    b = jnp.where(jnp.any(ok), jnp.max(jnp.where(ok, bitr, -jnp.inf)),
                  jnp.min(bitr))
    return jnp.where(live & (W > 0.0), b, 0.0)


# -- frame selection (reducto) -----------------------------------------------

def spread(count, F: int):
    """min(F, count) evenly spaced positions over count items, the last
    repeated: (C, F) positions and (C,) how many are real."""
    j = jnp.arange(F, dtype=jnp.int32)[None, :]
    count = jnp.maximum(count.astype(jnp.int32), 1)[:, None]
    f_eff = jnp.minimum(F, count)
    pos = (jnp.minimum(j, f_eff - 1) * (count - 1)) // jnp.maximum(f_eff - 1, 1)
    return pos, f_eff[:, 0]


def select(keep, F: int):
    """Kept frames scored (evenly spread), the last kept frame whose
    detections are reused, and the filtered frames they are scored on."""
    C, N = keep.shape
    kept = jnp.argsort(~keep, axis=1, stable=True)
    missed = jnp.argsort(keep, axis=1, stable=True)
    m = jnp.sum(keep, axis=1).astype(jnp.int32)
    n_miss = N - m
    j = jnp.arange(F, dtype=jnp.int32)[None, :]
    ev_p, f_eff = spread(m, F)
    eval_idx = jnp.take_along_axis(kept, ev_p, axis=1)
    eval_w = jnp.where(j < f_eff[:, None],
                       1.0 / jnp.maximum(f_eff[:, None], 1), 0.0)
    ms_p, fm_eff = spread(n_miss, F)
    miss_idx = jnp.take_along_axis(missed, ms_p, axis=1)
    miss_w = jnp.where((j < fm_eff[:, None]) & (n_miss[:, None] > 0),
                       1.0 / jnp.maximum(fm_eff[:, None], 1), 0.0)
    reuse_idx = jnp.take_along_axis(kept, jnp.maximum(m - 1, 0)[:, None],
                                    axis=1)[:, 0]
    return (m.astype(jnp.float32), eval_idx, eval_w.astype(jnp.float32),
            reuse_idx, miss_idx, miss_w.astype(jnp.float32),
            jnp.mean(keep.astype(jnp.float32), axis=1))


# -- one slot ----------------------------------------------------------------

class Weights(NamedTuple):
    light: Dict
    server: Dict
    mlp: Dict


def make_slot(cfg: Dict, detector_dtype):
    sc, cc, ec, sysc = cfg["scene"], cfg["codec"], cfg["elastic"], cfg["system"]
    method = cfg["method"]
    C = cfg["num_cameras"]
    H, W = sc["height"], sc["width"]
    N = int(sc["fps"] * sc["seg_seconds"])
    bs = sysc["block_size"]
    F = min(sysc["eval_frames"], N)
    G = gt_capacity(cfg)
    w_cap = dp_capacity(cfg)
    bitrates = tuple(cc["bitrates_kbps"])
    resolutions = tuple(cc["resolutions"])
    tau_wl = jnp.float32(cfg["control"]["tau_wl"])
    tau_wh = jnp.float32(cfg["control"]["tau_wh"])
    reducto = method == "reducto"
    if method not in ("deepstream", "reducto"):
        raise ValueError(f"the reference covers deepstream and reducto, "
                         f"not {method!r}")

    def slot(wts: Weights, scene, skey, ckey, carry, xs):
        est, ref, live_prev = carry
        t, W_t, live = xs
        frames, gtb, gtv = scene_slot(sc, scene, skey, t, G)
        kt = jax.random.fold_in(jax.random.fold_in(ckey, SALT), t)
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            kt, jnp.arange(C, dtype=jnp.int32))
        reconnect = live & ~live_prev
        zero = jnp.float32(0.0)
        if reducto:
            masks = jnp.ones((C, H // bs, W // bs), bool)
            area = extra = zero
            b = allocate_fair(bitrates, W_t, live)
            r = jnp.ones((C,), jnp.float32)
            first = (t == 0) | reconnect
            ref = jnp.where(first[:, None, None], frames[:, 0], ref)
            allf = jnp.concatenate([ref[:, None], frames], axis=1)
            moved = jnp.sum(jax.vmap(lambda f: motion_blocks(f, bs))(allf),
                            axis=(2, 3)) > KEEP_THRESH
            keep = moved.at[:, 0].set(moved[:, 0] | first
                                      | ~jnp.any(moved, axis=1))
            last = (N - 1) - jnp.argmax(jnp.flip(keep, axis=1), axis=1)
            ref = jnp.take_along_axis(frames, last[:, None, None, None],
                                      axis=1)[:, 0]
        else:
            masks, a, c = roidet(frames, wts.light, bs, detector_dtype)
            area = jnp.sum(jnp.where(live, a, 0.0))
            est, extra_kbits = elastic_step(ec, est, area, W_t, tau_wl,
                                            tau_wh, jnp.any(reconnect))
            extra = extra_kbits / cc["slot_seconds"]
            util, best_res = utility_table(wts.mlp, a, c, bitrates,
                                           resolutions)
            b, r = allocate_dp(util, best_res, bitrates,
                               jnp.maximum(W_t + extra, 0.0), live, w_cap)
            keep = jnp.ones((C, N), bool)
        n_eff, eval_idx, eval_w, reuse_idx, miss_idx, miss_w, w_keep = \
            select(keep, F)
        up = jnp.repeat(jnp.repeat(masks.astype(jnp.float32), bs, axis=1),
                        bs, axis=2)[:, None]
        fill = jnp.mean(frames, axis=(2, 3), keepdims=True)
        cropped = frames * up + fill * (1.0 - up)
        roi_pixels = (jnp.sum(masks, axis=(1, 2)) * bs * bs).astype(jnp.float32)
        decoded, sizes = jax.vmap(functools.partial(encode, cc))(
            cropped, roi_pixels, b, r, keys, n_eff)
        batch = jnp.take_along_axis(decoded, eval_idx[:, :, None, None],
                                    axis=1).reshape(C * F, H, W)
        if reducto:
            batch = jnp.concatenate([batch, jnp.take_along_axis(
                frames, reuse_idx[:, None, None, None], axis=1)[:, 0]])
        pb, _, pv = decode(detect(wts.server, batch, detector_dtype),
                           SERVER_CONF)
        gt_e = jnp.take_along_axis(gtb, eval_idx[:, :, None, None], axis=1)
        gv_e = jnp.take_along_axis(gtv, eval_idx[:, :, None], axis=1)
        f1f = f1_batch(pb[:C * F], pv[:C * F], gt_e.reshape(C * F, G, 4),
                       gv_e.reshape(C * F, G)).reshape(C, F)
        f1 = jnp.sum(f1f * eval_w, axis=1)
        if reducto:
            gt_m = jnp.take_along_axis(gtb, miss_idx[:, :, None, None], axis=1)
            gv_m = jnp.take_along_axis(gtv, miss_idx[:, :, None], axis=1)
            f1m = f1_batch(jnp.repeat(pb[C * F:], F, axis=0),
                           jnp.repeat(pv[C * F:], F, axis=0),
                           gt_m.reshape(C * F, G, 4),
                           gv_m.reshape(C * F, G)).reshape(C, F)
            f1 = f1 * w_keep + jnp.sum(f1m * miss_w, axis=1) * (1.0 - w_keep)
        tx = live & (b > 0.0)
        row = (jnp.where(tx, f1, 0.0), jnp.where(tx, sizes, 0.0),
               jnp.stack([extra, area, jnp.sum(b)]))
        return (est, ref, live), row

    return slot


_COMPILED: Dict[Tuple[str, str], object] = {}


def _chunk_fn(cfg: Dict, detector_dtype: str):
    key = (json.dumps(cfg, sort_keys=True), detector_dtype)
    if key in _COMPILED:
        return _COMPILED[key]
    slot = make_slot(cfg, jnp.dtype(detector_dtype))

    @jax.jit
    def run_chunk(wts, scene, skey, ckey, carry, ts, Ws, lives, active):
        def step(carry, xs):
            t, W_t, live, act = xs
            new, row = slot(wts, scene, skey, ckey, carry, (t, W_t, live))
            new = jax.tree.map(lambda n, o: jnp.where(act, n, o), new, carry)
            return new, row
        return jax.lax.scan(step, carry, (ts, Ws, lives, active))

    _COMPILED[key] = run_chunk
    return run_chunk


def run(cfg: Dict, wts: Weights, seed: int, trace: np.ndarray,
        live: np.ndarray, detector_dtype: str = "float32"
        ) -> Dict[str, np.ndarray]:
    """Log rows of slots 0 .. len(trace)-1 served from a fresh start."""
    fn = _chunk_fn(cfg, detector_dtype)
    C = cfg["num_cameras"]
    sc = cfg["scene"]
    scene = {k: jnp.asarray(v) for k, v in init_scene(cfg, seed).items()}
    skey = jax.random.PRNGKey(seed)
    ckey = jax.random.PRNGKey(cfg["system"]["codec_key"])
    carry = (elastic_init(),
             jnp.zeros((C, sc["height"], sc["width"]), jnp.float32),
             jnp.ones((C,), bool))
    T = len(trace)
    f1s, sizes, packs = [], [], []
    for t0 in range(0, T, CHUNK):
        n = min(CHUNK, T - t0)
        pad = CHUNK - n
        ts = jnp.arange(t0, t0 + CHUNK, dtype=jnp.int32)
        Ws = jnp.asarray(np.pad(np.asarray(trace[t0:t0 + n], np.float32),
                                (0, pad)))
        lv = jnp.asarray(np.pad(np.asarray(live[t0:t0 + n], bool),
                                ((0, pad), (0, 0)), constant_values=True))
        act = jnp.arange(CHUNK) < n
        carry, (f1, sz, pk) = fn(wts, scene, skey, ckey, carry, ts, Ws, lv,
                                 act)
        f1s.append(np.asarray(f1)[:n])
        sizes.append(np.asarray(sz)[:n])
        packs.append(np.asarray(pk)[:n])
    # the served log's own arithmetic: float32 rows, summed on the host
    f1 = np.concatenate(f1s)
    sz = np.concatenate(sizes)
    pk = np.concatenate(packs).astype(np.float64)
    return {"utility": f1 @ np.ones(C), "mean_f1": f1.mean(axis=1),
            "bytes": sz.sum(axis=1), "W": np.asarray(trace, np.float64),
            "extra": pk[:, 0], "area": pk[:, 1], "alloc_kbps": pk[:, 2]}
