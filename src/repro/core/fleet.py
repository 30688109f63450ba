"""Sharded, sync-free fleet slot-step: ONE executable for every method.

The sequential control loop pays C x (encode jit call + block_until_ready +
eager decode_boxes + per-frame jnp F1) host round-trips per slot.  This module
compiles the whole server-side slot step into ONE program over the camera
axis, shared by all four scheduler methods:

  * ``fleet_slot_step`` — vmaps ROI-masked encoding (``crop_to_mask`` +
    ``codec.encode_segment``) over cameras with traced per-camera (b_i, r_i),
    a split key batch and per-camera effective frame counts, gathers the eval
    frames PLUS one raw "reuse" frame per camera, runs the server detector on
    the flat (C*F + C, H, W) batch, scores padded ground truth with the
    traced greedy F1 (``detector.f1_score_batch``), and mixes in the
    detection-reuse arm with traced per-camera weights.  One dispatch; the
    only host fetch a slot needs is the packed (2, C) ``host_pack``
    (final F1s + sizes) — a single D2H transfer.
  * ``pad_gt`` — host-side helper packing ragged per-frame GT box lists into
    padded (C, F, G, 4)/(C, F, G) arrays with a FIXED per-scene capacity G
    (``gt_capacity``), so the jit signature never changes mid-run.

Method routing is pure data, no Python branches in the hot loop:

  * deepstream / deepstream_no_elastic — ROI masks from ROIDet, w_keep = 1
    (reuse arm weighted to zero);
  * jcab / static — all-ones mask == 'no cropping' (identity crop, exact
    H*W pixel count), w_keep = 1;
  * reducto — all-ones mask, per-camera traced kept-frame count ``n_eff``,
    eval indices over kept frames, and the reuse arm live: the detections of
    the last kept frame (part of the same detector batch) score the
    filtered-out frames' GT, mixed as w_keep*F1_kept + (1-w_keep)*F1_reuse.

Device-resident control loop
----------------------------
The server-side control loop (paper sections 5.2 + 5.3 — elastic adjustment,
utility table, knapsack allocation) is ONE traced step per method
(``_control_impl``) that the episode scan body calls every slot: slot t's
per-camera (b, r) assignment is computed on device from the fleet ROIDet's
(a, c) feature vectors, the window's bandwidth trace and an
``ElasticStateJax`` of device scalars threaded slot to slot.  What runs on
device: the elastic EMA/variance/debt update, the fused utility-MLP table,
the knapsack sweep at ONE static bucketed capacity
(``allocation.dp_capacity``) with a traced backtrack, the traced
fair/static pick, and the (extra, area, alloc_kbps, feasible) log pack.
Reducto's keep-flag decision is traced too (``reducto_keep_step``: motion
-> cross-slot-reference keep mask, consumed by ``keep_selection`` INSIDE
the slot-step).  The independent reference for this control is the host
numpy path of ``scheduler._slot_allocation``.

Whole-trace episodes
--------------------
``fleet_episode`` closes the remaining host round-trips: a FULL N-slot run
executes as ONE compiled program per method — ``lax.scan`` over the trace
of segment generation (``data.synthetic.segments_device``, a traced seeded
generator: slot t's frames + padded GT are a pure function of (scene
params, base key, t) via ``jax.random.fold_in``), fleet ROIDet, the control
step (``_control_impl``), the traced reducto keep decision and the
unified slot-step.  Carry:
the codec PRNG key chain + ``ElasticStateJax`` + reducto's cross-slot
reference frames.  Per-slot logs are STACKED on device — (T, 2, C) F1/size
packs and (T, 4) control packs — and harvested with one fetch at episode
end, so "what the host still does" shrinks to: build the trace/context
once, dispatch once, fetch once.  The timed episode runs under
``jax.transfer_guard("disallow")`` in BOTH directions with NO scoped
exemptions: zero per-slot H2D uploads and zero per-slot D2H fetches of any
category, by construction.  Under a camera mesh the scan body is
shard_map'd whole: per-camera stages run on camera shards, the control
stage ``all_gather``s (a, c) and runs replicated with the pure-jnp DP (one
redundant small sweep per device instead of N interpret-mode kernel
emulations), and each device slices its cameras' (b, r) back out.  The
host-control batched ``run()`` is kept as the ``episode=False`` reference;
over the same ``DeviceScene`` seeds both modes produce the same logs (the
equivalence tests assert <= 1e-5).

Trace lengths are BUCKETED: T is part of the scan's shape, so
``fleet_episode`` pads every trace up to a power-of-two bucket
(``EPISODE_BUCKETS``) and one executable per (method, bucket) serves any
T — a mixed-length suite stops re-tracing the fleet per trace length.
``bucket_len`` documents the padded-slot contract (a masked tail slot runs
the per-slot program on dead inputs but cannot advance the key chain, the
elastic state, the logs, or the DP capacity, which derives from the active
prefix via ``allocation.trace_capacity``).

Fault tolerance: the liveness-mask contract
-------------------------------------------
Camera churn / link faults are DATA, not shape: every fleet entry point
accepts a per-slot boolean **liveness mask** (``live`` (C,) per slot,
``faults`` (T, C) per episode, default all-True) that rides through the
traced programs exactly like reducto's keep-flags — one executable
signature serves faulty and fault-free runs, zero recompiles, zero extra
transfers.  A dead (camera, slot) reuses the inert-camera contract the
mesh padding already defines: it still COMPUTES (dead flops keep the
program shape static) but cannot contribute — its F1/size/log entries are
masked to zero in the slot-step, the allocators exclude it (it holds no
bitrate; see ``allocation`` — the knapsack runs on a forced-row transform,
fair shares split among live cameras only), the elastic controller's area
signal drops it, and its logs read zero bytes / zero F1.  On RECONNECT a
camera rejoins as if fresh: reducto's cross-slot reference re-seeds from
its first frame (the per-camera ``first`` flag ORs the reconnect edge) and
the elastic debt clamp (``elastic.update*(reset_debt=...)``) bars it from
claiming bandwidth borrowed against a fleet it wasn't part of.  Codec keys
are a pure per-(slot, camera) function (``slot_camera_keys``), NOT a
fleet-size-dependent chain, so a camera dead for the whole trace is
log-equivalent (<= 1e-5) to a fleet that never had it — the headline
differential guarantee (tests/test_faults.py), across all methods and all
runner modes.  Slot 0's camera (or any one camera) must stay live per slot:
the control step needs >= 1 live camera.

``checked=True`` (diagnostics lane, off by default) threads
``jax.experimental.checkify`` user checks through the slot-step and the
episode scan, control step included — finite logs, allocation <=
capacity, keep-mask and liveness consistency, elastic debt in [0,
budget] — and surfaces them via
``checkify.check``/``err.throw()`` AFTER the transfer-guarded region.
Unchecked programs contain no checkify code at all (the flag is a trace
static), so the default lane's overhead is structurally zero.

Mesh & donation
---------------
The camera axis is the leading axis of every per-camera operand, and the
executable is built per (mesh, codec-config, statics) via
``jax.shard_map`` on a 1-D ("camera",) mesh (``sharding.rules.camera_mesh``):
each device runs the identical per-camera program on its C/D-camera shard, so
results are bit-stable vs the single-device path and multi-host scaling is a
mesh-shape change.  C is padded up to a multiple of the device count
(``sharding.rules.pad_cameras``) with inert cameras and sliced back off.
The big per-slot buffers (frames, GT) are donated (``donate_argnums``,
pinned by ``analysis.programs.SLOT_STEP_DONATE_ARGNUMS``), so slot t's
inputs are recycled into slot t+1's workspace instead of accumulating;
callers keep results on device and fetch only ``host_pack``.  On CPU, validate with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import functools
import warnings
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import checkify
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common import obs
from repro.core import allocation as alloc_mod
from repro.core import codec as codec_mod
from repro.core import elastic as elastic_mod
from repro.core import roidet as roidet_mod
from repro.core import utility as util_mod
from repro.core.codec import CodecConfig
from repro.core.elastic import ElasticConfig, ElasticStateJax
from repro.data import synthetic as synth_mod
from repro.data.synthetic import DeviceSceneParams, SceneConfig
from repro.kernels.edge_motion import ops as em_ops
from repro.models import detector as det
from repro.sharding.rules import (cached_sharded_jit, mesh_cache_key,
                                  pad_cameras, pad_leading, sharded_jit)

# block-motion mass above which a frame counts as "changed" (reducto keep
# rule) — shared by the sequential, batched and episode paths, which must
# stay bit-in-sync for the cross-mode equivalence guarantees
MOTION_KEEP_THRESH = 25.0

# default trace-length buckets for the episode runner: T is part of the
# episode scan's shape, so every distinct trace length used to re-trace the
# whole fleet program.  ``fleet_episode`` pads T up to the smallest bucket
# (doubling past the largest) and masks the padding — one executable per
# (method, bucket) serves every T.  See ``bucket_len`` for the padded-slot
# semantics contract.
EPISODE_BUCKETS: Tuple[int, ...] = (8, 16, 32)


def bucket_len(T: int, buckets: Optional[Sequence[int]] = EPISODE_BUCKETS
               ) -> int:
    """Padded trace length for a T-slot episode: the smallest bucket >= T,
    doubling the largest bucket until it covers T, or T itself when
    bucketing is disabled (``buckets`` falsy).

    Padded-slot contract (what a masked slot is and is not allowed to do):
    a padded slot RUNS the full per-slot program — segment synthesis,
    ROIDet, control, slot-step — on slot indices past the active prefix
    (pure wasted flops, bounded by the bucket granularity), but it cannot
    advance any OBSERVABLE episode state: the returned codec PRNG key and
    elastic state are read from the last *active* slot's stacked carry, its
    log rows are sliced off before the harvest, and the reducto reference
    it perturbs is dead state (padding sits at the END of the scan, after
    every active slot, and the cross-slot reference resets per run).  The
    DP capacity is likewise computed from the active prefix of the trace
    (``allocation.trace_capacity`` runs before padding), so bucketing can
    never change a pick."""
    T = int(T)
    if not buckets:
        return T
    bs = sorted(int(b) for b in buckets)
    if bs[0] < 1:
        raise ValueError(f"episode buckets must be >= 1: {buckets!r}")
    for b in bs:
        if T <= b:
            return b
    b = bs[-1]
    while b < T:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnames=("n",))
def _key_chain(key: jax.Array, n: int) -> Tuple[jax.Array, jax.Array]:
    """n sequential key splits in ONE dispatch.  Bit-identical to repeatedly
    calling ``key, k = jax.random.split(key)`` on the host, so the fleet
    paths (batched loop AND episode scan) draw exactly the keys the
    per-camera loop would."""
    def step(k, _):
        k, sub = jax.random.split(k)
        return k, sub
    return jax.lax.scan(step, key, None, length=n)


# domain-separation salt for the codec key stream: the scene generator folds
# (key, t, cam) too, so without a salt a run whose codec base key equals the
# scene key would reuse the scene's noise samples as coding noise
CODEC_KEY_SALT = 0x0DEC


@jax.jit
def slot_camera_keys(key0: jax.Array, t, cam_ids) -> jax.Array:
    """Per-(slot, camera) codec keys as a PURE function of the run key:
    ``fold_in(fold_in(fold_in(key0, salt), t), cam_id)`` — no sequential
    chain.  This is the property the fault contract rests on: camera i's
    coding noise does not depend on which other cameras exist, live, or
    die, so a fleet that never had camera j draws bit-identical samples
    for the others as a fleet where j is dead (the dead-camera ==
    absent-camera differential guarantee).  ``t`` is the GLOBAL scene slot
    index (the cursor ``segment()`` stamps / the episode's ``t_idx``), so
    resumed runs continue the same stream.  Every execution mode draws
    through this one function."""
    kt = jax.random.fold_in(jax.random.fold_in(key0, CODEC_KEY_SALT),
                            jnp.asarray(t, jnp.int32))
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        kt, jnp.asarray(cam_ids, jnp.int32))


class FleetSlotOut(NamedTuple):
    f1: jax.Array          # (C,) final per-camera F1 (reuse-arm mixed)
    f1_frames: jax.Array   # (C, F) per-eval-frame F1 on kept frames
    sizes: jax.Array       # (C,) encoded bytes
    host_pack: jax.Array   # (2, C) [f1; sizes] — the ONE per-slot D2H fetch
    boxes: jax.Array       # (C, F, K, 4) server detections (eval frames)
    scores: jax.Array      # (C, F, K)
    valid: jax.Array       # (C, F, K)


class KeepSelection(NamedTuple):
    """Traced kept/missed eval-frame selection derived from a keep mask —
    the fixed-shape device equivalent of the host-side index building the
    pre-episode loop did in ``scheduler._reducto_fleet_inputs``."""
    n_eff: jax.Array     # (C,) float32 kept-frame counts (codec charge)
    eval_idx: jax.Array  # (C, F) int32 kept frames scored for F1
    eval_w: jax.Array    # (C, F) float32 per-frame weights (rows sum to 1)
    reuse_idx: jax.Array # (C,) int32 last kept frame (the reuse detection)
    miss_idx: jax.Array  # (C, F) int32 filtered-out frames the reuse scores
    miss_w: jax.Array    # (C, F) float32 (all-zero rows = arm inert)
    w_keep: jax.Array    # (C,) float32 arm mix (1 = reuse arm off)


def _linspace_sel(count: jax.Array, F: int) -> Tuple[jax.Array, jax.Array]:
    """Traced ``eval_indices``: min(F, count) evenly spaced positions over a
    length-``count`` list, padded by repeating the last pick.  Integer math
    — exhaustively verified equal to the host
    ``np.linspace(0, n-1, f).astype(int)`` truncation for every n <= 128,
    f <= 10 (``keep_selection`` asserts that envelope: np.linspace's float64
    rounding can truncate an exact integer grid point one lower, first at
    n=123/f=15, where the integer form is the mathematically exact one).
    Returns (positions (C, F) int32, f_eff (C,) int32)."""
    j = jnp.arange(F, dtype=jnp.int32)[None, :]
    count = jnp.maximum(count.astype(jnp.int32), 1)[:, None]     # (C, 1)
    f_eff = jnp.minimum(F, count)
    jj = jnp.minimum(j, f_eff - 1)
    pos = (jj * (count - 1)) // jnp.maximum(f_eff - 1, 1)
    return pos, f_eff[:, 0]


def keep_selection(keep: jax.Array, F: int) -> KeepSelection:
    """keep (C, N) bool (>= 1 True per row) -> every selection the slot step
    needs, computed on device with masked fixed-shape gathers.  For an
    all-True row (every non-reducto method) this degenerates exactly to the
    static ``eval_indices(N, F)`` spread with uniform weights, reuse frame =
    last raw frame, zero miss weights and w_keep = 1 — method routing stays
    pure data, ONE executable serves all four methods."""
    C, N = keep.shape
    # the host-equivalence envelope _linspace_sel is verified for
    assert N <= 128 and F <= 10, (N, F)
    kept_pos = jnp.argsort(~keep, axis=1, stable=True)   # kept first, ascending
    miss_pos = jnp.argsort(keep, axis=1, stable=True)    # missed first
    m = jnp.sum(keep, axis=1).astype(jnp.int32)
    n_miss = N - m
    ev_p, f_eff = _linspace_sel(m, F)
    eval_idx = jnp.take_along_axis(kept_pos, ev_p, axis=1).astype(jnp.int32)
    j = jnp.arange(F, dtype=jnp.int32)[None, :]
    eval_w = jnp.where(j < f_eff[:, None],
                       1.0 / jnp.maximum(f_eff[:, None], 1), 0.0
                       ).astype(jnp.float32)
    ms_p, fm_eff = _linspace_sel(n_miss, F)
    miss_idx = jnp.take_along_axis(miss_pos, ms_p, axis=1).astype(jnp.int32)
    miss_w = jnp.where((j < fm_eff[:, None]) & (n_miss[:, None] > 0),
                       1.0 / jnp.maximum(fm_eff[:, None], 1), 0.0
                       ).astype(jnp.float32)
    reuse_idx = jnp.take_along_axis(kept_pos, jnp.maximum(m - 1, 0)[:, None],
                                    axis=1)[:, 0].astype(jnp.int32)
    return KeepSelection(
        n_eff=m.astype(jnp.float32), eval_idx=eval_idx, eval_w=eval_w,
        reuse_idx=reuse_idx, miss_idx=miss_idx, miss_w=miss_w,
        w_keep=jnp.mean(keep.astype(jnp.float32), axis=1))


class SlotStaged(NamedTuple):
    """Encode-stage handoff of the SPLIT slot step (``_slot_encode`` ->
    ``_slot_finish``): everything slot t's detector dispatch + scoring needs,
    with no reference back to the raw frames/GT — the software-pipelined
    episode scan carries ONE of these across an iteration boundary so slot
    t's detector stage overlaps slot t+1's encode stage.  ``gt_m``/``gv_m``
    are None when the reuse arm is compiled out (``with_reuse=False``)."""
    batch: jax.Array            # (C*F [+ C], H, W) detector input
    gt_e: jax.Array             # (C, F, G, 4) eval-frame ground truth
    gv_e: jax.Array             # (C, F, G)
    gt_m: Optional[jax.Array]   # (C, F, G, 4) missed-frame GT (reuse arm)
    gv_m: Optional[jax.Array]   # (C, F, G)
    eval_w: jax.Array           # (C, F) per-eval-frame weights
    miss_w: jax.Array           # (C, F) reuse-arm weights
    w_keep: jax.Array           # (C,) arm mix
    sizes: jax.Array            # (C,) encoded bytes (pre tx-mask)
    tx: jax.Array               # (C,) bool transmit mask (live & b > 0)


@jax.named_scope("encode")
def _slot_encode(cfg: CodecConfig, frames: jax.Array, masks: jax.Array,
                 b: jax.Array, r: jax.Array, keys: jax.Array,
                 keep: jax.Array, gt_boxes: jax.Array, gt_valid: jax.Array,
                 live: jax.Array, *, eval_frames: int, block_size: int,
                 with_reuse: bool, use_kernel: bool) -> SlotStaged:
    """Stage A of the split slot step: crop -> fleet encode -> eval-frame
    gather -> detector-batch build (+ the reuse row and GT gathers).  Pure
    per-camera work with NO detector dependency, so the pipelined episode
    scan can run it for slot t+1 while slot t's ``_slot_finish`` is still in
    flight.  ``use_kernel`` routes the codec transform through the fused
    pallas transmission kernel (``kernels.tx_codec``); False is the vmapped
    per-camera ``codec.encode_segment`` oracle — the two agree to float32
    ulp (see the kernel package docstring)."""
    C, N, H, W = frames.shape
    F = min(eval_frames, N)
    sel = keep_selection(keep, F)

    cropped = jax.vmap(
        lambda fr, mk: roidet_mod.crop_to_mask(fr, mk, block_size)
    )(frames, masks)
    roi_pixels = (jnp.sum(masks, axis=(1, 2))
                  * (block_size ** 2)).astype(jnp.float32)
    decoded, sizes = codec_mod.encode_fleet_segment(
        cfg, cropped, roi_pixels, b, r, keys, sel.n_eff,
        use_kernel=use_kernel)
    ev = jnp.take_along_axis(decoded, sel.eval_idx[:, :, None, None], axis=1)
    batch = ev.reshape(C * F, H, W)
    gt_e = jnp.take_along_axis(gt_boxes, sel.eval_idx[:, :, None, None],
                               axis=1)
    gv_e = jnp.take_along_axis(gt_valid, sel.eval_idx[:, :, None], axis=1)
    gt_m = gv_m = None
    if with_reuse:
        # reuse frames are RAW camera frames (the camera ran its own detector
        # on them before filtering) — folded into the same server forward
        reuse_fr = jnp.take_along_axis(
            frames, sel.reuse_idx[:, None, None, None], axis=1)[:, 0]
        batch = jnp.concatenate([batch, reuse_fr], axis=0)
        gt_m = jnp.take_along_axis(gt_boxes, sel.miss_idx[:, :, None, None],
                                   axis=1)
        gv_m = jnp.take_along_axis(gt_valid, sel.miss_idx[:, :, None], axis=1)
    # the transmit mask: dead cameras and zero-allocation slots (a hard
    # outage leaves every camera at b == 0) send nothing — zero bytes, zero
    # F1 — while their dead compute keeps the program shape static
    tx = jnp.asarray(live, bool) & (b > 0.0)
    return SlotStaged(batch=batch, gt_e=gt_e, gv_e=gv_e, gt_m=gt_m,
                      gv_m=gv_m, eval_w=sel.eval_w, miss_w=sel.miss_w,
                      w_keep=sel.w_keep, sizes=sizes, tx=tx)


@jax.named_scope("detect")
def _slot_finish(server_params: Any, st: SlotStaged, *, conf_thresh: float,
                 with_reuse: bool) -> FleetSlotOut:
    """Stage B of the split slot step: the server detector forward on the
    staged batch, box decode, greedy-F1 scoring of both arms and the
    tx-masked log pack — the slot's dominant dispatch, consuming ONLY a
    ``SlotStaged`` so it can trail the encode stage by one scan iteration."""
    C, F, G = st.gt_e.shape[:3]
    grid = det.forward(server_params, st.batch)
    boxes, scores, valid = det.decode_boxes(grid, conf_thresh=conf_thresh)
    K = boxes.shape[1]
    f1_frames = det.f1_score_batch(
        boxes[:C * F], valid[:C * F], st.gt_e.reshape(C * F, G, 4),
        st.gv_e.reshape(C * F, G)).reshape(C, F)
    f1 = jnp.sum(f1_frames * st.eval_w, axis=1)
    if with_reuse:
        # detection-reuse arm: the reuse frame's detections score every
        # filtered-out frame's GT; miss_w rows are zero when the arm is off
        rb = jnp.repeat(boxes[C * F:], F, axis=0)
        rv = jnp.repeat(valid[C * F:], F, axis=0)
        f1_miss = det.f1_score_batch(
            rb, rv, st.gt_m.reshape(C * F, G, 4),
            st.gv_m.reshape(C * F, G)).reshape(C, F)
        f1 = (f1 * st.w_keep
              + jnp.sum(f1_miss * st.miss_w, axis=1) * (1.0 - st.w_keep))
    f1 = jnp.where(st.tx, f1, 0.0)
    f1_frames = jnp.where(st.tx[:, None], f1_frames, 0.0)
    sizes = jnp.where(st.tx, st.sizes, 0.0)
    return FleetSlotOut(
        f1=f1, f1_frames=f1_frames, sizes=sizes,
        host_pack=jnp.stack([f1, sizes]),
        boxes=boxes[:C * F].reshape(C, F, K, 4),
        scores=scores[:C * F].reshape(C, F, K),
        valid=valid[:C * F].reshape(C, F, K))


def _slot_step(cfg: CodecConfig, server_params: Any, frames: jax.Array,
               masks: jax.Array, b: jax.Array, r: jax.Array, keys: jax.Array,
               keep: jax.Array, gt_boxes: jax.Array, gt_valid: jax.Array,
               live: jax.Array, *, eval_frames: int, block_size: int,
               conf_thresh: float, with_reuse: bool, use_kernel: bool = False,
               checked: bool = False) -> FleetSlotOut:
    """The traced slot step for C cameras (C local under shard_map) —
    ``_slot_encode`` composed with ``_slot_finish`` back to back (the fused
    reference shape; the pipelined episode scan runs the two stages one slot
    apart instead).

    frames (C,N,H,W); masks (C,H/bs,W/bs) bool; b, r (C,) traced; keys
    (C,2); keep (C,N) bool frame keep-flags (all-True for every non-reducto
    method); gt_boxes (C,N,G,4) / gt_valid (C,N,G) padded ground truth for
    ALL N frames — which frames get scored is decided ON DEVICE by
    ``keep_selection`` (kept-frame eval spread, filtered-frame reuse scoring,
    per-camera arm weights), so no host-built index array ever enters the
    program.  ``live`` (C,) bool is the slot's camera liveness mask (see the
    module docstring's fault contract): a dead or unallocated (b == 0)
    camera still computes — dead flops, same program shape — but its
    F1/size/host_pack entries are masked to zero, so it contributes nothing
    observable.  ``with_reuse=False`` (profiling) drops the reuse arm from
    the program entirely — the profiling sweep's batch shape is its own
    specialization anyway, so it skips the arm's dead detector/F1 work;
    ``run()`` always compiles with the arm so all four methods share one
    executable.  ``use_kernel`` routes the codec transform through the
    fused pallas transmission kernel (float32-ulp parity with the vmapped
    scalar path).  ``checked`` inserts checkify invariants (trace static:
    the default program carries no checkify code) and forces the kernel off
    (the oracle path is the diagnostics reference).
    """
    st = _slot_encode(cfg, frames, masks, b, r, keys, keep, gt_boxes,
                      gt_valid, live, eval_frames=eval_frames,
                      block_size=block_size, with_reuse=with_reuse,
                      use_kernel=use_kernel and not checked)
    out = _slot_finish(server_params, st, conf_thresh=conf_thresh,
                       with_reuse=with_reuse)
    f1, f1_frames, sizes, tx = out.f1, out.f1_frames, out.sizes, st.tx
    if checked:
        checkify.check(jnp.all(jnp.isfinite(f1)) & jnp.all(jnp.isfinite(sizes)),
                       "slot-step: non-finite F1 or size")
        checkify.check(jnp.all((f1 >= -1e-3) & (f1 <= 1.0 + 1e-3)),
                       "slot-step: F1 outside [0, 1]")
        checkify.check(jnp.all(sizes >= 0.0), "slot-step: negative size")
        checkify.check(jnp.all(jnp.any(keep, axis=1)),
                       "slot-step: keep mask row with no kept frame")
        checkify.check(jnp.all(jnp.where(tx[:, None], True, f1_frames == 0.0)),
                       "slot-step: non-transmitting camera produced F1")
    return out


# -- traced reducto keep-flags ------------------------------------------------

@jax.named_scope("keep")
def _reducto_keep_impl(frames: jax.Array, ref: jax.Array, first: jax.Array, *,
                       block_size: int, edge_thresh: float,
                       use_kernel: bool) -> Tuple[jax.Array, jax.Array]:
    """Traced reducto keep decision with a CROSS-SLOT reference: frame 0's
    motion score is computed against the last kept frame of the previous
    slot (the frame whose detections the camera reuses — real Reducto
    filters against the last transmitted frame, it does not reset per
    segment), frames 1..N-1 against their predecessor.  Forced-keep rules:
    the first slot of a run keeps frame 0 (no reference exists yet), and an
    all-quiet slot keeps frame 0 so every slot transmits >= 1 frame.
    ``first`` is PER-CAMERA ((C,) bool, scalar broadcasts): besides the
    run's first slot it marks reconnect edges — a camera rejoining after a
    fault has no valid cross-slot reference, so it re-seeds from its own
    frame 0 exactly like a fresh run (the fault contract's "rejoin as
    fresh" rule).  Returns (keep (C, N) bool, new reference frames
    (C, H, W)); everything stays on device — the pre-episode per-slot
    'keep' D2H fetch is gone."""
    N = frames.shape[1]
    first = jnp.broadcast_to(jnp.asarray(first, bool), (frames.shape[0],))
    ref = jnp.where(first[:, None, None], frames[:, 0], ref)
    allf = jnp.concatenate([ref[:, None], frames], axis=1)   # (C, N+1, H, W)
    sc = em_ops._segment_motion_fleet_impl(
        allf, block_size=block_size, edge_thresh=edge_thresh, tile_rows=None,
        use_kernel=use_kernel)                               # (C, N, M, Nb)
    raw = jnp.sum(sc, axis=(2, 3)) > MOTION_KEEP_THRESH
    keep = raw.at[:, 0].set(raw[:, 0] | first | ~jnp.any(raw, axis=1))
    last = (N - 1) - jnp.argmax(jnp.flip(keep, axis=1), axis=1)
    new_ref = jnp.take_along_axis(frames, last[:, None, None, None],
                                  axis=1)[:, 0]
    return keep, new_ref


def reducto_keep_step(frames: jax.Array, ref: jax.Array, first, *,
                      block_size: int,
                      edge_thresh: float = roidet_mod.EDGE_THRESH,
                      use_kernel: bool = True, mesh: Optional[Mesh] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Dispatch the traced keep decision (camera-sharded when a mesh is
    given) WITHOUT blocking: (keep, new ref) come back as device arrays that
    feed ``fleet_slot_step`` / the next slot's keep step directly.
    ``first`` may be a scalar (whole-fleet run start) or a (C,) per-camera
    vector (run start OR reconnect edges, see ``_reducto_keep_impl``)."""
    cam = P("camera")
    fn = cached_sharded_jit(
        _reducto_keep_impl,
        dict(block_size=block_size, edge_thresh=edge_thresh,
             use_kernel=use_kernel),
        mesh, in_specs=(cam, cam, cam), out_specs=(cam, cam))
    C = frames.shape[0]
    C_pad = pad_cameras(C, mesh)
    first = jnp.broadcast_to(jnp.asarray(first, bool), (C,))
    keep, new_ref = fn(pad_leading(frames, C_pad), pad_leading(ref, C_pad),
                       pad_leading(first, C_pad, fill=False))
    if C_pad != C:
        keep, new_ref = keep[:C], new_ref[:C]
    return keep, new_ref


# -- executable cache: one compiled program per (mesh, config, statics) -------

_EXEC_CACHE: Dict[Tuple, Any] = {}
_COMPILE_COUNTS: Dict[Tuple, int] = {}


def _build_executable(cache_key: Tuple, mesh: Optional[Mesh],
                      cfg: CodecConfig, eval_frames: int, block_size: int,
                      conf_thresh: float, with_reuse: bool,
                      use_kernel: bool, checked: bool):
    impl = functools.partial(_slot_step, cfg, eval_frames=eval_frames,
                             block_size=block_size, conf_thresh=conf_thresh,
                             with_reuse=with_reuse, use_kernel=use_kernel,
                             checked=checked)

    def counted(*args):
        # this Python side effect runs exactly once per new jit
        # specialization (trace time) — a version-stable compile-count hook
        _COMPILE_COUNTS[cache_key] = _COMPILE_COUNTS.get(cache_key, 0) + 1
        return impl(*args)

    if checked:
        # the diagnostics lane: checkify functionalization composes with a
        # plain jit — no mesh, no donation (the error value aliases nothing)
        assert mesh is None, "checked mode runs unsharded (SystemConfig "\
                             "forces shard='off')"
        return jax.jit(checkify.checkify(counted))
    cam = P("camera")
    in_specs = (P(),) + (cam,) * 9
    out_specs = FleetSlotOut(cam, cam, cam, P(None, "camera"), cam, cam, cam)
    # donate the big per-slot buffers: frames(1), gt(7,8) — positions in the
    # (server_params, frames, masks, b, r, keys, keep, gt_boxes, gt_valid,
    # live) argument list.  masks stay undonated: callers hold the ROIDet
    # mask for the sequential-equivalence comparisons.
    return sharded_jit(counted, mesh, in_specs, out_specs, (1, 7, 8))


def _get_executable(mesh: Optional[Mesh], cfg: CodecConfig, eval_frames: int,
                    block_size: int, conf_thresh: float, with_reuse: bool,
                    use_kernel: bool, checked: bool):
    key = (mesh_cache_key(mesh), cfg, eval_frames, block_size, conf_thresh,
           with_reuse, use_kernel, checked)
    fn = _EXEC_CACHE.get(key)
    if fn is None:
        fn = _EXEC_CACHE[key] = _build_executable(
            key, mesh, cfg, eval_frames, block_size, conf_thresh,
            with_reuse, use_kernel, checked)
    return fn


def compile_count() -> int:
    """Total traced specializations of the fleet slot-step across every
    (mesh, config) executable — the bench's recompile detector: a 10-slot
    ``run()`` must raise this by at most one per (method, config)."""
    return sum(_COMPILE_COUNTS.values())


# -- device-resident control loop (elastic + allocation) ----------------------

class ControlOut(NamedTuple):
    b: jax.Array           # (C,) assigned bitrates (Kbps), device
    r: jax.Array           # (C,) assigned resolutions, device
    est: ElasticStateJax   # threaded slot to slot, device scalars
    pack: jax.Array        # (4,) [extra_kbps, area, alloc_kbps, feasible]


@jax.named_scope("control")
def _control_impl(mlp_params, jcab_util, jcab_res, lam, a, c, W_t, est,
                  tau_wl, tau_wh, live, reconnect, *, method: str,
                  ecfg: ElasticConfig, bitrates: Tuple[int, ...],
                  resolutions: Tuple[float, ...],
                  slot_seconds: float, use_elastic: bool, use_kernel: bool,
                  w_cap: int, num_cams: int,
                  checked: bool = False) -> ControlOut:
    """One traced slot of the server-side control loop (sections 5.2 + 5.3):
    elastic adjustment -> utility table -> allocation, method-routed at
    trace time.  Every input/output is a device array; the only host values
    are the statics.

    ``live`` (C,) bool masks dead cameras out of the area signal and every
    allocator (they hold zero bitrate, see ``allocation``'s fault contract);
    ``reconnect`` (bool scalar) marks a slot where >= 1 camera rejoined —
    it clears the outstanding elastic debt BEFORE the slot's borrow/repay
    (``elastic.update_jax(reset_debt=...)``), so a rejoining camera cannot
    claim retroactive bandwidth.  An all-live mask with reconnect=False is
    numerically identical to the pre-fault program.  The effective capacity
    floor is 0.0 (not bitrates[0]): a hard-outage slot (W == 0, no elastic
    borrow) must yield the explicit all-zero infeasible allocation, not a
    phantom minimum-bitrate grant."""
    zero = jnp.float32(0.0)
    W_t = jnp.asarray(W_t, jnp.float32)
    live = (jnp.ones((num_cams,), bool) if live is None
            else jnp.asarray(live, bool))
    reconnect = (jnp.asarray(False) if reconnect is None
                 else jnp.asarray(reconnect, bool))
    if method in ("deepstream", "deepstream_no_elastic"):
        area = jnp.sum(jnp.where(live, jnp.asarray(a, jnp.float32), 0.0))
        extra = zero
        if use_elastic:
            est, extra_kbits, _ = elastic_mod.update_jax(
                ecfg, est, area, W_t, tau_wl, tau_wh, reset_debt=reconnect)
            extra = extra_kbits / slot_seconds   # Kbps-equivalent
        util, best_res = util_mod.utility_table(
            mlp_params, a, c, jnp.asarray(bitrates, jnp.float32),
            jnp.asarray(resolutions, jnp.float32), lam)
        cap = W_eff = jnp.maximum(W_t + extra, 0.0)
        _, b, r, _, feasible = alloc_mod.allocate_dp_jax(
            util, best_res, bitrates, W_eff, w_cap=w_cap,
            use_kernel=use_kernel, live=live)
        if checked and use_elastic:
            checkify.check(
                jnp.isfinite(est.debt_kbits)
                & (est.debt_kbits >= -1e-3)
                & (est.debt_kbits <= ecfg.budget_kbits + 1e-3),
                "control: elastic debt outside [0, budget]")
    elif method == "jcab":
        area = extra = zero
        cap = W_t
        _, b, r, _, feasible = alloc_mod.allocate_dp_jax(
            jcab_util, jcab_res, bitrates, W_t, w_cap=w_cap,
            use_kernel=use_kernel, live=live)
    elif method in ("reducto", "static"):
        area = extra = zero
        cap = W_t
        b, feasible = alloc_mod.allocate_fair_jax(bitrates, W_t, num_cams,
                                                  live=live)
        r = jnp.ones(num_cams, jnp.float32)
    else:
        raise ValueError(method)
    pack = jnp.stack([extra, area, jnp.sum(b),
                      jnp.asarray(feasible, jnp.float32)])
    if checked:
        checkify.check(jnp.any(live), "control: no live camera in slot")
        checkify.check(jnp.isfinite(W_t) & (W_t >= 0.0),
                       "control: bandwidth sample not finite/non-negative")
        checkify.check(jnp.all(jnp.isfinite(b)) & jnp.all(jnp.isfinite(pack)),
                       "control: non-finite allocation or log pack")
        checkify.check(jnp.all(jnp.where(live, True, b == 0.0)),
                       "control: dead camera granted bandwidth")
        checkify.check(
            ~jnp.asarray(feasible, bool) | (jnp.sum(b) <= cap + 1.0),
            "control: feasible allocation exceeds slot capacity")
    return ControlOut(b=b, r=r, est=est, pack=pack)


def fleet_slot_step(cfg: CodecConfig, server_params: Any, frames: jax.Array,
                    masks: jax.Array, b: jax.Array, r: jax.Array,
                    keys: jax.Array, keep: jax.Array, gt_boxes: jax.Array,
                    gt_valid: jax.Array, *, eval_frames: int, block_size: int,
                    conf_thresh: float = 0.4, mesh: Optional[Mesh] = None,
                    with_reuse: bool = True,
                    use_kernel: bool = True,
                    live: Optional[jax.Array] = None, checked: bool = False
                    ) -> FleetSlotOut:
    """Dispatch the unified slot-step; pads C to the mesh size and slices
    the padding back off.  Returns device arrays WITHOUT blocking — callers
    fetch ``host_pack`` (one packed transfer) when they need the scalars.
    ``live`` is the slot's (C,) camera liveness mask (None = all live);
    mesh-padding cameras are marked dead.  ``use_kernel`` routes the codec
    transform through the fused pallas transmission kernel (float32-ulp
    parity; ``SystemConfig.use_kernels`` threads here).  ``checked=True``
    routes through the checkify-instrumented executable and raises on any
    violated invariant (a blocking D2H of the error flag — diagnostics lane
    only)."""
    C = frames.shape[0]
    if live is None:
        live = jnp.ones((C,), bool)
    C_pad = pad_cameras(C, mesh)
    if C_pad != C:
        frames = pad_leading(frames, C_pad)
        masks = pad_leading(masks, C_pad, fill=True)
        b = pad_leading(b, C_pad, fill=1.0)
        r = pad_leading(r, C_pad, fill=1.0)
        keys = pad_leading(keys, C_pad)
        keep = pad_leading(keep, C_pad, fill=True)
        gt_boxes = pad_leading(gt_boxes, C_pad)
        gt_valid = pad_leading(gt_valid, C_pad)
        live = pad_leading(jnp.asarray(live, bool), C_pad, fill=False)
    fn = _get_executable(mesh, cfg, eval_frames, block_size, conf_thresh,
                         with_reuse, use_kernel and not checked, checked)
    with warnings.catch_warnings():
        # donated frame/GT buffers can't alias the (small) outputs; XLA still
        # recycles them for intermediates, which is the point — drop the nag
        # (pytest re-enables default filters, so module scope isn't enough)
        warnings.filterwarnings("ignore",
                                message=".*donated buffers were not usable.*")
        out = fn(server_params, frames, masks, b, r, keys, keep, gt_boxes,
                 gt_valid, jnp.asarray(live, bool))
    if checked:
        err, out = out
        with jax.transfer_guard_device_to_host("allow"):
            err.throw()
    if C_pad != C:
        out = FleetSlotOut(
            f1=out.f1[:C], f1_frames=out.f1_frames[:C], sizes=out.sizes[:C],
            host_pack=out.host_pack[:, :C], boxes=out.boxes[:C],
            scores=out.scores[:C], valid=out.valid[:C])
    return out


# -- whole-trace episode runner ----------------------------------------------

class EpisodeOut(NamedTuple):
    packs: jax.Array       # (T, 2, C) stacked [f1; sizes] per slot
    cpacks: jax.Array      # (T, 4) [extra, area, alloc_kbps, feasible]
    key: jax.Array         # the run key, unchanged (codec keys are a pure
                           # per-(slot, camera) fold — see slot_camera_keys)
    est: ElasticStateJax   # final elastic state (last ACTIVE slot's)
    ref: jax.Array         # (C, H, W) final reducto reference frames — the
                           # cross-run carry a windowed stream hands to the
                           # next window (zeros-passthrough for non-reducto)


_EPISODE_COMPILE_COUNTS: Dict[Tuple, int] = {}


def episode_compile_count() -> int:
    """Traced specializations of the episode executables (one per
    (method, mesh, config) — a timed re-run must add zero)."""
    return sum(_EPISODE_COMPILE_COUNTS.values())


def _episode_impl(server_params, light_params, mlp_params, jcab_util,
                  jcab_res, lam, scene_params: DeviceSceneParams,
                  trace, live_tr, active, t_idx, t_first, t_len, key0, skey,
                  tau_wl, tau_wh,
                  est0: ElasticStateJax, ref0, live_prev0, *, method: str,
                  scfg: SceneConfig, ccfg: CodecConfig, ecfg: ElasticConfig,
                  bitrates: Tuple[int, ...], resolutions: Tuple[float, ...],
                  use_elastic: bool, use_kernel: bool, w_cap: int,
                  num_cams: int, c_pad: int, eval_frames: int,
                  block_size: int, conf_thresh: float, gt_pad: int,
                  sharded: bool, checked: bool = False,
                  pipelined: bool = True) -> EpisodeOut:
    """One whole bandwidth trace as ONE traced program (runs per-device
    under shard_map when ``sharded``): ``lax.scan`` of segment-gen ->
    ROIDet -> control -> keep -> slot-step over the (T,) trace.  Carry:
    ``ElasticStateJax`` + reducto's cross-slot reference frames + the
    previous slot's liveness row (codec keys are a pure per-(slot, camera)
    fold — ``slot_camera_keys`` — so no key chain is carried).  Logs are
    STACKED on device and harvested once by the caller — nothing inside the
    scan ever touches the host.

    ``live_tr`` (T_b, num_cams) bool is the scanned liveness mask (fault
    families or all-True): dead cameras are masked out of the area signal,
    the allocators and the slot logs; a reconnect edge
    (``live & ~live_prev``) resets that camera's reducto reference and
    clears the fleet's elastic debt — the module docstring's fault
    contract, traced end to end with zero extra transfers.  ``live_prev0``
    ((num_cams,) bool) seeds the previous liveness row — all-True for a
    standalone run (slot-0 liveness is steady state, no spurious
    reconnect), the last row of the previous window for a streamed one.

    Bucketed traces: the scanned (T_b,) operands may be PADDED past the
    active prefix (``t_len`` slots) up to a trace-length bucket.  Padded
    slots run the full per-slot program on dead inputs, but the scanned
    ``active`` flag FREEZES every carry leaf there (``jnp.where(active,
    new, old)``), so the final scan carry — elastic state, reducto
    reference, liveness row — is exactly the last ACTIVE slot's.  The
    padding can never advance the controller (or any other observable
    state: the whole carry is the windowed-serving handoff surface), and
    the caller slices the stacked logs back to ``t_len``.

    Sharding: everything per-camera runs on the local camera shard; the
    control step is the one cross-camera stage, so its (a, c) features are
    ``all_gather``-ed over the "camera" axis and the control program runs
    replicated (pure-jnp DP — ``use_kernel=False`` — so replication costs
    redundant flops, not N interpret-mode kernel emulations), each device
    slicing its own cameras' (b, r) back out.

    ``pipelined=True`` restructures the scan body into the 2-stage software
    pipeline (slot i's encode overlapping slot i-1's detector dispatch,
    cond-skipped padded slots, compacted live-camera detector batches — see
    the inline comments at the scan bodies below); the carry/harvest
    contracts above hold identically for both bodies, and the reference
    body (``pipelined=False``, always used when ``checked``) is what the
    pipeline differential proves the pipelined program against."""
    N, H, W = scfg.frames_per_segment, scfg.height, scfg.width
    n_local = scene_params.backgrounds.shape[0]   # == c_pad / D under shard_map
    if checked:
        checkify.check(jnp.all(jnp.isfinite(trace)),
                       "episode: non-finite bandwidth trace")

    def gather(x):
        """local (n_local,) -> global (num_cams,) — mesh padding dropped."""
        if sharded:
            x = jax.lax.all_gather(x, "camera", axis=0, tiled=True)
        return x[:num_cams]

    def scatter(x, fill):
        """global (num_cams, ...) -> this device's (n_local, ...) rows."""
        if c_pad > num_cams:
            pad = jnp.full((c_pad - num_cams,) + x.shape[1:], fill, x.dtype)
            x = jnp.concatenate([x, pad], axis=0)
        if not sharded:
            return x
        i = jax.lax.axis_index("camera")
        return jax.lax.dynamic_slice_in_dim(x, i * n_local, n_local, 0)

    # the reuse arm is a per-METHOD static here (episodes compile one
    # executable per method anyway): only reducto's filtered frames need
    # the reuse detection, so the other three methods drop the C extra
    # detector rows from the batch — exact (all-True keep => w_keep == 1,
    # the arm is numerically inert) and statically cheaper
    with_reuse = (method == "reducto")
    F = min(eval_frames, N)

    def slot_front(est, ref, live_prev, t, W_t, live_t):
        """Everything UP TO the staged detector batch for one slot:
        synth -> ROIDet -> control -> keep -> (compacted) encode.  Returns
        (new est, new ref, staged, control pack, inverse camera permutation)
        — the carry-advance plus the ``SlotStaged`` handoff ``_slot_finish``
        consumes (this iteration in the reference body, the NEXT iteration
        in the pipelined one)."""
        frames, gtb, gtv = synth_mod.segments_device(
            scfg, scene_params, skey, t, gt_pad=gt_pad)
        keys_l = slot_camera_keys(key0, t, scene_params.cam_ids)
        reconnect_g = live_t & ~live_prev            # (num_cams,) global
        live_l = scatter(live_t, False)
        a = c = None
        if method in ("deepstream", "deepstream_no_elastic"):
            # bounded_cc: checkify cannot functionalize the labeler's
            # batched-predicate while-loop, so the checked episode swaps it
            # for the fixed-sweep fori variant (identical fixpoint)
            roi = roidet_mod._roidet_fleet_impl(
                frames, light_params, block_size=block_size,
                motion_thresh=roidet_mod.MOTION_THRESH,
                edge_thresh=roidet_mod.EDGE_THRESH,
                conf_thresh=roidet_mod.CONF_THRESH,
                use_kernel=use_kernel, max_boxes=roidet_mod.MAX_BOXES,
                bounded_cc=checked)
            masks = roi.mask
            a, c = gather(roi.area_ratio), gather(roi.confidence)
        else:
            masks = jnp.ones((n_local, H // block_size, W // block_size),
                             bool)
        co = _control_impl(
            mlp_params, jcab_util, jcab_res, lam, a, c, W_t, est,
            tau_wl, tau_wh, live_t, jnp.any(reconnect_g), method=method,
            ecfg=ecfg, bitrates=bitrates,
            resolutions=resolutions, slot_seconds=ccfg.slot_seconds,
            use_elastic=use_elastic, use_kernel=False, w_cap=w_cap,
            num_cams=num_cams, checked=checked)
        b_l, r_l = scatter(co.b, 1.0), scatter(co.r, 1.0)
        if method == "reducto":
            # "first slot" is per-RUN (t == t_first), matching the batched
            # loop's per-run reference reset — a resumed episode
            # (t_start > 0 on a reused scene) force-keeps frame 0 of ITS
            # first slot, not of global slot 0; a reconnecting camera is
            # per-camera "first" too (its reference went stale while dead)
            first = (jnp.broadcast_to(t == t_first, (n_local,))
                     | scatter(reconnect_g, False))
            keep, new_ref = _reducto_keep_impl(
                frames, ref, first, block_size=block_size,
                edge_thresh=roidet_mod.EDGE_THRESH, use_kernel=use_kernel)
        else:
            keep = jnp.ones((n_local, N), bool)
            new_ref = ref
        if pipelined:
            # dead-compute masking, camera axis: a stable live-first
            # argsort COMPACTS the slot's live cameras to the leading rows
            # and ZEROES the dead rows' frames before they enter the
            # encode/detector batch — dead cameras ride through as inert
            # zero tiles instead of full dead-frame compute.  Exact for
            # live cameras (every slot-step stage is camera-row-local, so
            # a row permutation permutes outputs bitwise) and for dead
            # ones (their f1/size entries are tx-masked to zero either
            # way); ``inv`` scatters the host_pack columns back to the
            # original camera order at finish time.
            with jax.named_scope("compact"):
                order = jnp.argsort(~live_l, stable=True)
                inv = jnp.argsort(order, stable=True).astype(jnp.int32)
                live_e = live_l[order]
                frames_e = jnp.where(live_e[:, None, None, None],
                                     frames[order], 0.0)
                cams = [x[order] for x in (masks, b_l, r_l, keys_l, keep,
                                           gtb, gtv)]
            st = _slot_encode(
                ccfg, frames_e, *cams, live_e,
                eval_frames=eval_frames, block_size=block_size,
                with_reuse=with_reuse, use_kernel=use_kernel and not checked)
        else:
            inv = jnp.arange(n_local, dtype=jnp.int32)
            st = _slot_encode(
                ccfg, frames, masks, b_l, r_l, keys_l, keep, gtb, gtv,
                live_l, eval_frames=eval_frames, block_size=block_size,
                with_reuse=with_reuse, use_kernel=use_kernel and not checked)
        return co.est, new_ref, st, co.pack, inv

    if not pipelined:
        # the FUSED reference body (also the checked/diagnostics program):
        # one slot's front and finish back to back, padded tail slots
        # frozen with jnp.where — the differential baseline the pipelined
        # program is proven against
        def step(carry, xs):
            est, ref, live_prev = carry
            t, W_t, live_t, active_t = xs
            est2, ref2, st, cpack, _ = slot_front(
                est, ref, live_prev, t, W_t, live_t)
            out = _slot_finish(server_params, st, conf_thresh=conf_thresh,
                               with_reuse=with_reuse)
            if checked:
                checkify.check(
                    jnp.all(jnp.isfinite(out.f1))
                    & jnp.all(jnp.isfinite(out.sizes)),
                    "episode slot-step: non-finite F1 or size")
            # padded tail slots FREEZE the whole carry (est, reducto ref,
            # liveness row): the final scan carry is then exactly the last
            # ACTIVE slot's state — the handoff a windowed stream
            # checkpoints and reloads, with no stacked-carry gather needed
            new_c, old_c = (est2, ref2, live_t), (est, ref, live_prev)
            frozen = jax.tree.map(
                lambda n, o: jnp.where(active_t, n, o), new_c, old_c)
            return frozen, (out.host_pack, cpack)

        (est, ref_out, _), (packs, cpacks) = jax.lax.scan(
            step, (est0, ref0, live_prev0), (t_idx, trace, live_tr, active))
        return EpisodeOut(packs=packs, cpacks=cpacks, key=key0, est=est,
                          ref=ref_out)

    # -- the SOFTWARE-PIPELINED scan body (the production episode) --------
    # Two stages, one slot apart: iteration i runs slot i's front (synth ->
    # control -> keep -> encode, stage A) AND slot i-1's finish (detector
    # forward -> F1, stage B).  The stages share no data within an
    # iteration — stage B reads only the CARRIED SlotStaged — so XLA can
    # overlap slot i-1's detector dispatch with slot i's encode.  The scan
    # runs T_b + 1 iterations over INTERNALLY extended xs (one trailing
    # inactive row drains the pipeline); ys row i holds slot i-1's logs, so
    # the leading warmup row is sliced off below and the stacked outputs
    # keep their (T_b, ...) harvest shape — the two-fetch audit contract is
    # untouched.  Carry freezing moves from jnp.where to lax.cond: an
    # inactive slot SKIPS stage A outright (dead-compute masking, slot
    # axis) and passes every carry leaf through unchanged, which is the
    # same frozen-carry contract by construction; its staged slot is marked
    # invalid so stage B emits zero log rows for it (the caller's [:T]
    # slice discards them, exactly as it discarded the reference body's
    # dead-input rows).
    C_det = n_local * F + (n_local if with_reuse else 0)
    G = gt_pad
    zeros_staged = SlotStaged(
        batch=jnp.zeros((C_det, H, W), jnp.float32),
        gt_e=jnp.zeros((n_local, F, G, 4), jnp.float32),
        gv_e=jnp.zeros((n_local, F, G), bool),
        gt_m=(jnp.zeros((n_local, F, G, 4), jnp.float32) if with_reuse
              else None),
        gv_m=(jnp.zeros((n_local, F, G), bool) if with_reuse else None),
        eval_w=jnp.zeros((n_local, F), jnp.float32),
        miss_w=jnp.zeros((n_local, F), jnp.float32),
        w_keep=jnp.zeros((n_local,), jnp.float32),
        sizes=jnp.zeros((n_local,), jnp.float32),
        tx=jnp.zeros((n_local,), bool))

    def pipe_step(carry, xs):
        est, ref, live_prev, (st_p, cp_p, inv_p, valid_p) = carry
        t, W_t, live_t, active_t = xs

        # stage B: finish the PREVIOUS slot's staged batch (warmup and
        # drained-pipeline iterations emit zero rows)
        def finish_prev(_):
            out = _slot_finish(server_params, st_p, conf_thresh=conf_thresh,
                               with_reuse=with_reuse)
            with jax.named_scope("compact"):
                pack = out.host_pack[:, inv_p]
            return pack, cp_p

        def finish_none(_):
            return (jnp.zeros((2, n_local), jnp.float32),
                    jnp.zeros((4,), jnp.float32))

        ys = jax.lax.cond(valid_p, finish_prev, finish_none, None)

        # stage A: front the CURRENT slot — skipped entirely for padded
        # tail slots (the cond IS the carry freeze: every leaf passes
        # through untouched)
        def front_live(_):
            est2, ref2, st, cpack, inv = slot_front(
                est, ref, live_prev, t, W_t, live_t)
            return est2, ref2, live_t, (st, cpack, inv, jnp.asarray(True))

        def front_dead(_):
            return est, ref, live_prev, (st_p, cp_p, inv_p,
                                         jnp.asarray(False))

        return jax.lax.cond(active_t, front_live, front_dead, None), ys

    ext = lambda x, row: jnp.concatenate([x, row[None]], axis=0)
    xs_ext = (ext(t_idx, t_idx[-1]), ext(trace, jnp.zeros((), trace.dtype)),
              ext(live_tr, jnp.ones((num_cams,), bool)),
              ext(active, jnp.zeros((), bool)))
    init = (est0, ref0, live_prev0,
            (zeros_staged, jnp.zeros((4,), jnp.float32),
             jnp.arange(n_local, dtype=jnp.int32), jnp.asarray(False)))
    (est, ref_out, _, _), (packs_x, cpacks_x) = jax.lax.scan(
        pipe_step, init, xs_ext)
    # drop the warmup row INSIDE the program: the harvested out_avals stay
    # (T_b, 2, C)/(T_b, 4) — same two stacked fetches, same audit shape
    return EpisodeOut(packs=packs_x[1:], cpacks=cpacks_x[1:], key=key0,
                      est=est, ref=ref_out)


def _get_episode_executable(mesh: Optional[Mesh], **statics):
    key = ("episode", mesh_cache_key(mesh)) + tuple(sorted(statics.items()))
    fn = _EXEC_CACHE.get(key)
    if fn is not None:
        return fn
    impl = functools.partial(_episode_impl, **statics)

    def counted(*args):
        _EPISODE_COMPILE_COUNTS[key] = _EPISODE_COMPILE_COUNTS.get(key, 0) + 1
        return impl(*args)

    if statics.get("checked"):
        assert mesh is None, "checked episodes run unsharded"
        fn = _EXEC_CACHE[key] = jax.jit(checkify.checkify(counted))
        return fn
    cam = P("camera")
    # (server, light, mlp, jcab_util, jcab_res, lam) replicated (P() is a
    # pytree prefix, so it covers whole param trees); scene params carry
    # their own per-field specs; carries/trace/liveness replicated; ref0
    # sharded (and the returned ref carry likewise)
    in_specs = (P(), P(), P(), P(), P(), P(), DeviceSceneParams.pspecs(),
                P(), P(), P(), P(), P(), P(), P(), P(), P(), P(), P(), cam,
                P())
    out_specs = EpisodeOut(P(None, None, "camera"), P(), P(), P(), cam)
    fn = _EXEC_CACHE[key] = sharded_jit(counted, mesh, in_specs, out_specs)
    return fn


class EpisodeInvariants(NamedTuple):
    """The episode operands every window of a stream shares, placed once:
    the detector and utility-MLP parameters (``{}`` for a method without
    the MLP), the jcab tables (zeros/ones for every other method), the
    camera weights, the camera-padded scene params and key, the elastic
    thresholds, and the fresh carry a window without one starts from.
    ``episode_invariants`` builds it; the scheduler caches it per method."""
    server_params: Any
    light_params: Any
    mlp_params: Any
    jcab_util: jax.Array       # (C, J) f32
    jcab_res: jax.Array        # (C, J) f32
    lam: jax.Array             # (C,) f32
    scene_params: DeviceSceneParams
    skey: jax.Array
    tau_wl: jax.Array          # f32 scalar
    tau_wh: jax.Array          # f32 scalar
    est0: ElasticStateJax      # the fresh elastic state
    ref0: jax.Array            # (C_pad, H, W) f32 zeros: the fresh reference


class EpisodeArgs(NamedTuple):
    """The episode executable's arguments, in ``_episode_impl`` order."""
    server_params: Any
    light_params: Any
    mlp_params: Any
    jcab_util: jax.Array
    jcab_res: jax.Array
    lam: jax.Array
    scene_params: DeviceSceneParams
    trace: jax.Array           # (T_b,) f32 Kbps, zero past the active T
    live_tr: jax.Array         # (T_b, C) bool, all-live past T
    active: jax.Array          # (T_b,) bool
    t_idx: jax.Array           # (T_b,) int32 global slot indices
    t_first: jax.Array         # int32 scalar: the stream's first slot
    t_len: jax.Array           # int32 scalar: the active T
    key0: jax.Array
    skey: jax.Array
    tau_wl: jax.Array
    tau_wh: jax.Array
    est0: ElasticStateJax
    ref0: jax.Array            # (C_pad, H, W) f32
    live_prev0: jax.Array      # (C,) bool


def _mesh_shardings(mesh: Mesh) -> Tuple[NamedSharding, NamedSharding]:
    """(replicated, camera-sharded) placements on ``mesh``."""
    return NamedSharding(mesh, P()), NamedSharding(mesh, P("camera"))


def episode_invariants(*, server_params, light_params, mlp_params,
                       jcab_util: Optional[np.ndarray],
                       jcab_res: Optional[np.ndarray], lam: np.ndarray,
                       scene_params: DeviceSceneParams, skey: jax.Array,
                       tau_wl: float, tau_wh: float, num_cams: int,
                       num_bitrates: int, frame_hw: Tuple[int, int],
                       mesh: Optional[Mesh] = None) -> EpisodeInvariants:
    """Build the window invariants: the host values (``lam``, the
    thresholds, the jcab tables or None, the fresh carry) in their final
    dtypes in ONE ``device_put``; the device values (parameters, scene)
    passed through, or, under a camera mesh, placed replicated or
    camera-sharded in that same put.  The scene params are padded to the
    mesh's camera multiple first (a few device ops, once per build)."""
    C_pad = pad_cameras(num_cams, mesh)
    shape = (num_cams, num_bitrates)
    host = dict(
        jcab_util=(np.zeros(shape, np.float32) if jcab_util is None
                   else np.asarray(jcab_util, np.float32)),
        jcab_res=(np.ones(shape, np.float32) if jcab_res is None
                  else np.asarray(jcab_res, np.float32)),
        lam=np.asarray(lam, np.float32),
        tau_wl=np.float32(tau_wl), tau_wh=np.float32(tau_wh),
        est0=ElasticStateJax(a_ema=np.float32(0.0), a_var=np.float32(0.0),
                             debt_kbits=np.float32(0.0),
                             initialized=np.bool_(False)),
        ref0=np.zeros((C_pad,) + tuple(frame_hw), np.float32))
    dev = dict(server_params=server_params, light_params=light_params,
               mlp_params={} if mlp_params is None else mlp_params,
               scene_params=synth_mod.pad_scene_params(scene_params, C_pad),
               skey=skey)
    obs.count_put("invariants")
    if mesh is None:
        host = jax.device_put(host)
    else:
        rep_sh, cam_sh = _mesh_shardings(mesh)
        scene_sh = DeviceSceneParams(*(
            cam_sh if s == P("camera") else rep_sh
            for s in DeviceSceneParams.pspecs()))
        host, dev = jax.device_put(
            (host, dev),
            ({k: cam_sh if k == "ref0" else rep_sh for k in host},
             {k: scene_sh if k == "scene_params" else rep_sh for k in dev}))
    return EpisodeInvariants(**host, **dev)


def episode_args(inv: EpisodeInvariants, trace_kbps: np.ndarray, *,
                 key0: jax.Array, num_cams: int, t_start: int,
                 buckets: Optional[Sequence[int]] = EPISODE_BUCKETS,
                 mesh: Optional[Mesh] = None,
                 faults: Optional[np.ndarray] = None,
                 est0: Optional[ElasticStateJax] = None,
                 ref0: Optional[jax.Array] = None,
                 live_prev0: Optional[np.ndarray] = None,
                 t_first: Optional[int] = None) -> EpisodeArgs:
    """One window's episode arguments: the invariants ``inv`` plus the
    window's host values — the bandwidth trace, the liveness mask, the
    active-slot mask, the slot indices, the previous liveness row and the
    ``t_first``/``t_len`` scalars — built in numpy in their final dtypes
    and shapes (padded to the trace bucket) and placed with ONE
    ``device_put``.  The codec key and a carried elastic state and
    reference are device arrays already and pass through; under a camera
    mesh they join that put to take their placement (a no-op when they
    have it), and a carried reference is padded to the mesh's camera
    multiple.

    ``faults`` is the optional (T, C) bool liveness mask (None: all live);
    ``est0``/``ref0``/``live_prev0``/``t_first`` default to a fresh stream
    (``inv``'s fresh carry, all-live, ``t_start``)."""
    T = len(trace_kbps)
    T_b = bucket_len(T, buckets)
    live = np.ones((T_b, num_cams), bool)
    if faults is not None:
        faults = np.asarray(faults, bool)
        if faults.shape != (T, num_cams):
            raise ValueError(
                f"faults mask must be (T={T}, C={num_cams}) bool, got "
                f"{faults.shape}")
        if not faults.any(axis=1).all():
            raise ValueError("faults mask leaves a slot with zero live "
                             "cameras — the control step needs >= 1")
        live[:T] = faults
    # zero-Kbps tail: padded slots run (and are discarded); zeros keep the
    # traced DP's capacity clamp trivially satisfied there
    trace = np.zeros(T_b, np.float32)
    trace[:T] = np.asarray(trace_kbps, np.float32)
    # slot indices continue from the scene's cursor (t_start) — data values,
    # not statics, so resumed episodes reuse the same executable; t_first
    # marks the STREAM's first slot (reducto's reference-reset rule) and
    # t_len the ACTIVE prefix of a bucketed trace
    window = (trace, live, np.arange(T_b) < T,
              np.arange(T_b, dtype=np.int32) + np.int32(t_start),
              np.int32(t_start if t_first is None else t_first), np.int32(T),
              (np.ones(num_cams, bool) if live_prev0 is None
               else np.asarray(live_prev0, bool)))
    if ref0 is not None and ref0.shape[0] != inv.ref0.shape[0]:
        ref0 = pad_leading(ref0, inv.ref0.shape[0])
    obs.count_put("episode")
    if mesh is None:
        window = jax.device_put(window)
    else:
        rep_sh, cam_sh = _mesh_shardings(mesh)
        window, key0, est0, ref0 = jax.device_put(
            (window, key0, est0, ref0), (rep_sh, rep_sh, rep_sh, cam_sh))
    trace, live_tr, active, t_idx, t_first, t_len, live_prev0 = window
    return EpisodeArgs(
        inv.server_params, inv.light_params, inv.mlp_params, inv.jcab_util,
        inv.jcab_res, inv.lam, inv.scene_params, trace, live_tr, active,
        t_idx, t_first, t_len, key0, inv.skey, inv.tau_wl, inv.tau_wh,
        inv.est0 if est0 is None else est0,
        inv.ref0 if ref0 is None else ref0, live_prev0)


def fleet_episode(method: str, args: EpisodeArgs, T: int,
                  **kw) -> EpisodeOut:
    """Prepare one episode (``prepare_episode``) and launch it."""
    return prepare_episode(method, args, T, **kw)()


def prepare_episode(method: str, args: EpisodeArgs, T: int, *,
                    codec_cfg: CodecConfig, scene_cfg: SceneConfig,
                    ecfg: ElasticConfig, bitrates: Sequence[int],
                    resolutions: Sequence[float], use_elastic: bool,
                    w_cap: int, num_cams: int, eval_frames: int,
                    block_size: int, use_kernel: bool = True,
                    conf_thresh: float = 0.4, gt_pad: int = 16,
                    mesh: Optional[Mesh] = None, checked: bool = False,
                    pipelined: bool = True) -> Callable[[], EpisodeOut]:
    """Prepare a WHOLE bandwidth trace as one compiled episode: look up (or
    compile) the executable for ``args`` (``episode_args``: a T-slot trace
    padded to its bucket).  Returns ``launch()``, which runs the episode,
    blocks until its logs are ready and returns them — the serving loop
    times the two apart.

    ``pipelined=True`` (the default, and the production program) runs the
    scan body as a 2-stage software pipeline: iteration i overlaps slot i's
    encode stage with slot i-1's detector/score stage, with padded tail
    slots skipped by ``lax.cond`` and each slot's dead cameras compacted
    out of the detector batch (see ``_episode_impl``).  ``pipelined=False``
    is the fused reference body the pipeline is differentialed against
    (logs equal to <= 1e-5; measured exactly equal); ``checked=True``
    always uses the reference body — the diagnostics lane instruments the
    simplest program.

    The liveness mask is ALWAYS scanned — all-True when no faults are
    injected — so faulty and fault-free episodes share one executable
    signature: fault injection costs zero recompiles and zero extra
    per-slot transfers.  ``checked=True`` dispatches the
    checkify-instrumented executable (unsharded) and throws any violated
    invariant AFTER the transfer-guarded region.

    Operand contract: the invariants are cached across windows and each
    window's host values arrive in one batched put (``episode_invariants``,
    ``episode_args``), so every argument is device-resident and placed
    before this is called; ``launch()`` calls the executable and returns
    stacked (T, 2, C) log packs + (T, 4) control packs as device arrays —
    ONE harvest fetch at episode end is all the host ever does.

    Trace-length bucketing: T is padded up to ``bucket_len(T, buckets)``
    with zero-bandwidth tail slots and the active length rides along as a
    traced scalar, so one executable per (method, bucket) serves EVERY
    T <= bucket — a mixed-T suite stops re-tracing the fleet per trace
    length.  Padded slots obey the ``bucket_len`` contract (no observable
    state advances; logs here are already sliced back to T).  ``w_cap``
    must be computed from the ACTIVE trace (``allocation.trace_capacity``
    on the unpadded array) — the zero-Kbps padding never widens it.

    Streaming carry (windowed serving, ``serve.stream``): ``episode_args``'
    ``ref0`` ((C, H, W) reducto reference), ``live_prev0`` ((C,) bool
    previous liveness row) and ``t_first`` (the STREAM's first slot,
    distinct from this window's ``t_start``) seed the episode carry from
    the previous window so a chain of windows is slot-for-slot identical to
    one long episode; the final carry comes back in ``EpisodeOut`` (``est``,
    ``ref``)."""
    # the DP backtrack is only shard_map-scan-safe in its unrolled (<= 64
    # camera) form — fail loudly instead of hitting the XLA CHECK abort the
    # fori_loop fallback would trigger inside this scan (see backtrack_jax)
    assert num_cams <= 64, (
        f"fleet_episode supports <= 64 cameras (got {num_cams}): the "
        "knapsack backtrack must take its unrolled form inside the "
        "shard_map'd scan body")
    C_pad = pad_cameras(num_cams, mesh)
    T_b = int(args.trace.shape[0])
    # the traced generator reads only shape-like SceneConfig fields (N, H,
    # W, noise_std) — the seed lives in the DEVICE params, so normalize it
    # out of the static cache key or every new scene would re-trace
    import dataclasses as _dc
    scene_cfg = _dc.replace(scene_cfg, seed=0)
    fn = _get_episode_executable(
        mesh, method=method, scfg=scene_cfg, ccfg=codec_cfg, ecfg=ecfg,
        bitrates=tuple(int(b) for b in bitrates),
        resolutions=tuple(float(r) for r in resolutions),
        use_elastic=bool(use_elastic), use_kernel=bool(use_kernel),
        w_cap=int(w_cap), num_cams=int(num_cams), c_pad=int(C_pad),
        eval_frames=int(eval_frames), block_size=int(block_size),
        conf_thresh=float(conf_thresh), gt_pad=int(gt_pad),
        sharded=mesh is not None, checked=bool(checked),
        pipelined=bool(pipelined) and not bool(checked))

    # the timed episode proper: everything is device-resident by now, so the
    # whole T-slot trace executes under the transfer guard in BOTH
    # directions with NO scoped exemptions — any per-slot upload or fetch
    # would trip it (the zero-H2D/zero-D2H acceptance check)
    def launch() -> EpisodeOut:
        err = None
        with jax.transfer_guard("disallow"):
            out = fn(*args)
            if checked:
                err, out = out
            jax.block_until_ready(out.packs)
        if err is not None:
            # the invariant verdict is fetched AFTER the guarded region —
            # the checked lane keeps the zero-per-slot-transfer structure
            with jax.transfer_guard_device_to_host("allow"):
                err.throw()
        if T_b != T:
            # harvested logs are the ACTIVE prefix only — the padded tail
            # never reaches the host
            out = out._replace(packs=out.packs[:T], cpacks=out.cpacks[:T])
        if C_pad != num_cams:
            # the ref carry is sliced back to the REAL cameras too: padded
            # cameras re-seed as zeros next window, which is invisible —
            # their rows never feed any real camera's keep/control signal
            out = out._replace(packs=out.packs[:, :, :num_cams],
                               ref=out.ref[:num_cams])
        return out

    return launch


# -- host-side helpers --------------------------------------------------------

def eval_indices(n: int, eval_frames: int) -> np.ndarray:
    """The sequential path's scored-frame selection (kept identical)."""
    return np.linspace(0, n - 1, min(eval_frames, n)).astype(int)


def gt_capacity(max_boxes_per_frame: int, min_boxes: int = 16) -> int:
    """Fixed GT padding G for a whole scene: smallest multiple of 8 >=
    max(min_boxes, max_boxes_per_frame).  Deriving G from each slot's actual
    max count changes the jit signature whenever the max crosses a multiple
    of 8 and silently recompiles the fleet program mid-run — cap it ONCE per
    scene instead and assert in ``pad_gt``."""
    return max(min_boxes, -(-max_boxes_per_frame // 8) * 8)


def pad_gt(gts: Sequence[Sequence[Sequence[Tuple]]],
           idx: np.ndarray, G: int = 16) -> Tuple[np.ndarray, np.ndarray]:
    """Pack ragged GT lists into padded arrays for the traced scorer.

    gts[cam][frame] -> list of (x0,y0,x1,y1); idx (C, F) frame indices; G the
    scene-fixed box capacity (``gt_capacity``).  Asserts instead of growing G
    so the fleet executable never recompiles mid-run.
    """
    C, F = idx.shape
    boxes = np.zeros((C, F, G, 4), np.float32)
    valid = np.zeros((C, F, G), bool)
    for c_i in range(C):
        for f_i in range(F):
            bxs = gts[c_i][int(idx[c_i, f_i])]
            assert len(bxs) <= G, (
                f"slot has {len(bxs)} GT boxes > scene capacity G={G}; raise "
                "SceneConfig.max_objects-derived gt_capacity instead of "
                "recompiling the fleet program")
            for g_i, bx in enumerate(bxs):
                boxes[c_i, f_i, g_i] = bx
                valid[c_i, f_i, g_i] = True
    return boxes, valid


def pad_gt_all(gts: Sequence[Sequence[Sequence[Tuple]]], num_frames: int,
               G: int = 16) -> Tuple[np.ndarray, np.ndarray]:
    """``pad_gt`` over EVERY frame of the slot: (C, N, G, 4)/(C, N, G) —
    the unified slot-step scores traced frame selections, so it consumes the
    whole slot's GT and gathers on device."""
    idx = np.tile(np.arange(num_frames), (len(gts), 1))
    return pad_gt(gts, idx, G=G)
