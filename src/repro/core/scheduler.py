"""DeepStream end-to-end control loop + baselines (paper sections 3-5, Fig. 1).

Per time slot:
  camera side: ROIDet -> (ROI mask, a_i, c_i); masked ("cropped") encode at
  the assigned (b_i, r_i).
  server side: elastic adjustment -> bandwidth allocation (utility-MLP + DP
  knapsack) -> decode -> server detector -> per-camera F1; slot utility =
  sum_i lambda_i F1_i.

Three execution modes (``SystemConfig.batched`` / ``SystemConfig.episode``):
  * batched (default) — the sharded, sync-free fleet slot-step: ONE compiled
    encode->detect->score->reuse-mix program over the camera axis
    (``core.fleet.fleet_slot_step``) shared by ALL methods (deepstream,
    jcab, reducto, static — method routing is data, not Python branches), so
    ``run()`` compiles the fleet executable once per (method, config).  The
    slot loop is pipelined: slot t+1's ROIDet dispatches while slot t's
    scores are still in flight (``SystemConfig.pipeline``).  With the
    default ``SystemConfig.alloc="device"`` the control loop itself
    (elastic + utility table + allocation, ``fleet.fleet_control_step``)
    is a traced program consuming the ROIDet (a, c) device vectors and a
    prefetched bandwidth-trace device array, and reducto's keep-flag
    decision is traced too (``fleet.reducto_keep_step`` + the in-program
    ``fleet.keep_selection``) — the host harvests ONLY the previous slot's
    packed (F1, sizes) + (4,) control logs, so the timed loop is clean
    under ``jax.transfer_guard_device_to_host("disallow")``.
    ``alloc="host"`` keeps the numpy reference control path (one packed
    (a, c) D2H fetch per slot).  With >1 device the camera axis is
    shard_map'd over a ("camera",) mesh and the big per-slot buffers are
    donated (``SystemConfig.shard`` / ``donate``).
  * episode (``SystemConfig.episode=True``) — the whole-trace runner
    (``run_episode``): segment generation moves on device
    (``data.synthetic.DeviceScene`` / ``segments_device``) and the ENTIRE
    N-slot trace executes as one ``fleet.fleet_episode`` lax.scan per
    method, under ``jax.transfer_guard("disallow")`` both directions with
    no scoped exemptions; stacked logs are harvested once at episode end.
    Trace lengths are BUCKETED (``SystemConfig.episode_buckets``): T pads
    up to a power-of-two bucket with masked tail slots so one executable
    per (method, bucket) serves every T.  A padded slot runs the per-slot
    program on dead inputs but cannot advance observable state — the
    returned codec key chain and elastic state come from the last active
    slot, its logs are sliced off before harvest, and the DP capacity is
    computed from the active prefix (``allocation.trace_capacity``), so
    bucketing never changes a pick (see ``fleet.bucket_len``).
  * sequential — the original per-camera Python loop, kept as the
    equivalence/benchmark baseline.  All modes consume PRNG keys in the
    same order, so F1/size logs agree within float tolerance — including
    reducto, whose sequential arm encodes fixed-shape segments with a traced
    kept-frame count and tracks the same cross-slot reference frame, so
    every arm draws identical coding noise.

Baselines (section 7.2):
  * reducto  — on-camera frame filtering (low-level feature deltas) + fair
               equal-share bitrates, full frames, detections reused for
               filtered frames;
  * jcab     — joint config adaptation + bandwidth allocation with a
               content-AGNOSTIC profiled utility (no ROI cropping, no (a,c));
  * static   — fixed equal share;
  * deepstream_no_elastic — ablation of section 5.3.

Fault tolerance: ``run(..., faults=)`` takes a (T, C) bool liveness mask
(``data.scenarios.make_faults`` families: camera_churn, camera_flap,
sensor_corrupt, ...) threaded through the batched and episode runners as
traced data — a dead (camera, slot) transmits nothing, is excluded from
every allocator and the elastic area signal, and rejoins as fresh
(reducto reference re-seed + elastic debt clamp) — see ``core.fleet``'s
liveness-mask contract.  The mask mirrors the padded-slot contract: a
dead camera still COMPUTES (one executable signature, zero recompiles,
zero extra transfers) but cannot advance any observable state, and a
camera dead for a whole trace is log-equivalent to a fleet that never had
it.  ``SystemConfig.checked`` turns on checkify-guarded invariants
(diagnostics lane), and ``EpisodeSupervisor`` wraps episode dispatch with
the ``ft.watchdog`` straggler gate, bounded retries and degraded-mode
fallback.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import obs
# the fetch counters stay readable here, where tests and the chip benchmark
# read them
from repro.common.obs import d2h_fetch_counts  # noqa: F401
from repro.core import allocation as alloc
from repro.core import codec as codec_mod
from repro.core import elastic as elastic_mod
from repro.core import fleet as fleet_mod
from repro.core import roidet as roidet_mod
from repro.core import utility as util_mod
from repro.core.codec import CodecConfig
from repro.core.elastic import ElasticConfig, ElasticState
from repro.data.synthetic import DeviceScene, MultiCameraScene, SceneConfig
from repro.ft import watchdog as ft_watchdog
from repro.kernels.edge_motion import ops as em_ops
from repro.models import detector as det
from repro.sharding import rules as shard_rules


# block-motion mass above which a frame counts as "changed" (reducto keep
# rule) — one constant shared by the sequential, pipelined-traced and
# episode paths (they must stay bit-in-sync for the cross-mode equivalence
# guarantees); lives in ``fleet`` so traced programs need no import cycle
MOTION_KEEP_THRESH = fleet_mod.MOTION_KEEP_THRESH


# -- device-to-host accounting ------------------------------------------------
# Every D2H fetch the batched loop performs goes through ``_d2h`` so the
# "zero per-slot sync" guarantee of the device-resident paths is CHECKABLE:
# on TPU/GPU, running the loop under
# ``jax.transfer_guard_device_to_host("disallow")`` trips on any fetch not
# scoped ``exempt`` (the pipelined log harvest; episode mode has NO per-slot
# exemption at all — its one harvest happens after the trace); on the CPU
# backend D2H is zero-copy and the guard never fires, so the per-category
# counters (``common.obs``, categories listed there) are the proof instead.
# Episode runs must leave 'keep' and 'control' at zero and add exactly TWO
# 'harvest' fetches per run (the stacked F1/size pack and the stacked
# control pack), independent of slot count.


def _d2h(x, kind: str, exempt: bool = False) -> np.ndarray:
    obs.count_fetch(kind)
    if exempt:
        with jax.transfer_guard_device_to_host("allow"):
            return np.asarray(x)
    return np.asarray(x)


def _motion_keep(score_sums: np.ndarray, first: bool) -> np.ndarray:
    """(..., N) per-pair motion-score sums (pair 0 = frame 0 vs the CROSS-
    SLOT reference, the last kept frame of the previous slot) -> (..., N)
    keep flags.  Frame 0 is forced kept on the first slot of a run (no
    reference yet) and on all-quiet slots (every slot transmits >= 1 frame)
    — the host mirror of ``fleet._reducto_keep_impl``."""
    keep = score_sums > MOTION_KEEP_THRESH
    keep[..., 0] |= first | ~keep.any(axis=-1)
    return keep


# the profiling sweep still draws from ONE key-split chain (its batched and
# sequential arms must match sample-for-sample); the RUN loops switched to
# ``fleet.slot_camera_keys`` fold-in keys — per-(slot, camera), fleet-size
# independent — so every execution mode draws identical coding noise AND a
# camera's noise stream survives adding/removing/killing other cameras
_key_chain = fleet_mod._key_chain


@dataclass
class SystemConfig:
    scene: SceneConfig = field(default_factory=SceneConfig)
    codec: CodecConfig = field(default_factory=CodecConfig)
    elastic: ElasticConfig = field(default_factory=ElasticConfig)
    block_size: int = 8
    weights: Optional[np.ndarray] = None      # lambda_i (default: ones)
    eval_frames: int = 4                      # frames scored per segment
    use_kernels: bool = True
    batched: bool = True                      # fleet slot-step vs Python loop
    shard: str = "auto"                       # "auto": camera mesh if >1 dev
    pipeline: bool = True                     # deferred-harvest slot loop
    donate: bool = True                       # donate per-slot fleet buffers
    alloc: str = "device"                     # control loop: "device" | "host"
    episode: bool = False                     # whole-trace lax.scan episodes
    # software-pipelined episode scan body (2-stage: slot t's detector
    # dispatch overlaps slot t+1's encode; padded slots cond-skipped, dead
    # cameras compacted out of the detector batch).  False runs the fused
    # reference body — the differential baseline; checked runs always do.
    episode_pipelined: bool = True
    # trace-length buckets for episode mode: T pads up to the smallest
    # bucket (masked tail slots, see fleet.bucket_len for the contract) so
    # ONE compiled episode per (method, bucket) serves every trace length.
    # None disables bucketing (the unbucketed reference program).
    episode_buckets: Optional[Tuple[int, ...]] = fleet_mod.EPISODE_BUCKETS
    # optional bandwidth ceiling (Kbps) pinning the traced allocator's
    # static DP capacity across runs: without it w_cap derives from each
    # trace's max and every new trace re-traces the control/episode
    # programs (w_cap is a jit static).  The scenario harness pins it so a
    # whole (method x family x T) matrix shares executables.
    w_cap_kbps: Optional[float] = None
    # checkify-guarded invariants (finite logs, allocation <= capacity,
    # liveness/keep consistency, elastic debt bounds) — the DIAGNOSTICS
    # lane, off by default.  When off, the compiled programs contain no
    # checkify code at all (the flag is a trace static), so the overhead of
    # having the feature is structurally zero.  When on, runs are forced
    # unsharded/undonated/kernel-free (checkify functionalization composes
    # with plain jit; pallas calls have no checkify rule).
    checked: bool = False

    def __post_init__(self):
        if self.alloc not in ("device", "host"):
            raise ValueError(f"alloc must be 'device' or 'host': {self.alloc!r}")
        if self.checked:
            self.shard = "off"
            self.donate = False
            self.use_kernels = False
        if self.episode:
            # the episode scan IS the device control loop — there is no
            # host-alloc variant of a program the host never re-enters
            if not self.batched:
                raise ValueError("episode mode requires batched=True")
            if self.alloc != "device":
                raise ValueError("episode mode requires alloc='device' "
                                 f"(got {self.alloc!r})")
        # the sequential reference loop has no traced control path; normalize
        # so the config (and bench metadata stamped from it) states what runs
        if not self.batched:
            self.alloc = "host"

    def lam(self) -> np.ndarray:
        if self.weights is None:
            return np.ones(self.scene.num_cameras, np.float64)
        return np.asarray(self.weights, np.float64)


class EpisodeCarry(NamedTuple):
    """The cross-run serving carry: everything a windowed stream must hand
    from one run to the next so a CHAIN of runs is slot-for-slot identical
    to one uninterrupted run over the concatenated trace.

    Lifecycle (the serving contract, see ``serve.stream``):

      1. Run window k with ``carry=`` (None for the stream's first window).
      2. The runner records the post-run carry on ``system.last_carry`` —
         ``est``/``ref`` are DEVICE arrays straight out of the episode scan
         (no fetch), ``live_prev``/``t_first`` host values the caller
         already owns.
      3. Checkpoint ``last_carry`` + the codec run key + host counters at
         the window boundary (``ckpt.AsyncSaver``); a restored process
         rebuilds the scene (pure in (seed, t)), sets its cursor, and
         passes the restored carry into window k+1.

    Not part of the carry — by construction, not omission: codec keys are a
    pure per-(slot, camera) fold of the run key (``fleet.slot_camera_keys``,
    the key never advances), and the scene is pure in (seed, cursor), so
    both "resume" for free.

    ``t_first`` is the STREAM's first global slot: reducto force-keeps
    frame 0 only when a slot's global index equals it, so later windows do
    NOT re-seed the reference the carry just handed them."""
    est: "elastic_mod.ElasticStateJax"   # device elastic EMA/variance/debt
    ref: jax.Array                       # (C, H, W) reducto reference frames
    live_prev: np.ndarray                # (C,) bool last served liveness row
    t_first: int                         # stream-origin slot index


class DeepStreamSystem:
    def __init__(self, cfg: SystemConfig, light_params: Any, server_params: Any,
                 mlp_params: Any = None):
        self.cfg = cfg
        self.light = light_params
        self.server = server_params
        self.mlp = mlp_params
        self.tau_wl: float = 0.0
        self.tau_wh: float = float("inf")
        self.jcab_table: Optional[np.ndarray] = None   # (J, R) content-agnostic F1
        self._key = jax.random.PRNGKey(1234)
        self._reducto_ref: Optional[jax.Array] = None       # batched runs
        self._reducto_ref_host: List[Optional[np.ndarray]] = []  # sequential
        # post-run serving carry (EpisodeCarry) recorded by run_episode and
        # the carried pipelined loop — what serve.stream checkpoints
        self.last_carry: Optional[EpisodeCarry] = None
        self.mesh = (shard_rules.camera_mesh()
                     if cfg.batched and cfg.shard == "auto" else None)
        # GT padding capacity fixed ONCE per scene config: deriving it from
        # each slot's max GT count silently recompiled the fleet executable
        # whenever the max crossed a multiple of 8
        self._G = fleet_mod.gt_capacity(
            cfg.scene.max_objects + cfg.scene.num_stationary)

    # -- small utilities ------------------------------------------------------

    def _nextkey(self) -> jax.Array:
        self._key, k = jax.random.split(self._key)
        return k

    def _keys(self, n: int) -> jax.Array:
        """n sequential keys, stacked (n, 2) — the fleet path draws keys in
        the same order the per-camera loop would, so both paths match."""
        self._key, subs = _key_chain(self._key, n)
        return subs

    # -- camera side -----------------------------------------------------------

    def camera_features(self, frames_c: np.ndarray, block: bool = True):
        """frames_c (C, N, H, W) -> ROIResult batch (fleet ROIDet, sharded
        over the camera mesh when one exists).  ``block=False`` skips the
        device sync — the pipelined slot loop fetches only the packed (a, c)
        scalars it needs."""
        res = roidet_mod.roidet_fleet(
            jnp.asarray(frames_c), self.light, block_size=self.cfg.block_size,
            use_kernel=self.cfg.use_kernels, mesh=self.mesh)
        if block:
            jax.block_until_ready(res.mask)
        return res

    # -- server-side evaluation: sequential path --------------------------------

    def detect_f1(self, decoded: jax.Array, gt_frames: List[List[Tuple]]
                  ) -> float:
        """decoded (N,H,W); gt per frame.  Scores cfg.eval_frames frames.
        (Reducto's detection-reuse scoring lives in ``_reuse_f1``.)"""
        n = decoded.shape[0]
        idxs = fleet_mod.eval_indices(n, self.cfg.eval_frames)
        grid = det.forward(self.server, decoded[idxs])
        boxes, scores, valid = det.decode_boxes(grid, conf_thresh=0.4)
        boxes, valid = np.asarray(boxes), np.asarray(valid)
        f1s = [det.f1_score(boxes[i], valid[i], gt_frames[j])
               for i, j in enumerate(idxs)]
        return float(np.mean(f1s))

    def encode_eval(self, frames: np.ndarray, gt: List[List[Tuple]],
                    mask: Optional[jax.Array], b: float, r: float,
                    key: Optional[jax.Array] = None) -> Tuple[float, float]:
        """Encode one camera's segment (optionally ROI-masked) and score F1.
        ``key`` pins the coding-noise key (the sequential run loop passes
        fold-in per-(slot, camera) keys; profiling keeps the split chain).
        Returns (f1, size_bytes)."""
        fr = jnp.asarray(frames)
        H, W = fr.shape[-2:]
        if mask is not None:
            fr = roidet_mod.crop_to_mask(fr, mask, self.cfg.block_size)
            roi_pixels = float(jnp.sum(mask)) * self.cfg.block_size ** 2
        else:
            roi_pixels = float(H * W)
        decoded, size = codec_mod.encode_segment(
            self.cfg.codec, fr, jnp.float32(roi_pixels), jnp.float32(b),
            jnp.float32(r), self._nextkey() if key is None else key)
        jax.block_until_ready(decoded)
        f1 = self.detect_f1(decoded, gt)
        return f1, float(size)

    # -- server-side evaluation: batched fleet path ------------------------------

    def _slot_dispatch(self, frames, gts, masks, b: np.ndarray, r: np.ndarray,
                       *, keys=None, keep: Optional[jax.Array] = None,
                       gt_dev: Optional[Tuple[jax.Array, jax.Array]] = None,
                       with_reuse: bool = True,
                       live: Optional[jax.Array] = None
                       ) -> fleet_mod.FleetSlotOut:
        """Dispatch the unified fleet slot-step WITHOUT blocking.

        frames (C,N,H,W); gts[cam][frame] GT lists (ignored when ``gt_dev``
        already holds the padded (C,N,G,..) device GT, e.g. from a
        ``DeviceScene``); masks (C,M,Nb) bool or None (no cropping);
        b, r (C,).  ``keep`` carries reducto's traced (C, N) keep-flags
        (None = all frames kept, which routes every other method through the
        same executable with the reuse arm inert).  ``run()`` keeps
        ``with_reuse=True`` so all methods share ONE executable; the
        profiling sweep (its batch shape is a separate specialization anyway)
        drops the arm's dead work with ``with_reuse=False``.
        """
        C, N = frames.shape[:2]
        if masks is None:
            masks = roidet_mod.full_frame_mask(
                C, frames.shape[2], frames.shape[3], self.cfg.block_size)
        if keys is None:
            keys = self._keys(C)
        if keep is None:
            keep = jnp.ones((C, N), bool)
        if gt_dev is None:
            gt_boxes, gt_valid = fleet_mod.pad_gt_all(gts, N, G=self._G)
        else:
            gt_boxes, gt_valid = gt_dev
        out = fleet_mod.fleet_slot_step(
            self.cfg.codec, self.server, jnp.asarray(frames),
            jnp.asarray(masks), jnp.asarray(b, jnp.float32),
            jnp.asarray(r, jnp.float32), keys, keep,
            jnp.asarray(gt_boxes), jnp.asarray(gt_valid),
            eval_frames=self.cfg.eval_frames, block_size=self.cfg.block_size,
            mesh=self.mesh, donate=self.cfg.donate, with_reuse=with_reuse,
            use_kernel=self.cfg.use_kernels, live=live,
            checked=self.cfg.checked)
        return out

    def fleet_encode_eval(self, frames: np.ndarray, gts: List[List[List[Tuple]]],
                          masks: Optional[jax.Array], b: np.ndarray,
                          r: np.ndarray, *, keys: Optional[jax.Array] = None
                          ) -> Tuple[np.ndarray, np.ndarray, fleet_mod.FleetSlotOut]:
        """Whole-fleet encode->detect->score in one compiled call (blocking
        variant used by profiling and tests; no reuse arm).  Returns
        (per-frame F1s (C, F), sizes (C,), raw FleetSlotOut)."""
        out = self._slot_dispatch(frames, gts, masks, b, r, keys=keys,
                                  with_reuse=False)
        jax.block_until_ready(out.host_pack)
        return np.asarray(out.f1_frames), np.asarray(out.sizes), out

    # -- offline profiling (section 5.1 + 5.3.1b) --------------------------------

    def profile(self, scene: MultiCameraScene, num_slots: int = 10,
                mlp_steps: int = 600, seed: int = 0) -> Dict:
        cfgc = self.cfg.codec
        feats, tgts = [], []
        C = self.cfg.scene.num_cameras
        J = len(cfgc.bitrates_kbps)
        R = len(cfgc.resolutions)
        acc_table = np.zeros((num_slots, C, J), np.float32)
        jcab_acc = np.zeros((num_slots, C, J, R), np.float32)
        for t in range(num_slots):
            seg = scene.segment()
            roi = self.camera_features(seg["frames"])
            if self.cfg.batched:
                masked_f1, full_f1 = self._profile_slot_batched(seg, roi)
                # masked_f1/full_f1: (C, J, R)
                a = np.asarray(roi.area_ratio)
                c = np.asarray(roi.confidence)
                for i in range(C):
                    for j, b in enumerate(cfgc.bitrates_kbps):
                        for k, r in enumerate(cfgc.resolutions):
                            feats.append((float(a[i]), float(c[i]),
                                          float(b), float(r)))
                            tgts.append(float(masked_f1[i, j, k]))
                acc_table[t] = masked_f1.max(-1)
                jcab_acc[t] = full_f1
            else:
                for i in range(C):
                    a_i = float(roi.area_ratio[i])
                    c_i = float(roi.confidence[i])
                    for j, b in enumerate(cfgc.bitrates_kbps):
                        best = 0.0
                        for k, r in enumerate(cfgc.resolutions):
                            f1, _ = self.encode_eval(
                                seg["frames"][i], seg["boxes"][i],
                                roi.mask[i], b, r)
                            feats.append((a_i, c_i, float(b), float(r)))
                            tgts.append(f1)
                            best = max(best, f1)
                            # content-agnostic (JCAB) profiling: full frames
                            f1_full, _ = self.encode_eval(
                                seg["frames"][i], seg["boxes"][i], None, b, r)
                            jcab_acc[t, i, j, k] = f1_full
                        acc_table[t, i, j] = best
        mlp = util_mod.init_utility_mlp(jax.random.PRNGKey(seed))
        self.mlp, mse = util_mod.fit(mlp, np.array(feats), np.array(tgts),
                                     steps=mlp_steps)
        self.tau_wl, self.tau_wh = elastic_mod.offline_thresholds(
            self.cfg.elastic, acc_table, np.asarray(cfgc.bitrates_kbps))
        self.jcab_table = jcab_acc.mean(axis=(0, 1))          # (J, R)
        return {"mlp_mse": mse, "tau_wl": self.tau_wl, "tau_wh": self.tau_wh,
                "num_samples": len(tgts)}

    def _profile_slot_batched(self, seg: Dict, roi) -> Tuple[np.ndarray,
                                                             np.ndarray]:
        """One slot of the profiling sweep, fleet-batched.

        Evaluates the full (camera x bitrate x resolution) x {masked, full}
        grid in J fleet calls of C*R*2 entries each (chunked on the bitrate
        axis to bound decoded-segment memory) instead of C*J*R*2 sequential
        encode_eval round-trips; each fleet call shards its entry axis over
        the camera mesh.  Key draw order matches the sequential nesting
        (camera, bitrate, resolution, masked-then-full) exactly.
        Returns (masked_f1 (C,J,R), full_f1 (C,J,R)).
        """
        cfgc = self.cfg.codec
        frames = seg["frames"]
        C, N, H, W = frames.shape
        J = len(cfgc.bitrates_kbps)
        R = len(cfgc.resolutions)
        keyseq = self._keys(C * J * R * 2).reshape(C, J, R, 2, 2)
        ones = np.ones_like(np.asarray(roi.mask))
        masks_cr = np.stack([np.asarray(roi.mask), ones], axis=1)  # (C,2,M,Nb)
        masked_f1 = np.zeros((C, J, R), np.float32)
        full_f1 = np.zeros((C, J, R), np.float32)
        # entry layout per chunk: (camera, resolution, masked/full)
        B = C * R * 2
        frames_b = np.repeat(frames[:, None], R * 2, axis=1).reshape(
            B, N, H, W)
        masks_b = np.repeat(
            masks_cr[:, None, :], R, axis=1).reshape(B, *masks_cr.shape[2:])
        r_b = np.repeat(np.tile(np.asarray(cfgc.resolutions, np.float32),
                                C)[:, None], 2, 1).reshape(B)
        gts_b = [seg["boxes"][i] for i in range(C) for _ in range(R * 2)]
        for j, b in enumerate(cfgc.bitrates_kbps):
            keys_j = keyseq[:, j].reshape(B, 2)
            f1f, _, _ = self.fleet_encode_eval(
                frames_b, gts_b, jnp.asarray(masks_b), np.full(B, b),
                r_b, keys=keys_j)
            f1 = f1f.mean(axis=1).reshape(C, R, 2)
            masked_f1[:, j] = f1[:, :, 0]
            full_f1[:, j] = f1[:, :, 1]
        return masked_f1, full_f1

    # -- reducto helpers ---------------------------------------------------------

    def _kept_eval_selection(self, keep_i: np.ndarray
                             ) -> Tuple[np.ndarray, np.ndarray]:
        """One camera's keep flags (N,) -> (kept frame indices, the subset of
        them scored for F1) — the selection both execution modes share."""
        kept_idx = np.flatnonzero(keep_i)
        sel = fleet_mod.eval_indices(len(kept_idx), self.cfg.eval_frames)
        return kept_idx, kept_idx[sel]

    def _reuse_f1(self, dets: Tuple[np.ndarray, np.ndarray],
                  gts_missed: List[List[Tuple]]) -> float:
        """Score filtered-out frames against the reused last detections."""
        boxes, valid = dets
        n = len(gts_missed)
        sel = fleet_mod.eval_indices(n, self.cfg.eval_frames)
        return float(np.mean([det.f1_score(boxes, valid, gts_missed[j])
                              for j in sel]))

    def _reducto_keep(self, frames: jax.Array, first_slot: bool,
                      reconnect: Optional[np.ndarray] = None
                      ) -> Tuple[jax.Array, None]:
        """Traced reducto keep decision for the batched loop: motion ->
        keep-flags -> next-slot reference, ONE device dispatch with ZERO
        host fetches (the pre-episode per-slot 'keep' D2H sync is gone —
        kept/missed frame selection happens inside the slot-step program
        via ``fleet.keep_selection``).  The cross-slot reference (last kept
        frame) is threaded through ``self._reducto_ref``; ``first_slot``
        marks the first slot of a FRESH stream (no reference yet — a
        carry-seeded window passes False, its reference is live);
        ``reconnect`` (C,) bool marks cameras whose reference went stale
        while dead — they re-seed from frame 0 like a run start."""
        C, H, W = frames.shape[0], frames.shape[2], frames.shape[3]
        if self._reducto_ref is None:
            self._reducto_ref = jnp.zeros((C, H, W), jnp.float32)
        first = np.full(C, bool(first_slot))
        if reconnect is not None:
            first = first | np.asarray(reconnect, bool)
        keep, self._reducto_ref = fleet_mod.reducto_keep_step(
            frames, self._reducto_ref, first,
            block_size=self.cfg.block_size, use_kernel=self.cfg.use_kernels,
            mesh=self.mesh)
        return keep, None

    # -- online loop -------------------------------------------------------------

    def _jcab_utility_table(self) -> Tuple[np.ndarray, np.ndarray]:
        """jcab's content-agnostic (util (C, J), best_res (C, J)) tables —
        the same (J, R) profiled table folded and lambda-weighted for every
        camera.  The ONE construction both control paths use: the host
        allocator calls it per slot, the device context uploads it once."""
        jt = self.jcab_table                              # (J, R)
        C = self.cfg.scene.num_cameras
        lam = self.cfg.lam()
        util = (np.repeat(jt.max(-1)[None], C, 0)
                * lam[:, None]).astype(np.float32)
        best_res = np.repeat(np.asarray(
            self.cfg.codec.resolutions, np.float32)[jt.argmax(-1)][None], C, 0)
        return util, best_res

    def run(self, scene: MultiCameraScene, trace_kbps: np.ndarray,
            method: str = "deepstream", use_elastic: Optional[bool] = None,
            faults: Optional[np.ndarray] = None) -> Dict[str, np.ndarray]:
        """One bandwidth trace.  ``faults`` is an optional (T, C) bool
        liveness mask (True = camera live that slot; see
        ``data.scenarios.make_faults``), honored by the batched and episode
        runners; the sequential reference loop predates the fault contract
        and rejects it."""
        if use_elastic is None:
            use_elastic = method == "deepstream"
        if faults is not None:
            faults = np.asarray(faults, bool)
            T, C = len(trace_kbps), self.cfg.scene.num_cameras
            if faults.shape != (T, C):
                raise ValueError(f"faults mask must be (T={T}, C={C}), got "
                                 f"{faults.shape}")
            if not faults.any(axis=1).all():
                raise ValueError("faults mask leaves a slot with zero live "
                                 "cameras")
        if self.cfg.episode:
            return self.run_episode(scene, trace_kbps, method, use_elastic,
                                    faults=faults)
        if self.cfg.batched:
            return self._run_batched(scene, trace_kbps, method, use_elastic,
                                     faults=faults)
        if faults is not None:
            raise NotImplementedError("fault injection needs the batched or "
                                      "episode runner (batched=True)")
        return self._run_sequential(scene, trace_kbps, method, use_elastic)

    def run_episode(self, scene: DeviceScene, trace_kbps: np.ndarray,
                    method: str = "deepstream",
                    use_elastic: Optional[bool] = None,
                    faults: Optional[np.ndarray] = None,
                    carry: Optional[EpisodeCarry] = None
                    ) -> Dict[str, np.ndarray]:
        """Whole-trace device-resident episode: one ``fleet_episode``
        dispatch covers every slot (segment generation included — ``scene``
        must be a ``DeviceScene``), then ONE stacked-log harvest.  During
        the timed region (dispatch + wait) the host performs ZERO per-slot
        work: no uploads, no fetches, no Python slot loop — callers may wrap
        it in ``jax.transfer_guard("disallow")`` with no scoped exemptions.
        Log-equivalent to the pipelined ``run()`` over the same
        ``DeviceScene`` seeds (<= 1e-5, see tests/test_episode.py), for any
        trace length: T is padded to a ``cfg.episode_buckets`` bucket inside
        ``fleet_episode`` and the harvested logs come back already sliced
        to the active T.

        Serving contract (``carry=``, see ``EpisodeCarry``): passing the
        previous window's carry seeds the elastic state, reducto reference,
        previous liveness row and stream-origin ``t_first``, making a chain
        of windowed calls over one reused scene slot-for-slot identical to
        a single call over the concatenated trace.  Every call (carried or
        not) records its post-run carry on ``self.last_carry`` — device
        arrays straight from the scan, no extra fetch — which is what
        ``serve.stream`` checkpoints at window boundaries."""
        if use_elastic is None:
            use_elastic = method == "deepstream"
        if not (self.cfg.batched and self.cfg.alloc == "device"):
            raise ValueError("episode mode requires batched=True and "
                             "alloc='device'")
        if not isinstance(scene, DeviceScene):
            raise TypeError("run_episode needs a DeviceScene (device-side "
                            f"segment generation), got {type(scene)!r}")
        assert scene.G == self._G, (scene.G, self._G)
        C = self.cfg.scene.num_cameras
        lam = self.cfg.lam()
        t_begin = scene._t
        # three host spans per dispatch: serve.prep (control context,
        # padding, placement, executable lookup), serve.device (the episode
        # until its logs are ready) and serve.harvest (the two fetches and
        # the log rows)
        with obs.span("serve.prep"):
            ctx = self._control_context(method, trace_kbps, use_elastic)
            if carry is not None:
                ctx["est"] = carry.est
            deep = method in ("deepstream", "deepstream_no_elastic")
            launch = fleet_mod.prepare_episode(
                method, codec_cfg=self.cfg.codec, scene_cfg=scene.cfg,
                server_params=self.server, light_params=self.light,
                mlp_params=self.mlp if deep else None,
                jcab_util=ctx["jcab_util"], jcab_res=ctx["jcab_res"],
                lam=ctx["lam"], scene_params=scene.params,
                trace=ctx["trace"], key0=self._key, skey=scene.key,
                tau_wl=ctx["tau_wl"], tau_wh=ctx["tau_wh"], est0=ctx["est"],
                ecfg=self.cfg.elastic,
                bitrates=tuple(self.cfg.codec.bitrates_kbps),
                resolutions=tuple(self.cfg.codec.resolutions),
                use_elastic=use_elastic, w_cap=ctx["w_cap"], num_cams=C,
                eval_frames=self.cfg.eval_frames,
                block_size=self.cfg.block_size,
                use_kernel=self.cfg.use_kernels, gt_pad=self._G,
                t_start=scene._t, mesh=self.mesh,
                buckets=self.cfg.episode_buckets, faults=faults,
                checked=self.cfg.checked,
                pipelined=self.cfg.episode_pipelined,
                ref0=None if carry is None else carry.ref,
                live_prev0=None if carry is None else carry.live_prev,
                t_first=None if carry is None else carry.t_first)
        # the whole trace runs under jax.transfer_guard("disallow") in BOTH
        # directions with NO scoped exemptions, and blocks — the structural
        # zero-per-slot-transfer guarantee of episode mode
        with obs.span("serve.device"):
            out = launch()
        with obs.span("serve.harvest"):
            # advance the scene cursor exactly like T pipelined segment()
            # calls would — a reused scene continues, matching the
            # pipelined reference
            scene._t += len(trace_kbps)
            self._key = out.key
            self.last_carry = EpisodeCarry(
                est=out.est, ref=out.ref,
                # audit: allow(host-sync) host-input faults mask, after dispatch
                live_prev=(np.asarray(faults[-1], bool) if faults is not None
                           else np.ones(C, bool)),
                t_first=(carry.t_first if carry is not None else t_begin))
            # the ONE whole-trace harvest — deliberately NOT transfer-guard
            # exempted: it happens after the timed region, so episode runs
            # need no scoped per-slot exemption anywhere
            packs = _d2h(out.packs, "harvest")
            cpacks = _d2h(out.cpacks, "harvest")
            return {
                "utility": packs[:, 0] @ lam,
                "mean_f1": packs[:, 0].mean(axis=1),
                "bytes": packs[:, 1].sum(axis=1),
                # audit: allow(host-sync) host-input trace echo, post-harvest
                "W": np.asarray(trace_kbps, float),
                "extra": cpacks[:, 0].astype(float),
                "area": cpacks[:, 1].astype(float),
                "alloc_kbps": cpacks[:, 2].astype(float),
            }

    def _slot_allocation(self, method: str, frames: np.ndarray, W_t: float,
                         est: ElasticState, use_elastic: bool,
                         live: Optional[np.ndarray] = None,
                         reconnect: bool = False
                         ) -> Tuple[np.ndarray, np.ndarray,
                                    Optional[jax.Array], float, float, float,
                                    ElasticState]:
        """Per-slot method routing shared by both execution modes: content
        features (deepstream only) -> elastic -> allocation.
        ``live`` (C,) bool masks dead cameras out of the area signal and
        every allocator; ``reconnect`` clears the elastic debt before the
        slot (the camera-rejoin clamp) — the numpy mirror of the traced
        ``fleet._control_impl`` fault contract.  The effective-capacity
        floor is 0.0 (a hard-outage slot allocates nothing), not
        bitrates[0].  Returns (b, r, masks, extra, area, alloc_kbps, est)."""
        cfgc = self.cfg.codec
        lam = self.cfg.lam()
        C = self.cfg.scene.num_cameras
        bitrates = list(cfgc.bitrates_kbps)
        if live is None:
            live = np.ones(C, bool)
        masks = None
        extra = area = 0.0

        if method in ("deepstream", "deepstream_no_elastic"):
            roi = self.camera_features(frames, block=not self.cfg.batched)
            # the host control path's ONE camera-side sync: packed (a_i, c_i)
            # scalars — the fetch alloc="device" eliminates (counted, NOT
            # transfer-guard exempt)
            ac = _d2h(jnp.stack([roi.area_ratio, roi.confidence]), "control")
            a, c = ac[0], ac[1]
            area = float(a[live].sum())
            if use_elastic:
                est, extra_kbits, _ = elastic_mod.update(
                    self.cfg.elastic, est, area, W_t,
                    self.tau_wl, self.tau_wh, reset_debt=bool(reconnect))
                extra = extra_kbits / cfgc.slot_seconds   # Kbps-equivalent
            util, best_res = alloc.build_utility_table(
                self.mlp, a, c, bitrates, cfgc.resolutions, lam)
            al = alloc.allocate_dp(util, best_res, bitrates,
                                   max(W_t + extra, 0.0),
                                   use_kernel=self.cfg.use_kernels,
                                   live=live)
            b, r = al.bitrates_kbps, al.resolutions
            masks = roi.mask
            alloc_kbps = float(al.bitrates_kbps.sum())

        elif method == "jcab":
            util, best_res = self._jcab_utility_table()
            al = alloc.allocate_dp(util, best_res, bitrates, W_t,
                                   use_kernel=self.cfg.use_kernels,
                                   live=live)
            b, r = al.bitrates_kbps, al.resolutions
            alloc_kbps = float(al.bitrates_kbps.sum())

        elif method in ("reducto", "static"):
            al = alloc.allocate_fair(bitrates, W_t, C, live=live)
            b, r = al.bitrates_kbps, al.resolutions
            alloc_kbps = float(al.bitrates_kbps.sum())
        else:
            raise ValueError(method)
        return b, r, masks, extra, area, alloc_kbps, est

    def _control_context(self, method: str, trace_kbps: np.ndarray,
                         use_elastic: bool) -> Dict[str, Any]:
        """Per-run device uploads for the traced control loop: the prefetched
        bandwidth trace, lambda weights, elastic thresholds, (for jcab) the
        content-agnostic table, the fresh device elastic state, and the ONE
        static DP capacity covering every slot (trace max plus the maximum
        elastic borrow)."""
        cfgc = self.cfg.codec
        bitrates = tuple(int(b) for b in cfgc.bitrates_kbps)
        # the static DP capacity comes from the ACTIVE (unpadded) trace —
        # episode bucketing appends zero-Kbps tail slots AFTER this runs, so
        # a bucketed run solves the exact DP the unbucketed program would.
        # cfg.w_cap_kbps optionally pins it so different traces share one
        # compiled control program (w_cap is a jit static).
        borrow = (self.cfg.elastic.budget_kbits / cfgc.slot_seconds
                  if use_elastic else 0.0)
        w_cap = alloc.trace_capacity(
            bitrates, trace_kbps, self.cfg.scene.num_cameras,
            elastic_borrow_kbps=borrow, pin_kbps=self.cfg.w_cap_kbps)
        ctx: Dict[str, Any] = dict(
            trace=jnp.asarray(np.asarray(trace_kbps, np.float32)),
            lam=jnp.asarray(self.cfg.lam(), jnp.float32),
            tau_wl=jnp.float32(self.tau_wl), tau_wh=jnp.float32(self.tau_wh),
            w_cap=w_cap,
            est=elastic_mod.init_state_jax(),
            jcab_util=None, jcab_res=None)
        if method == "jcab":
            # the SAME table _slot_allocation builds, uploaded ONCE per run
            util, best_res = self._jcab_utility_table()
            ctx["jcab_util"] = jnp.asarray(util)
            ctx["jcab_res"] = jnp.asarray(best_res)
        return ctx

    def _slot_control_device(self, method: str, frames: jax.Array, t: int,
                             ctx: Dict[str, Any], use_elastic: bool,
                             live: Optional[np.ndarray] = None,
                             reconnect: bool = False
                             ) -> Tuple[jax.Array, jax.Array,
                                        Optional[jax.Array], jax.Array]:
        """Per-slot method routing, device-resident: ROIDet's (a, c) device
        vectors feed the traced elastic -> allocation program directly —
        no host fetch anywhere.  ``live``/``reconnect`` are the slot's
        fault signals (traced data: no recompile, and their upload is H2D —
        the loop's zero-D2H guarantee is untouched).  Returns
        (b, r, masks, ctrl_pack), all device arrays; the elastic state is
        threaded through ``ctx``."""
        a = c = masks = None
        if method in ("deepstream", "deepstream_no_elastic"):
            roi = self.camera_features(frames, block=False)
            masks = roi.mask
            # shard-boundary gather onto the control device
            a = shard_rules.unshard(roi.area_ratio, self.mesh)
            c = shard_rules.unshard(roi.confidence, self.mesh)
        co = fleet_mod.fleet_control_step(
            method, self.mlp if a is not None else None,
            ctx["jcab_util"], ctx["jcab_res"], ctx["lam"], a, c,
            ctx["trace"][t], ctx["est"], ctx["tau_wl"], ctx["tau_wh"],
            ecfg=self.cfg.elastic,
            bitrates=tuple(self.cfg.codec.bitrates_kbps),
            resolutions=tuple(self.cfg.codec.resolutions),
            slot_seconds=self.cfg.codec.slot_seconds,
            use_elastic=use_elastic, use_kernel=self.cfg.use_kernels,
            w_cap=ctx["w_cap"], num_cams=self.cfg.scene.num_cameras,
            mesh=self.mesh,
            live=None if live is None else jnp.asarray(live, bool),
            reconnect=bool(reconnect), checked=self.cfg.checked)
        ctx["est"] = co.est
        return co.b, co.r, masks, co.pack

    def _run_batched(self, scene: MultiCameraScene, trace_kbps: np.ndarray,
                     method: str, use_elastic: bool,
                     faults: Optional[np.ndarray] = None,
                     carry: Optional[EpisodeCarry] = None
                     ) -> Dict[str, np.ndarray]:
        """Pipelined fleet loop: every method routes through ONE compiled
        slot-step.  With ``alloc="device"`` the control loop runs on device
        too — the host only harvests slot t's packed (F1, sizes) + control
        logs while slot t+1 is in flight (those fetches are scoped
        transfer-guard exemptions; everything else is D2H-free).  With
        ``alloc="host"`` the numpy reference control path syncs on one
        packed (a, c) fetch per slot.  ``faults`` (T, C) bool threads the
        liveness mask through control, keep-flags and the slot-step as
        traced data (same executables, no extra D2H).

        ``carry`` (device-control only) seeds the same serving carry as
        ``run_episode`` — ``serve.stream``'s degraded "pipelined" rung
        stays slot-for-slot identical to the episode rungs — and every
        device-control run records ``self.last_carry``."""
        lam = self.cfg.lam()
        C = self.cfg.scene.num_cameras
        device_ctrl = self.cfg.alloc == "device"
        if carry is not None and not device_ctrl:
            raise ValueError("carry-seeded runs need alloc='device' (the "
                             "host control path has no device carry)")
        est = ElasticState()
        t_begin = getattr(scene, "_t", 0)
        ctx = (self._control_context(method, trace_kbps, use_elastic)
               if device_ctrl else None)
        if carry is not None:
            ctx["est"] = carry.est
        logs = {k: [] for k in ("utility", "mean_f1", "bytes", "W", "extra",
                                "alloc_kbps", "area")}

        def harvest(item: Tuple[fleet_mod.FleetSlotOut,
                                Optional[jax.Array]]) -> None:
            out, cpack = item
            # the per-slot log harvest: one (2, C) + one (4,) D2H transfer,
            # explicitly exempted from the loop's transfer-guard guarantee
            pack = _d2h(out.host_pack, "harvest", exempt=True)
            cp = (None if cpack is None
                  else _d2h(cpack, "harvest", exempt=True))
            logs["utility"].append(float(np.dot(lam, pack[0])))
            logs["mean_f1"].append(float(np.mean(pack[0])))
            logs["bytes"].append(float(np.sum(pack[1])))
            if cp is not None:
                logs["extra"].append(float(cp[0]))
                logs["area"].append(float(cp[1]))
                logs["alloc_kbps"].append(float(cp[2]))

        self._reducto_ref = None if carry is None else carry.ref
        live_prev = (np.ones(C, bool) if carry is None
                     else np.asarray(carry.live_prev, bool))
        pending: Optional[Tuple] = None
        for t in range(len(trace_kbps)):
            W_t = float(trace_kbps[t])
            seg = scene.segment()
            # DeviceScene segments carry padded GT device arrays — the lazy
            # host "boxes" lists (a D2H fetch + Python build) stay untouched
            gt_dev = seg.get("gt_dev")
            gts = None if gt_dev is not None else seg["boxes"]
            # ONE H2D upload per slot: ROIDet/motion and the slot-step all
            # consume this device array (their jnp.asarray is then a no-op);
            # they dispatch before the slot-step donates it, and the next
            # slot uploads a fresh segment.  DeviceScene segments are already
            # device-resident (incl. padded GT) — zero uploads.
            frames = jnp.asarray(seg["frames"])
            # fleet-size-independent per-(slot, camera) fold-in keys: the
            # coding noise of camera i at trace slot t never depends on which
            # OTHER cameras exist or live — the property behind the
            # dead-camera == absent-camera log equivalence.  self._key is the
            # run key and is NOT advanced (matches episode mode).
            keys = fleet_mod.slot_camera_keys(self._key, seg["t"],
                                              np.arange(C))
            live_t = np.ones(C, bool) if faults is None else faults[t]
            reconnect_vec = live_t & ~live_prev
            if device_ctrl:
                b, r, masks, cpack = self._slot_control_device(
                    method, frames, t, ctx, use_elastic,
                    live=None if faults is None else live_t,
                    reconnect=bool(reconnect_vec.any()))
            else:
                b, r, masks, extra, area, alloc_kbps, est = \
                    self._slot_allocation(method, frames, W_t, est,
                                          use_elastic, live=live_t,
                                          reconnect=bool(reconnect_vec.any()))
                cpack = None
                logs["extra"].append(extra)
                logs["area"].append(area)
                logs["alloc_kbps"].append(alloc_kbps)
            keep = None
            if method == "reducto":
                keep, _ = self._reducto_keep(
                    frames, t == 0 and carry is None,
                    reconnect=None if faults is None else reconnect_vec)

            out = self._slot_dispatch(
                frames, gts, masks, b, r, keys=keys, keep=keep, gt_dev=gt_dev,
                live=None if faults is None else jnp.asarray(live_t))
            live_prev = live_t
            logs["W"].append(W_t)
            if pending is not None:
                harvest(pending)
            if self.cfg.pipeline:
                pending = (out, cpack)
            else:
                harvest((out, cpack))
        if pending is not None:
            harvest(pending)
        if device_ctrl:
            ref = self._reducto_ref
            if ref is None:      # non-reducto: the reference passes through
                ref = (carry.ref if carry is not None else jnp.zeros(
                    (C, self.cfg.scene.height, self.cfg.scene.width),
                    jnp.float32))
            self.last_carry = EpisodeCarry(
                est=ctx["est"], ref=ref, live_prev=np.asarray(live_prev),
                t_first=(carry.t_first if carry is not None else t_begin))
        return {k: np.asarray(v) for k, v in logs.items()}

    def _run_sequential(self, scene: MultiCameraScene, trace_kbps: np.ndarray,
                        method: str, use_elastic: bool
                        ) -> Dict[str, np.ndarray]:
        lam = self.cfg.lam()
        C = self.cfg.scene.num_cameras
        est = ElasticState()
        logs = {k: [] for k in ("utility", "mean_f1", "bytes", "W", "extra",
                                "alloc_kbps", "area")}

        self._reducto_ref_host: List[Optional[np.ndarray]] = [None] * C
        for t in range(len(trace_kbps)):
            W_t = float(trace_kbps[t])
            seg = scene.segment()
            frames, gts = seg["frames"], seg["boxes"]
            # same fold-in key scheme as the fleet paths (run key untouched)
            keys = fleet_mod.slot_camera_keys(self._key, seg["t"],
                                              np.arange(C))
            b, r, masks, extra, area, alloc_kbps, est = self._slot_allocation(
                method, frames, W_t, est, use_elastic)
            if method == "reducto":
                f1s, sizes = self._reducto_slot(frames, gts, b, first=t == 0,
                                                keys=keys)
            else:
                f1s, sizes = self._encode_eval_all(frames, gts, masks, b, r,
                                                   keys=keys)
            logs["extra"].append(extra)
            logs["area"].append(area)
            logs["alloc_kbps"].append(alloc_kbps)
            logs["utility"].append(float(np.dot(lam, f1s)))
            logs["mean_f1"].append(float(np.mean(f1s)))
            logs["bytes"].append(float(np.sum(sizes)))
            logs["W"].append(W_t)

        return {k: np.asarray(v) for k, v in logs.items()}

    # -- per-slot encode+score dispatch ------------------------------------------

    def _encode_eval_all(self, frames: np.ndarray,
                         gts: List[List[List[Tuple]]],
                         masks: Optional[jax.Array], b: np.ndarray,
                         r: np.ndarray, keys: Optional[jax.Array] = None
                         ) -> Tuple[List[float], List[float]]:
        """All cameras' encode->detect->score, one camera at a time (the
        sequential reference; the batched loop dispatches ``_slot_dispatch``)."""
        C = frames.shape[0]
        f1s, sizes = [], []
        for i in range(C):
            f1, size = self.encode_eval(
                frames[i], gts[i], None if masks is None else masks[i],
                float(b[i]), float(r[i]),
                key=None if keys is None else keys[i])
            f1s.append(f1); sizes.append(size)
        return f1s, sizes

    def _reducto_slot(self, frames: np.ndarray, gts: List[List[List[Tuple]]],
                      bs: np.ndarray, first: bool,
                      keys: Optional[jax.Array] = None
                      ) -> Tuple[List[float], List[float]]:
        """Sequential reducto baseline slot: edge-diff frame filtering + fair
        shares, one camera at a time.

        Encodes the FIXED-SHAPE segment with a traced kept-frame count
        (``num_frames``) and scores the kept frames through eval indices —
        exactly the math the unified fleet program runs (including the
        cross-slot reference: frame 0 scores against the previous slot's
        last KEPT frame, threaded through ``self._reducto_ref_host``) — so
        the batched path reproduces this reference to float tolerance (both
        draw the same coding-noise samples on the same-shaped arrays).
        """
        C, N = frames.shape[:2]
        f1s, sizes = [], []
        H, W = frames.shape[-2:]
        for i in range(C):
            fr = frames[i]
            ref = fr[0] if first else self._reducto_ref_host[i]
            sc = em_ops.segment_motion(
                jnp.concatenate([jnp.asarray(ref)[None], jnp.asarray(fr)]),
                block_size=self.cfg.block_size,
                use_kernel=self.cfg.use_kernels)             # (N, M, Nb)
            keep = _motion_keep(_d2h(jnp.sum(sc, axis=(1, 2)), "keep",
                                     exempt=True), first)
            kept_idx, ev_idx = self._kept_eval_selection(keep)
            self._reducto_ref_host[i] = fr[kept_idx[-1]]
            decoded, size = codec_mod.encode_segment(
                self.cfg.codec, jnp.asarray(fr), jnp.float32(H * W),
                jnp.float32(bs[i]), jnp.float32(1.0),
                self._nextkey() if keys is None else keys[i],
                num_frames=jnp.float32(len(kept_idx)))
            jax.block_until_ready(decoded)
            grid = det.forward(self.server, decoded[ev_idx])
            db, _, dv = det.decode_boxes(grid, conf_thresh=0.4)
            db, dv = np.asarray(db), np.asarray(dv)
            f1 = float(np.mean([det.f1_score(db[k], dv[k], gts[i][j])
                                for k, j in enumerate(ev_idx)]))
            # filtered frames reuse the last kept RAW frame's detections
            # (within-slot reuse: the camera detects on what it transmits)
            grid2 = det.forward(self.server, jnp.asarray(fr[kept_idx[-1:]]))
            rb, _, rv = det.decode_boxes(grid2, conf_thresh=0.4)
            dets = (np.asarray(rb[0]), np.asarray(rv[0]))
            if not keep.all():
                miss_idx = np.flatnonzero(~keep)
                f1_re = self._reuse_f1(dets, [gts[i][j] for j in miss_idx])
                w_keep = keep.mean()
                f1 = f1 * w_keep + f1_re * (1 - w_keep)
            f1s.append(f1); sizes.append(float(size))
        return f1s, sizes


# -- watchdog-supervised episode execution ------------------------------------


@dataclass
class SupervisorConfig:
    """Policy knobs for ``EpisodeSupervisor``.

    ``max_retries`` bounds re-dispatches of ONE run at the same mode rung;
    ``backoff_s`` is the base of an exponential retry backoff (0 = retry
    immediately — the default, since a failed jit dispatch has no cooldown
    to wait out); ``degrade`` allows falling down the mode ladder when
    retries are exhausted or the watchdog escalates; ``recover_after`` is
    how many consecutive healthy ('ok' verdict) runs at a degraded rung
    climb back one rung (0 disables recovery — rungs stay sticky);
    ``watchdog`` parameterizes the EMA+sigma straggler gate
    (``ft.watchdog``) fed with per-run wall times."""
    max_retries: int = 2
    backoff_s: float = 0.0
    degrade: bool = True
    recover_after: int = 3
    watchdog: ft_watchdog.WatchdogConfig = field(
        default_factory=ft_watchdog.WatchdogConfig)


class EpisodeSupervisor:
    """Host-side supervisor wrapping ``DeepStreamSystem`` episode dispatch
    with fault tolerance: bounded retry with backoff, an ``ft.watchdog``
    straggler gate on per-run wall time, and a degraded-mode ladder.

    The ladder (for an episode-mode system):

      ``episode``          whole-trace lax.scan (the fast path)
      ``episode_chunked``  the SAME episode program dispatched per
                           next-smaller-bucket chunk of the trace — smaller
                           programs, more dispatches; elastic/reducto state
                           re-seeds at chunk boundaries, the documented
                           degraded-mode approximation
      ``pipelined``        the per-slot pipelined fleet loop (no episode
                           scan at all)

    A run that raises is retried up to ``cfg.max_retries`` times at the
    current rung, then the supervisor degrades one rung (when
    ``cfg.degrade``) and retries there; a run whose wall time trips the
    watchdog's ``'replace'`` verdict degrades the NEXT run preemptively.
    Rungs are sticky across runs (``self._rung``), and a degraded fleet
    climbs BACK one rung after ``cfg.recover_after`` consecutive healthy
    runs at the degraded rung (a ``'recover'`` event; 0 disables and makes
    degradation permanent until the caller resets it).  EVERY rung change
    — watchdog degrade, retries-exhausted degrade, or recovery —
    rebaselines the watchdog (``Watchdog.rebaseline``): the step-time
    distribution shifts wholesale across modes, so the new rung's EMA must
    never be seeded from the old rung's timings (a recovered runner gated
    against its degraded-rung baseline would either instantly re-trip or
    mask real stragglers).  Every decision is appended to ``self.events``
    for tests and post-mortems.

    ``fault_hook(attempt=, mode=)`` (tests/chaos injection) runs right
    before each dispatch; raising from it counts as that attempt failing.
    """

    LADDER_EPISODE = ("episode", "episode_chunked", "pipelined")

    def __init__(self, system: DeepStreamSystem,
                 cfg: Optional[SupervisorConfig] = None,
                 fault_hook: Optional[Any] = None):
        self.system = system
        self.cfg = cfg if cfg is not None else SupervisorConfig()
        self.fault_hook = fault_hook
        self.watchdog = ft_watchdog.Watchdog(self.cfg.watchdog)
        self.events: List[Dict[str, Any]] = []
        self._step = 0          # watchdog step counter (successful runs)
        self._rung = 0          # current position on the mode ladder
        self._ok_streak = 0     # consecutive healthy runs at a degraded rung

    @property
    def mode(self) -> str:
        return self._ladder()[min(self._rung, len(self._ladder()) - 1)]

    def _ladder(self) -> Tuple[str, ...]:
        if self.system.cfg.episode:
            return self.LADDER_EPISODE
        return ("pipelined",)

    def run(self, scene, trace_kbps: np.ndarray, method: str = "deepstream",
            use_elastic: Optional[bool] = None,
            faults: Optional[np.ndarray] = None) -> Dict[str, np.ndarray]:
        """One supervised bandwidth-trace run; same signature and logs as
        ``DeepStreamSystem.run``."""
        ladder = self._ladder()
        last_err: Optional[BaseException] = None
        for rung in range(min(self._rung, len(ladder) - 1), len(ladder)):
            mode = ladder[rung]
            for attempt in range(self.cfg.max_retries + 1):
                if attempt and self.cfg.backoff_s > 0.0:
                    time.sleep(self.cfg.backoff_s * (2.0 ** (attempt - 1)))
                t0 = time.perf_counter()
                try:
                    if self.fault_hook is not None:
                        self.fault_hook(attempt=attempt, mode=mode)
                    logs = self._dispatch(mode, scene, trace_kbps, method,
                                          use_elastic, faults)
                except Exception as e:   # retry-with-backoff boundary
                    last_err = e
                    self.events.append({"kind": "retry", "mode": mode,
                                        "attempt": attempt,
                                        "error": repr(e)})
                    continue
                wall = time.perf_counter() - t0
                self._step += 1
                verdict = self.watchdog.record(self._step, wall)
                self.events.append({"kind": "ok", "mode": mode,
                                    "attempt": attempt, "wall_s": wall,
                                    "verdict": verdict})
                if (verdict == "replace" and self.cfg.degrade
                        and rung + 1 < len(ladder)):
                    # persistent straggling at this rung: degrade the NEXT
                    # run preemptively (this one already succeeded)
                    self._rung = rung + 1
                    self._ok_streak = 0
                    self.watchdog.rebaseline()
                    self.events.append({"kind": "degrade", "mode": mode,
                                        "to": ladder[self._rung],
                                        "cause": "watchdog"})
                elif (verdict == "ok" and rung > 0
                        and self.cfg.recover_after > 0):
                    self._ok_streak += 1
                    if self._ok_streak >= self.cfg.recover_after:
                        # sustained health at the degraded rung: climb back
                        # one rung, gating its first steps against a FRESH
                        # baseline (not the degraded rung's timings)
                        self._rung = rung - 1
                        self._ok_streak = 0
                        self.watchdog.rebaseline()
                        self.events.append({"kind": "recover", "mode": mode,
                                            "to": ladder[self._rung],
                                            "after_ok":
                                                self.cfg.recover_after})
                else:
                    self._ok_streak = 0
                return logs
            if self.cfg.degrade and rung + 1 < len(ladder):
                self._rung = rung + 1
                self._ok_streak = 0
                self.watchdog.rebaseline()
                self.events.append({"kind": "degrade", "mode": mode,
                                    "to": ladder[self._rung],
                                    "cause": "retries_exhausted"})
            else:
                break
        raise RuntimeError(
            f"supervised run failed at every mode rung (last mode "
            f"{self.mode!r}, {self.cfg.max_retries} retries each)"
        ) from last_err

    # -- mode dispatch ---------------------------------------------------------

    def _dispatch(self, mode: str, scene, trace_kbps: np.ndarray, method: str,
                  use_elastic: Optional[bool],
                  faults: Optional[np.ndarray]) -> Dict[str, np.ndarray]:
        if use_elastic is None:
            use_elastic = method == "deepstream"
        if mode == "episode":
            return self.system.run_episode(scene, trace_kbps, method,
                                           use_elastic, faults=faults)
        if mode == "episode_chunked":
            return self._run_chunked(scene, trace_kbps, method, use_elastic,
                                     faults)
        if mode == "pipelined":
            return self.system._run_batched(scene, trace_kbps, method,
                                            use_elastic, faults=faults)
        raise ValueError(mode)

    def _chunk_len(self, T: int) -> int:
        """Degraded chunk size: the bucket BELOW the one a T-slot episode
        would use (smaller compiled program, already warm from the bucket
        ladder), floored at the smallest bucket."""
        buckets = self.system.cfg.episode_buckets
        if not buckets:
            return max(1, T // 2)
        below = [b for b in sorted(buckets)
                 if b < fleet_mod.bucket_len(T, buckets)]
        return below[-1] if below else sorted(buckets)[0]

    def _run_chunked(self, scene, trace_kbps: np.ndarray, method: str,
                     use_elastic: bool, faults: Optional[np.ndarray]
                     ) -> Dict[str, np.ndarray]:
        """The episode program dispatched per trace chunk.  Cross-chunk
        carry (elastic EMA/debt, reducto reference, fault reconnect edges
        at chunk boundaries) re-seeds fresh each chunk — the documented
        approximation that buys degraded-mode progress when the whole-trace
        program is the thing failing."""
        T = len(trace_kbps)
        step = self._chunk_len(T)
        parts: List[Dict[str, np.ndarray]] = []
        for i0 in range(0, T, step):
            i1 = min(i0 + step, T)
            parts.append(self.system.run_episode(
                scene, np.asarray(trace_kbps)[i0:i1], method, use_elastic,
                faults=None if faults is None else faults[i0:i1]))
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
