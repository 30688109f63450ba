"""DeepStream end-to-end control loop + baselines (paper sections 3-5, Fig. 1).

Per time slot:
  camera side: ROIDet -> (ROI mask, a_i, c_i); masked ("cropped") encode at
  the assigned (b_i, r_i).
  server side: elastic adjustment -> bandwidth allocation (utility-MLP + DP
  knapsack) -> decode -> server detector -> per-camera F1; slot utility =
  sum_i lambda_i F1_i.

Three execution modes (``SystemConfig.batched`` / ``SystemConfig.episode``):
  * episode (``SystemConfig.episode=True``) — the device-control path and
    the one the chip serves (``run_episode``): segment generation moves on
    device (``data.synthetic.DeviceScene`` / ``segments_device``) and the
    ENTIRE N-slot trace — ROIDet, the traced control step
    (``fleet._control_impl``), reducto's keep decision and the slot-step —
    executes as one ``fleet.fleet_episode`` lax.scan per method, under
    ``jax.transfer_guard("disallow")`` both directions with no scoped
    exemptions; stacked logs are harvested once at episode end.
    Trace lengths are BUCKETED (``SystemConfig.episode_buckets``): T pads
    up to a power-of-two bucket with masked tail slots so one executable
    per (method, bucket) serves every T.  A padded slot runs the per-slot
    program on dead inputs but cannot advance observable state — the
    returned codec key chain and elastic state come from the last active
    slot, its logs are sliced off before harvest, and the DP capacity is
    computed from the active prefix (``allocation.trace_capacity``), so
    bucketing never changes a pick (see ``fleet.bucket_len``).
  * batched (``episode=False``, the default) — the host-control
    reference: one compiled encode->detect->score->reuse-mix slot-step over
    the camera axis (``core.fleet.fleet_slot_step``) shared by ALL methods
    (method routing is data, not Python branches), with the control loop
    (elastic + utility table + allocation) in host numpy
    (``_slot_allocation``) — independent of the traced control the episode
    runs, which is what makes it a reference.  One packed (a, c) fetch and
    one log harvest per slot; each slot is harvested before the next
    dispatches.  With >1 device the camera axis is shard_map'd over a
    ("camera",) mesh (``SystemConfig.shard``).
  * sequential (``batched=False``) — the original per-camera Python loop,
    kept as the equivalence/benchmark baseline.  All modes consume PRNG
    keys in the same order, so F1/size logs agree within float tolerance
    — including reducto, whose sequential arm encodes fixed-shape segments
    with a traced kept-frame count and tracks the same cross-slot
    reference frame, so every arm draws identical coding noise.

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
(diagnostics lane); retries and degraded-mode fallback live in
``serve.stream``'s watchdog ladder.
"""
from __future__ import annotations

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
from repro.kernels.edge_motion import ops as em_ops
from repro.models import detector as det
from repro.sharding import rules as shard_rules


# block-motion mass above which a frame counts as "changed" (reducto keep
# rule) — one constant shared by the sequential, batched and episode paths
# (they must stay bit-in-sync for the cross-mode equivalence guarantees);
# lives in ``fleet`` so traced programs need no import cycle
MOTION_KEEP_THRESH = fleet_mod.MOTION_KEEP_THRESH


# -- device-to-host accounting ------------------------------------------------
# Every D2H fetch the run loops perform goes through ``_d2h`` so the "zero
# per-slot sync" guarantee of episode mode is CHECKABLE: on TPU/GPU the
# episode runs under ``jax.transfer_guard("disallow")`` with no exemption
# (its one harvest happens after the trace); on the CPU backend D2H is
# zero-copy and the guard never fires, so the per-category counters
# (``common.obs``, categories listed there) are the proof instead.
# Episode runs must leave 'keep' and 'control' at zero and add exactly TWO
# 'harvest' fetches per run (the stacked F1/size pack and the stacked
# control pack), independent of slot count.


def _d2h(x, kind: str) -> np.ndarray:
    obs.count_fetch(kind)
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
    # trace's max and every new trace re-traces the episode programs
    # (w_cap is a jit static).  The scenario harness pins it so a
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
        if self.checked:
            self.shard = "off"
            self.use_kernels = False
        if self.episode and not self.batched:
            raise ValueError("episode mode requires batched=True")

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
        # post-run serving carry (EpisodeCarry) recorded by run_episode —
        # what serve.stream checkpoints
        self.last_carry: Optional[EpisodeCarry] = None
        # method -> (host values, device leaves, EpisodeInvariants) the
        # episode windows reuse (see _episode_invariants)
        self._invariants: Dict[str, Tuple[Any, List[Any],
                                          fleet_mod.EpisodeInvariants]] = {}
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
        device sync — the batched slot loop fetches only the packed (a, c)
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
            mesh=self.mesh, with_reuse=with_reuse,
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
                      reconnect: Optional[np.ndarray] = None) -> jax.Array:
        """Traced reducto keep decision for the batched loop: motion ->
        keep-flags -> next-slot reference, ONE device dispatch with ZERO
        host fetches (kept/missed frame selection happens inside the
        slot-step program via ``fleet.keep_selection``).  The cross-slot
        reference (last kept frame) is threaded through
        ``self._reducto_ref``; ``first_slot`` marks the run's first slot (no
        reference yet); ``reconnect`` (C,) bool marks cameras whose
        reference went stale while dead — they re-seed from frame 0 like a
        run start."""
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
        return keep

    # -- online loop -------------------------------------------------------------

    def _jcab_utility_table(self) -> Tuple[np.ndarray, np.ndarray]:
        """jcab's content-agnostic (util (C, J), best_res (C, J)) tables —
        the same (J, R) profiled table folded and lambda-weighted for every
        camera.  The ONE construction both control paths use: the host
        allocator calls it per slot, the episode invariants upload it once."""
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
        Log-equivalent to the host-control batched ``run()`` over the same
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
        if not self.cfg.batched:
            raise ValueError("episode mode requires batched=True")
        if not isinstance(scene, DeviceScene):
            raise TypeError("run_episode needs a DeviceScene (device-side "
                            f"segment generation), got {type(scene)!r}")
        assert scene.G == self._G, (scene.G, self._G)
        C = self.cfg.scene.num_cameras
        lam = self.cfg.lam()
        t_begin = scene._t
        # three host spans per dispatch: serve.prep (the window's operands:
        # the cached invariants plus ONE batched put of its host values,
        # and the executable lookup), serve.device (the episode until its
        # logs are ready) and serve.harvest (the two fetches and the log
        # rows)
        with obs.span("serve.prep"):
            args = self._episode_args(method, scene, trace_kbps,
                                      faults=faults, carry=carry)
            launch = fleet_mod.prepare_episode(
                method, args, len(trace_kbps), codec_cfg=self.cfg.codec,
                scene_cfg=scene.cfg, ecfg=self.cfg.elastic,
                bitrates=tuple(self.cfg.codec.bitrates_kbps),
                resolutions=tuple(self.cfg.codec.resolutions),
                use_elastic=use_elastic,
                w_cap=self._w_cap(trace_kbps, use_elastic), num_cams=C,
                eval_frames=self.cfg.eval_frames,
                block_size=self.cfg.block_size,
                use_kernel=self.cfg.use_kernels, gt_pad=self._G,
                mesh=self.mesh, checked=self.cfg.checked,
                pipelined=self.cfg.episode_pipelined)
        # the whole trace runs under jax.transfer_guard("disallow") in BOTH
        # directions with NO scoped exemptions, and blocks — the structural
        # zero-per-slot-transfer guarantee of episode mode
        with obs.span("serve.device"):
            out = launch()
        with obs.span("serve.harvest"):
            # advance the scene cursor exactly like T batched segment()
            # calls would — a reused scene continues, matching the
            # batched reference
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
        if self.cfg.checked and not (np.isfinite(W_t) and W_t >= 0.0):
            # the host mirror of the traced control step's checkify check
            raise ValueError("control: bandwidth sample not "
                             "finite/non-negative")
        if live is None:
            live = np.ones(C, bool)
        masks = None
        extra = area = 0.0

        if method in ("deepstream", "deepstream_no_elastic"):
            roi = self.camera_features(frames, block=not self.cfg.batched)
            # the host control path's ONE camera-side sync: packed (a_i, c_i)
            # scalars — the fetch the episode scan does not make (counted)
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

    def _w_cap(self, trace_kbps: np.ndarray, use_elastic: bool) -> int:
        """The ONE static DP capacity covering every slot of a run: the
        trace max plus the maximum elastic borrow.  It comes from the
        ACTIVE (unpadded) trace — episode bucketing appends zero-Kbps tail
        slots after this runs, so a bucketed run solves the exact DP the
        unbucketed program would.  ``cfg.w_cap_kbps`` optionally pins it so
        different traces share one compiled episode program (w_cap is a
        jit static)."""
        cfgc = self.cfg.codec
        borrow = (self.cfg.elastic.budget_kbits / cfgc.slot_seconds
                  if use_elastic else 0.0)
        return alloc.trace_capacity(
            tuple(int(b) for b in cfgc.bitrates_kbps), trace_kbps,
            self.cfg.scene.num_cameras, elastic_borrow_kbps=borrow,
            pin_kbps=self.cfg.w_cap_kbps)

    def _episode_invariants(self, method: str, scene: DeviceScene
                            ) -> fleet_mod.EpisodeInvariants:
        """The episode operands every window of a stream shares
        (``fleet.EpisodeInvariants``), built once per method on this
        system's device or mesh and reused while what they were built from
        holds: the host values (camera weights, elastic thresholds, the
        jcab table, the codec resolutions) compared by value, the device
        ones (detector and MLP parameters, scene params and key) by
        identity of the arrays, which are immutable and held by the cache,
        so a rebuild follows any reassignment, e.g. ``tau_wl`` after
        re-profiling or a new scene."""
        deep = method in ("deepstream", "deepstream_no_elastic")
        jcab = method == "jcab"
        host_key = (self.cfg.lam().tobytes(), float(self.tau_wl),
                    float(self.tau_wh), tuple(self.cfg.codec.resolutions),
                    (self.jcab_table.shape, self.jcab_table.tobytes())
                    if jcab else None)
        mlp = self.mlp if deep else None
        dev_key = jax.tree.leaves((self.server, self.light, mlp,
                                   scene.params, scene.key))
        hit = self._invariants.get(method)
        if (hit is not None and hit[0] == host_key
                and len(hit[1]) == len(dev_key)
                and all(a is b for a, b in zip(hit[1], dev_key))):
            obs.count_operand_cache("reused")
            return hit[2]
        util, best_res = self._jcab_utility_table() if jcab else (None, None)
        inv = fleet_mod.episode_invariants(
            server_params=self.server, light_params=self.light,
            mlp_params=mlp, jcab_util=util, jcab_res=best_res,
            lam=self.cfg.lam(), scene_params=scene.params, skey=scene.key,
            tau_wl=self.tau_wl, tau_wh=self.tau_wh,
            num_cams=self.cfg.scene.num_cameras,
            num_bitrates=len(self.cfg.codec.bitrates_kbps),
            frame_hw=(scene.cfg.height, scene.cfg.width), mesh=self.mesh)
        self._invariants[method] = (host_key, dev_key, inv)
        obs.count_operand_cache("built")
        return inv

    def _episode_args(self, method: str, scene: DeviceScene,
                      trace_kbps: np.ndarray,
                      faults: Optional[np.ndarray] = None,
                      carry: Optional[EpisodeCarry] = None
                      ) -> fleet_mod.EpisodeArgs:
        """One window's episode arguments: the cached invariants plus ONE
        batched put of the window's host values (``fleet.episode_args``);
        the run key and a carried elastic state and reference pass
        through."""
        return fleet_mod.episode_args(
            self._episode_invariants(method, scene), trace_kbps,
            key0=self._key, num_cams=self.cfg.scene.num_cameras,
            t_start=scene._t, buckets=self.cfg.episode_buckets,
            mesh=self.mesh, faults=faults,
            est0=None if carry is None else carry.est,
            ref0=None if carry is None else carry.ref,
            live_prev0=None if carry is None else carry.live_prev,
            t_first=None if carry is None else carry.t_first)

    def _run_batched(self, scene: MultiCameraScene, trace_kbps: np.ndarray,
                     method: str, use_elastic: bool,
                     faults: Optional[np.ndarray] = None
                     ) -> Dict[str, np.ndarray]:
        """Host-control fleet loop: every method routes through ONE compiled
        slot-step, and the numpy reference control path
        (``_slot_allocation``) syncs on one packed (a, c) fetch per slot.
        Each slot's packed (F1, sizes) is harvested before the next slot
        dispatches.  ``faults`` (T, C) bool threads the liveness mask
        through control, keep-flags and the slot-step (same executables)."""
        lam = self.cfg.lam()
        C = self.cfg.scene.num_cameras
        est = ElasticState()
        logs = {k: [] for k in ("utility", "mean_f1", "bytes", "W", "extra",
                                "alloc_kbps", "area")}
        self._reducto_ref = None
        live_prev = np.ones(C, bool)
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
            b, r, masks, extra, area, alloc_kbps, est = self._slot_allocation(
                method, frames, W_t, est, use_elastic, live=live_t,
                reconnect=bool(reconnect_vec.any()))
            keep = None
            if method == "reducto":
                keep = self._reducto_keep(
                    frames, t == 0,
                    reconnect=None if faults is None else reconnect_vec)
            out = self._slot_dispatch(
                frames, gts, masks, b, r, keys=keys, keep=keep, gt_dev=gt_dev,
                live=None if faults is None else jnp.asarray(live_t))
            live_prev = live_t
            pack = _d2h(out.host_pack, "harvest")
            logs["utility"].append(float(np.dot(lam, pack[0])))
            logs["mean_f1"].append(float(np.mean(pack[0])))
            logs["bytes"].append(float(np.sum(pack[1])))
            logs["W"].append(W_t)
            logs["extra"].append(extra)
            logs["area"].append(area)
            logs["alloc_kbps"].append(alloc_kbps)
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
            keep = _motion_keep(_d2h(jnp.sum(sc, axis=(1, 2)), "keep"), first)
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
