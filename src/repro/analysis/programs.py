"""The audited-executable registry: every program the static auditor
traces/lowers, with its abstract ``ShapeDtypeStruct`` arguments.

One canonical deployment config (the scenario harness's: default
``SceneConfig`` fleet, ``eval_frames=3``, the pinned ``W_CAP_KBPS`` DP
capacity) parameterizes every entry, so the manifest fingerprints the
exact executables the differential suites compile — same statics, same
cache keys in ``fleet._EXEC_CACHE``.  Args are abstract: building a
program here allocates nothing and runs nothing; ``fn.lower(*abs_args)``
/ ``jax.make_jaxpr(fn)(*abs_args)`` are the only consumers.

The registry enumerates:

* ``episode/<method>/b<bucket>`` — the whole-trace scan executable per
  (method, trace-length bucket): exactly ``len(METHODS) x
  len(fleet.EPISODE_BUCKETS)`` entries, the matrix whose recompile-free
  serving the harness asserts at runtime;
* ``slot_step/unified`` — the donated unified fleet slot-step the
  host-control reference loop dispatches.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

METHODS: Tuple[str, ...] = ("deepstream", "jcab", "reducto", "static")

# the scenario harness's pinned DP capacity (tests/harness.py W_CAP_KBPS);
# tests/test_audit.py asserts the two constants stay equal so the manifest
# keeps fingerprinting the programs the matrix suites actually compile
W_CAP_KBPS = 8000.0

# harness systems score 3 frames per segment (tests/harness.py build_system)
EVAL_FRAMES = 3

@dataclasses.dataclass(frozen=True)
class Program:
    """One audited executable: the cached jitted callable plus the
    abstract args that lower it.  ``donated`` is the EXPECTED set of
    donated flattened-argument indices (what ``lowered.args_info`` must
    report); ``timed`` marks programs whose body runs inside a
    transfer-guarded timed region (the no-host-callback rules apply)."""
    name: str
    kind: str                      # "episode" | "slot_step"
    fn: Callable
    abs_args: Tuple[Any, ...]
    donated: Tuple[int, ...] = ()
    timed: bool = True


def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _abstract(tree):
    """Concrete (tiny) pytree -> ShapeDtypeStruct pytree."""
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda x: _sds(jnp.shape(x), jnp.result_type(x)), tree)


class Canonical:
    """The one deployment config every audited program is built at."""

    def __init__(self) -> None:
        import jax.numpy as jnp
        from repro.common.params import abstract_params
        from repro.core import allocation as alloc
        from repro.core import elastic as elastic_mod
        from repro.core import fleet as fleet_mod
        from repro.core import utility as util_mod
        from repro.core.codec import CodecConfig
        from repro.core.elastic import ElasticConfig
        from repro.data.synthetic import DeviceSceneParams, SceneConfig
        from repro.models.detector import detector_defs

        # seed normalized to 0 exactly like fleet_episode's cache key
        self.scfg = SceneConfig(seed=0)
        self.ccfg = CodecConfig()
        self.ecfg = ElasticConfig()
        self.C = self.scfg.num_cameras
        self.H, self.W = self.scfg.height, self.scfg.width
        self.N = self.scfg.frames_per_segment
        self.J = len(self.ccfg.bitrates_kbps)
        self.G = fleet_mod.gt_capacity(
            self.scfg.max_objects + self.scfg.num_stationary)
        self.bitrates = tuple(int(b) for b in self.ccfg.bitrates_kbps)
        self.resolutions = tuple(float(r) for r in self.ccfg.resolutions)
        self.block_size = 8
        self.conf_thresh = 0.4
        # the harness pin covers every family's traces plus the elastic
        # borrow, so w_cap is trace-independent — the whole matrix shares
        # one static capacity (and therefore one compiled program)
        borrow = self.ecfg.budget_kbits / self.ccfg.slot_seconds
        self.w_cap = alloc.trace_capacity(
            self.bitrates, np.zeros(1), self.C,
            elastic_borrow_kbps=borrow, pin_kbps=W_CAP_KBPS)

        f32, i32 = jnp.float32, jnp.int32
        self._f32, self._i32, self._bool = f32, i32, jnp.bool_
        self.key = _sds((2,), jnp.uint32)
        self.server = abstract_params(detector_defs("server"))
        self.light = abstract_params(detector_defs("light"))
        self.mlp = abstract_params(util_mod.utility_mlp_defs())
        self.est0 = _abstract(elastic_mod.init_state_jax())
        self.scene_params = DeviceSceneParams(
            backgrounds=_sds((self.C, self.H, self.W), f32),
            stat_boxes=_sds((self.C, self.scfg.num_stationary, 4), f32),
            stat_valid=_sds((self.C, self.scfg.num_stationary), jnp.bool_),
            offsets=_sds((self.C, 2), f32),
            lags=_sds((self.C,), i32),
            cam_ids=_sds((self.C,), i32),
            objects=_sds((self.scfg.max_objects, 10), f32))

    # -- per-kind builders ----------------------------------------------------

    def episode_statics(self, method: str) -> Dict[str, Any]:
        return dict(
            method=method, scfg=self.scfg, ccfg=self.ccfg, ecfg=self.ecfg,
            bitrates=self.bitrates, resolutions=self.resolutions,
            use_elastic=method == "deepstream", use_kernel=True,
            w_cap=int(self.w_cap), num_cams=self.C, c_pad=self.C,
            eval_frames=EVAL_FRAMES, block_size=self.block_size,
            conf_thresh=self.conf_thresh, gt_pad=self.G, sharded=False,
            checked=False, pipelined=True)

    def episode_args(self, method: str, bucket: int) -> Tuple[Any, ...]:
        """Abstract args in ``fleet._episode_impl`` positional order, at
        the shapes ``fleet_episode`` prepares for a bucketed trace."""
        f32, i32, b = self._f32, self._i32, self._bool
        C, T = self.C, bucket
        deep = method == "deepstream"
        return (
            self.server, self.light, self.mlp if deep else {},
            _sds((C, self.J), f32), _sds((C, self.J), f32),   # jcab tables
            _sds((C,), f32),                                  # lam
            self.scene_params,
            _sds((T,), f32), _sds((T, C), b), _sds((T,), b),  # trace/live/active
            _sds((T,), i32), _sds((), i32), _sds((), i32),    # t_idx/t_first/t_len
            self.key, self.key,                               # key0, skey
            _sds((), f32), _sds((), f32),                     # tau_wl, tau_wh
            self.est0,
            _sds((C, self.H, self.W), f32),                   # ref0
            _sds((C,), b))                                    # live_prev0

    def slot_step_args(self) -> Tuple[Any, ...]:
        f32, b = self._f32, self._bool
        C, N, H, W, G = self.C, self.N, self.H, self.W, self.G
        bs = self.block_size
        return (
            self.server, _sds((C, N, H, W), f32),
            _sds((C, H // bs, W // bs), b),
            _sds((C,), f32), _sds((C,), f32),                 # b, r
            _sds((C, 2), np.uint32),                          # per-camera keys
            _sds((C, N), b),
            _sds((C, N, G, 4), f32), _sds((C, N, G), b),      # gt boxes/valid
            _sds((C,), b))                                    # live


def _donated_leaf_indices(abs_args: Sequence[Any],
                          donate_argnums: Sequence[int]) -> Tuple[int, ...]:
    """Flattened-leaf indices covered by the donated TOP-LEVEL positions —
    the layout ``lowered.args_info`` reports, derived from the arg tree so
    a param-tree size change can never silently shift the expectation."""
    import jax
    out, base = [], 0
    for i, a in enumerate(abs_args):
        n = len(jax.tree.leaves(a))
        if i in donate_argnums:
            out.extend(range(base, base + n))
        base += n
    return tuple(out)


# slot-step donated top-level positions: frames, gt_boxes, gt_valid in the
# (server_params, frames, masks, b, r, keys, keep, gt_boxes, gt_valid, live)
# argument list — fleet._build_executable's donate_argnums claim (PRs 2-4)
SLOT_STEP_DONATE_ARGNUMS: Tuple[int, ...] = (1, 7, 8)


def get_programs(kinds: Optional[Sequence[str]] = None,
                 canon: Optional[Canonical] = None) -> Tuple[Program, ...]:
    """Build the full audited-program registry (or the ``kinds`` subset).

    Reuses ``fleet``'s own executable caches — the audited callables ARE
    the cached jitted programs the runtime dispatches, not re-wrapped
    copies, so a donation/static drift there is a drift here."""
    from repro.core import fleet as fleet_mod

    canon = canon or Canonical()
    want = set(kinds) if kinds is not None else None
    progs = []

    def take(kind: str) -> bool:
        return want is None or kind in want

    if take("episode"):
        for method in METHODS:
            statics = canon.episode_statics(method)
            fn = fleet_mod._get_episode_executable(None, **statics)
            for bucket in fleet_mod.EPISODE_BUCKETS:
                progs.append(Program(
                    name=f"episode/{method}/b{bucket}", kind="episode",
                    fn=fn, abs_args=canon.episode_args(method, bucket)))
    if take("slot_step"):
        args = canon.slot_step_args()
        fn = fleet_mod._get_executable(
            None, canon.ccfg, EVAL_FRAMES, canon.block_size,
            canon.conf_thresh, True, True, False)
        progs.append(Program(
            name="slot_step/unified", kind="slot_step", fn=fn, abs_args=args,
            donated=_donated_leaf_indices(args, SLOT_STEP_DONATE_ARGNUMS)))
    return tuple(progs)
