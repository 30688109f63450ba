"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Mesh axes:
  single-pod : ("data", "model")                    16 x 16 = 256 chips
  multi-pod  : ("pod", "data", "model")             2 x 16 x 16 = 512 chips

Weight sharding strategy (Megatron TP x FSDP):
  * "model"-group logical axes (mlp, heads-features, vocab, experts) shard the
    tensor-parallel dimension of each matrix;
  * "embed"-group logical axes FSDP-shard the complementary matrix dimension
    over the data axis (and optionally the pod axis for >=400B archs);
  * activations shard batch over (pod, data) and keep features unsharded at
    block boundaries (GSPMD propagates interior shardings).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.params import ParamDef, is_def


def camera_mesh(min_devices: int = 2) -> Optional[Mesh]:
    """1-D ("camera",) mesh over every local device, or None below
    ``min_devices`` (single-device runs skip shard_map entirely).

    The fleet slot-step, fleet ROIDet and the profiling sweep all shard their
    leading camera axis over this mesh; on CPU, 8 fake host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` exercise the same
    code path a TPU slice would.
    """
    import numpy as np
    devs = jax.devices()
    if len(devs) < min_devices:
        return None
    return Mesh(np.asarray(devs), ("camera",))


def pad_cameras(n: int, mesh: Optional[Mesh]) -> int:
    """Smallest multiple of the camera-mesh size >= n (n itself when
    unsharded) — shard_map needs the leading axis divisible by the mesh."""
    if mesh is None:
        return n
    d = mesh.shape["camera"]
    return -(-n // d) * d


def mesh_cache_key(mesh: Optional[Mesh]) -> Optional[Tuple[int, ...]]:
    """Hashable identity of a mesh for executable caches (None = unsharded)."""
    return None if mesh is None else tuple(d.id for d in mesh.devices.flat)


def sharded_jit(impl, mesh: Optional[Mesh], in_specs, out_specs,
                donate_argnums=(), check: bool = False):
    """The one builder every fleet executable (slot-step, fleet ROIDet,
    fleet motion) goes through: shard_map over the camera mesh when one is
    given, then jit with optional buffer donation."""
    if mesh is not None:
        impl = jax.shard_map(impl, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check)
    return jax.jit(impl, donate_argnums=donate_argnums)


_SHARDED_JIT_CACHE: dict = {}


def cached_sharded_jit(fn, statics: dict, mesh: Optional[Mesh], in_specs,
                       out_specs, donate_argnums=()):
    """Get-or-build the ``sharded_jit`` of ``partial(fn, **statics)``, cached
    per (fn, mesh, statics) so repeated wrapper calls reuse one executable.
    ``fn`` must be a module-level function (stable identity) and every static
    value hashable."""
    key = (fn, mesh_cache_key(mesh), tuple(sorted(statics.items())),
           tuple(donate_argnums))
    got = _SHARDED_JIT_CACHE.get(key)
    if got is None:
        got = _SHARDED_JIT_CACHE[key] = sharded_jit(
            functools.partial(fn, **statics), mesh, in_specs, out_specs,
            donate_argnums)
    return got


def pad_leading(x, n: int, fill=0) -> jax.Array:
    """Pad a camera-leading array to n rows with `fill` (inert cameras the
    sharded executables compute and the wrappers slice back off)."""
    x = jnp.asarray(x)
    if x.shape[0] == n:
        return x
    pad = jnp.full((n - x.shape[0],) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, pad], axis=0)

# logical axis name -> mesh axis (or tuple of mesh axes)
def rules(mesh: Mesh, fsdp_over_pod: bool = False, policy: str = "2d"):
    axes = mesh.axis_names
    has_pod = "pod" in axes
    all_axes = tuple(a for a in ("pod", "data", "model") if a in axes)
    non_weight = ("layers", "norm", "state", "conv", "act_seq", "act_embed",
                  "cache_seq")
    if policy == "dp":
        # small-model policy: replicate all weights, DP over every axis
        return {k: () for k in (
            "embed", "mlp", "heads", "kv_heads", "vocab", "experts") + non_weight} | {
            "batch": all_axes, "cache_batch": all_axes}
    if policy == "fsdp":
        # ZeRO-style: body matrices sharded on their "embed" dim over the data
        # axes (per-layer all-gather, grad reduce-scatter), NO tensor
        # parallelism on the body — but the embedding/unembed stay
        # vocab-parallel over "model" (Megatron-style): a 256k-vocab unembed
        # computed unsharded would add ~2 TFLOP/device (measured, see
        # EXPERIMENTS section Perf seamless-3).
        fsdp_t = ("pod", "data") if has_pod else ("data",)
        return {k: () for k in (
            "mlp", "heads", "kv_heads", "experts") + non_weight} | {
            "embed": fsdp_t, "vocab": ("model",),
            "batch": all_axes, "cache_batch": all_axes}
    fsdp: Tuple[str, ...] = ("data",)
    if fsdp_over_pod and has_pod:
        fsdp = ("pod", "data")
    batch: Tuple[str, ...] = ("pod", "data") if has_pod else ("data",)
    return {
        # weights
        "embed": fsdp,          # FSDP axis of every matrix
        "mlp": ("model",),
        "heads": ("model",),     # flattened q-features (H*hd)
        "kv_heads": ("model",),  # flattened kv-features (KV*hd)
        "vocab": ("model",),
        "experts": ("model",),
        "layers": (),            # scan-stacked layer axis: never sharded
        "norm": (),
        "state": (),
        "conv": (),
        # activations
        "batch": batch,
        "act_seq": (),
        "act_embed": (),
        "cache_batch": batch,
        "cache_seq": (),
    }


def spec_for(d: ParamDef, mesh: Mesh, fsdp_over_pod: bool = False,
             policy: str = "2d") -> P:
    r = rules(mesh, fsdp_over_pod, policy)
    parts = []
    for ax in d.logical_axes:
        if ax is None:
            parts.append(None)
            continue
        mapped = r.get(ax, ())
        if not mapped:
            parts.append(None)
        elif len(mapped) == 1:
            parts.append(mapped[0])
        else:
            parts.append(tuple(mapped))
    return P(*parts)


def _divisible(size: int, mesh: Mesh, mesh_axes) -> bool:
    if mesh_axes is None:
        return True
    if isinstance(mesh_axes, str):
        mesh_axes = (mesh_axes,)
    n = 1
    for a in mesh_axes:
        n *= mesh.shape[a]
    return size % n == 0


def safe_spec(shape: Sequence[int], spec: P, mesh: Mesh) -> P:
    """Drop mesh axes that don't divide the corresponding dim (GSPMD would pad;
    we prefer explicit replication for clarity)."""
    parts = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        parts.append(ax if _divisible(dim, mesh, ax) else None)
    return P(*parts)


def param_pspecs(defs, mesh: Mesh, fsdp_over_pod: bool = False,
                 policy: str = "2d"):
    """Tree of PartitionSpecs matching a ParamDef tree (divisibility-safe)."""
    def one(d: ParamDef):
        return safe_spec(d.shape, spec_for(d, mesh, fsdp_over_pod, policy), mesh)
    return jax.tree.map(one, defs, is_leaf=is_def)


def param_shardings(defs, mesh: Mesh, fsdp_over_pod: bool = False,
                    policy: str = "2d"):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        param_pspecs(defs, mesh, fsdp_over_pod, policy))


def batch_axes(mesh: Mesh, policy: str = "2d") -> Tuple[str, ...]:
    if policy in ("dp", "fsdp") or policy is True:
        return tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fit_batch_axes(mesh: Mesh, batch: int, policy: str = "2d"
                   ) -> Tuple[str, ...]:
    """Longest prefix of the DP axes whose product divides `batch`."""
    ba = batch_axes(mesh, policy)
    while ba:
        n = 1
        for a in ba:
            n *= mesh.shape[a]
        if batch % n == 0:
            return ba
        ba = ba[:-1]
    return ()


def data_spec(mesh: Mesh, batch: int, *trailing: Optional[str],
              policy: str = "2d") -> P:
    """Spec for (batch, ...) input arrays; shards batch over the largest
    feasible DP-axis prefix, else replicates."""
    ba = fit_batch_axes(mesh, batch, policy)
    first: Optional[object]
    if not ba:
        first = None
    elif len(ba) == 1:
        first = ba[0]
    else:
        first = tuple(ba)
    return P(first, *trailing)


def cache_spec(mesh: Mesh, batch: int, seq: int) -> Tuple[Optional[object], Optional[object]]:
    """(batch_part, seq_part) for KV caches: batch over DP if divisible, else
    sequence over data (long-context, batch=1), else replicated."""
    ba = batch_axes(mesh)
    n = 1
    for a in ba:
        n *= mesh.shape[a]
    if batch % n == 0:
        first = tuple(ba) if len(ba) > 1 else ba[0]
        return first, None
    if seq % mesh.shape["data"] == 0:
        return None, "data"
    return None, None


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint that is a no-op outside a mesh context."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x
