"""int8 error-feedback gradient compression for data-parallel reduction.

For cross-pod (DCN) gradient sync the wire bytes dominate: int8 quantization
cuts them 4x vs fp32 / 2x vs bf16, and error feedback (Seide et al., 1-bit
SGD lineage) keeps SGD convergence by carrying quantization residuals into
the next step.

Implementation: a ``shard_map`` over the DP axis; each device quantizes its
local gradient shard with a per-tensor scale, ``psum``s the int32-accumulated
values, and dequantizes.  Residual state lives alongside the optimizer state.
Used by the pure-DP trainers (utility MLP / detector at fleet scale) and
available to the backbone trainer on the pod axis (``opt.compress_grads``).
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def init_residuals(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum(grads: Any, residuals: Any, mesh: Mesh, axis: str = "data"
                    ) -> Tuple[Any, Any]:
    """All-reduce-mean `grads` over `axis` with int8 error feedback.

    grads: pytree of per-device *replicated-shape* gradients that differ in
    value across `axis` (the pure-DP case).  Returns (mean grads, residuals).
    """
    n = mesh.shape[axis]

    def one(g, r):
        def local(gl, rl):
            x = gl.astype(jnp.float32) + rl
            q, scale = _quantize(x)
            err = x - q.astype(jnp.float32) * scale
            total = jax.lax.psum(q.astype(jnp.int32), axis)
            # scales differ per device: reduce with max for a safe bound
            smax = jax.lax.pmax(scale, axis)
            mean = total.astype(jnp.float32) * smax / n
            return mean, err

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(*([None] * g.ndim)), P(*([None] * g.ndim))),
            out_specs=(P(*([None] * g.ndim)), P(*([None] * g.ndim))),
            check_vma=False,
        )(g, r)

    flat_g, tdef = jax.tree.flatten(grads)
    flat_r = jax.tree.leaves(residuals)
    out = [one(g, r) for g, r in zip(flat_g, flat_r)]
    return (jax.tree.unflatten(tdef, [o[0] for o in out]),
            jax.tree.unflatten(tdef, [o[1] for o in out]))


def wire_bytes(params: Any, dtype_bytes: int = 4) -> Tuple[int, int]:
    """(uncompressed, compressed) per-step DP wire bytes for reporting."""
    n = sum(int(jnp.size(p)) for p in jax.tree.leaves(params))
    return n * dtype_bytes, n  # int8 payload (+ negligible scales)
