"""jit'd wrapper for the knapsack DP: kernel/oracle dispatch + backtracking."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels
from repro.kernels.knapsack_dp import ref
from repro.kernels.knapsack_dp.knapsack_dp import knapsack_dp_pallas


def bucket_capacity(Wg: int) -> int:
    """Bucket a grid capacity up to the next multiple of 128 (the kernel's
    native row padding) minus 1 — the ONE formula both the per-slot host
    ``solve`` and the whole-trace ``allocation.dp_capacity`` use, so their
    compiled sweeps stay shape-aligned."""
    return ((Wg + 1 + 127) // 128) * 128 - 1


@functools.partial(jax.jit, static_argnames=("W", "use_kernel"))
def solve_values(util: jax.Array, costs: jax.Array, W: int,
                 use_kernel: bool = True) -> Tuple[jax.Array, jax.Array]:
    if use_kernel:
        return knapsack_dp_pallas(util, costs, W,
                                  interpret=kernels.pallas_interpret())
    return ref.knapsack_dp_ref(util, costs, W)


def solve_device(util: jax.Array, costs: jax.Array, Wg: jax.Array, *,
                 w_cap: int, use_kernel: bool = True
                 ) -> Tuple[jax.Array, jax.Array]:
    """Jit-friendly solve: DP sweep at the STATIC bucketed capacity ``w_cap``
    plus the traced on-device backtrack bounded by the traced capacity
    ``Wg`` (grid units, <= w_cap).  Returns (picks (I,) int32, total) as
    device arrays — the device-resident allocator's entry, callable from
    inside a jitted control program with zero host round-trips.

    Value-row entries w <= Wg don't depend on the capacity bound, so the
    result equals ``solve(util, costs, Wg)`` exactly while every slot of a
    bandwidth trace shares ONE compiled sweep."""
    costs = jnp.asarray(costs, jnp.int32)
    vals, choices = solve_values(jnp.asarray(util, jnp.float32), costs,
                                 int(w_cap), use_kernel)
    return ref.backtrack_jax(choices, costs, vals,
                             jnp.asarray(Wg, jnp.int32))


def solve(util: np.ndarray, costs: np.ndarray, W: int,
          use_kernel: bool = True) -> Tuple[np.ndarray, float]:
    """Full solve: DP sweep + backtrack.  Returns (per-camera option index
    picks (I,), achieved total utility).

    The static capacity is bucketed up to the next multiple of 128 (the
    kernel's native row padding) and the exact-W columns sliced outside:
    value row entries w <= W don't depend on the capacity bound, so results
    are identical while every slot of a bandwidth trace shares ONE compiled
    sweep instead of recompiling per distinct W."""
    Wb = bucket_capacity(W)
    vals, choices = solve_values(jnp.asarray(util, jnp.float32),
                                 jnp.asarray(costs, jnp.int32), int(Wb),
                                 use_kernel)
    vals = np.asarray(vals)[:W + 1]
    choices = np.asarray(choices)[:, :W + 1]
    picks, _ = ref.backtrack(choices, np.asarray(costs), vals)
    total = float(vals.max())
    return picks, total
