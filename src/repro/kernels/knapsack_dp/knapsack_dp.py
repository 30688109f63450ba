"""Pallas TPU kernel: multiple-choice knapsack DP sweep (section 5.2).

The DP has a true sequential dependency over cameras, but each camera's
update is a W-wide max-plus over J shifted copies of the value row — pure
VPU work on a row that stays resident in vregs for the whole sweep.  The
HBM traffic is just the (I, J) utility table in and the (I, W+1) choice
table out; a jnp formulation re-materializes the O(W x J) candidate matrix
per camera in HBM.

The shift-by-cost_j is a lane rotation of the (1, Wpad) value row by the
runtime cost (``pltpu.roll``, which Mosaic lowers for a dynamic shift);
lanes below the cost, where the rotation wraps, are masked to NEG.  The
utility table and the costs are scalars read per step, so they ride in
SMEM.

Grid: () — one program per allocation problem; fleets batch via vmap
(DeepStream solves one problem per time slot; a datacenter ingest tier
solves thousands concurrently).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _dp_kernel(util_ref, cost_ref, vals_ref, choice_ref, *, num_cams: int,
               num_opts: int, wpad: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, wpad), 1)

    def cam_body(i, v):
        best = jnp.full((1, wpad), NEG, jnp.float32)
        arg = jnp.zeros((1, wpad), jnp.int32)
        for j in range(num_opts):                      # J static, unrolled
            c = cost_ref[j]
            # shifted[w] = v[w - c]; w < c has no source (NEG, like ref.py)
            cand = jnp.where(lane < c, NEG,
                             pltpu.roll(v, c, 1) + util_ref[i, j])
            take = cand > best
            best = jnp.where(take, cand, best)
            arg = jnp.where(take, j, arg)
        choice_ref[pl.ds(i, 1), :] = arg
        return best

    vals_ref[...] = jax.lax.fori_loop(
        0, num_cams, cam_body, jnp.zeros((1, wpad), jnp.float32))


def knapsack_dp_pallas(util: jax.Array, costs: jax.Array, W: int, *,
                       interpret: bool):
    """util (I, J) fp32, costs (J,) int32, W capacity (grid units).
    Returns (values (W+1,), choices (I, W+1) int32)."""
    I, J = util.shape
    wpad = ((W + 1 + 127) // 128) * 128
    kern = functools.partial(_dp_kernel, num_cams=I, num_opts=J, wpad=wpad)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vals, choices = pl.pallas_call(
        kern,
        in_specs=[smem, smem],
        out_shape=[jax.ShapeDtypeStruct((1, wpad), jnp.float32),
                   jax.ShapeDtypeStruct((I, wpad), jnp.int32)],
        interpret=interpret,
        name="knapsack_dp",
    )(util.astype(jnp.float32), costs.astype(jnp.int32))
    return vals[0, :W + 1], choices[:, :W + 1]
