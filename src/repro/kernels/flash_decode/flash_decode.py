"""Pallas TPU kernel: flash-decode (online-softmax single-token attention).

Serving hot spot for the decode_32k / long_500k cells: one query token
attends to a long KV cache.  The cache never fits VMEM, so the kernel
streams KV blocks HBM->VMEM and maintains the online-softmax running
(max, sum, acc) in fp32 scratch; per (batch, kv-head) the query block
(G x hd, <=32 KB) stays resident.

Grid: (B, KV, S/BS) — the S dimension is the innermost (sequential on TPU)
axis; scratch carries the softmax state across S-steps and the output is
written once at the last step.  VMEM per program: BS x hd KV block x2
(K and V) + G x hd accumulators ~= 2 x 512 x 128 x 4B = 512 KB.

The MXU sees (G x hd) @ (hd x BS) and (G x BS) @ (BS x hd) GEMMs — small-M
but well-shaped for GQA groups G in {8, 16}; for G < 8 the VPU path wins and
XLA's fallback (ref.py) is preferable — ops.py picks per shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _fd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, mo_ref, lo_ref,
               m_ref, l_ref, acc_ref, *, block_s: int, scale: float):
    s_idx = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (G, hd)
    k = k_ref[0, :, 0].astype(jnp.float32)            # (BS, hd)
    v = v_ref[0, :, 0].astype(jnp.float32)            # (BS, hd)
    valid_len = len_ref[0]
    pos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (G, BS)
    s = jnp.where(pos < valid_len, s, NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))     # (G,1)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)
        mo_ref[0, 0] = m_ref[...]
        lo_ref[0, 0] = l_ref[...]


def flash_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        kv_valid_len, block_s: int = 512,
                        interpret: bool) -> jax.Array:
    """q (B,1,H,hd); k,v (B,S,KV,hd) -> (B,1,H,hd)."""
    B, _, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    bs = min(block_s, S)
    assert S % bs == 0, (S, bs)
    scale = 1.0 / float(hd) ** 0.5
    qg = q.reshape(B, KV, G, hd)
    vlen = jnp.full((1,), kv_valid_len, jnp.int32) if jnp.ndim(kv_valid_len) == 0 \
        else kv_valid_len.reshape(1).astype(jnp.int32)

    kern = functools.partial(_fd_kernel, block_s=bs, scale=scale)
    out, m_out, l_out = pl.pallas_call(
        kern,
        grid=(B, KV, S // bs),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # valid len
            pl.BlockSpec((1, 1, G, hd), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, bs, 1, hd), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, bs, 1, hd), lambda b, h, s: (b, s, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, s: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
            jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, G, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(vlen, qg, k, v)
    return out.reshape(B, 1, H, hd), m_out, l_out
