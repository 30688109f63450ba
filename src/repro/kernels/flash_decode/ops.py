"""jit'd wrapper for flash-decode: kernel/oracle dispatch + new-token merge."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels
from repro.kernels.flash_decode import ref
from repro.kernels.flash_decode.flash_decode import flash_decode_pallas


def _kernel_ok(q, k, block_s):
    B, _, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    bs = min(block_s, S)
    return G >= 4 and S % bs == 0 and hd % 8 == 0


@functools.partial(jax.jit, static_argnames=("block_s", "force_kernel"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 kv_valid_len, block_s: int = 512,
                 force_kernel: bool = False) -> jax.Array:
    """Dispatch: kernel when the GQA group is MXU-worthy and S blocks evenly;
    oracle otherwise (small G is VPU-bound — see kernel docstring)."""
    if force_kernel or _kernel_ok(q, k, block_s):
        out, _, _ = flash_decode_pallas(
            q, k, v, kv_valid_len=kv_valid_len,
            block_s=min(block_s, k.shape[1]),
            interpret=kernels.pallas_interpret())
        return out
    return ref.flash_decode_ref(q, k, v, kv_valid_len=kv_valid_len)


@functools.partial(jax.jit, static_argnames=("block_s", "force_kernel"))
def flash_decode_with_new(q: jax.Array, k: jax.Array, v: jax.Array,
                          k1: jax.Array, v1: jax.Array, *, kv_valid_len,
                          block_s: int = 512, force_kernel: bool = False
                          ) -> jax.Array:
    """Decode attention over old cache + one fresh (k1, v1) token: the kernel
    emits its online-softmax stats (m, l), and the new token's contribution
    merges outside — so the 1-token cache write never serializes against the
    multi-GB cache read."""
    B, _, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    if not (force_kernel or _kernel_ok(q, k, block_s)):
        from repro.models.attention import decode_attention_with_new
        return decode_attention_with_new(q, k, v, k1, v1,
                                         kv_valid_len=kv_valid_len)
    out_old, m_old, l_old = flash_decode_pallas(
        q, k, v, kv_valid_len=kv_valid_len, block_s=min(block_s, k.shape[1]),
        interpret=kernels.pallas_interpret())
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
    scale = 1.0 / np.sqrt(hd)
    s_new = jnp.einsum("bkgd,bkd->bkg", qg,
                       k1.reshape(B, KV, hd).astype(jnp.float32))[..., None] * scale
    m = jnp.maximum(m_old, s_new)                       # (B,KV,G,1)
    alpha = jnp.exp(m_old - m)
    p_new = jnp.exp(s_new - m)
    denom = l_old * alpha + p_new
    out = (out_old.reshape(B, KV, G, hd).astype(jnp.float32) * (l_old * alpha)
           + p_new * v1.reshape(B, KV, 1, hd).astype(jnp.float32)) / denom
    return out.reshape(B, 1, H, hd).astype(q.dtype)
