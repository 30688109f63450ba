"""Mixture-of-Experts FFN with expert parallelism.

Two execution modes sharing one local kernel:

* ``local``  — single device (smoke tests): all experts local, no collectives.
* ``ep_psum`` — shard_map over the mesh: experts sharded over the "model"
  axis; activations arrive batch-sharded over the DP axes and replicated over
  "model" (standard TP layout), each model rank selects the (token, k) pairs
  routed to *its* experts into a fixed-capacity buffer, runs a grouped GEMM
  (``jax.lax.ragged_dot``), scatter-adds weighted outputs, and a single
  ``psum`` over "model" combines — the same collective a dense TP FFN needs,
  so MoE costs no *extra* collective class.  (An all_to_all dispatch variant
  is evaluated in EXPERIMENTS §Perf.)

Token overflow beyond the capacity buffer is dropped (standard fixed-capacity
MoE); drops are counted and returned for monitoring.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.common.config import ModelConfig
from repro.common.params import ParamDef
from repro.models import layers as L


def moe_defs(cfg: ModelConfig) -> Dict[str, Any]:
    m = cfg.moe
    d, dt = cfg.d_model, jnp.dtype(cfg.dtype)
    out: Dict[str, Any] = {
        "router": ParamDef((d, m.num_experts), ("embed", None), "normal", jnp.float32),
        "w_gate": ParamDef((m.num_experts, d, m.expert_d_ff), ("experts", "embed", None), "normal", dt),
        "w_up": ParamDef((m.num_experts, d, m.expert_d_ff), ("experts", "embed", None), "normal", dt),
        "w_down": ParamDef((m.num_experts, m.expert_d_ff, d), ("experts", None, "embed"), "normal", dt),
    }
    if m.num_shared_experts > 0:
        out["shared"] = L.swiglu_defs(cfg, d_ff=m.shared_d_ff * m.num_shared_experts)
    return out


def _capacity(n_tokens: int, top_k: int, num_shards: int, cf: float) -> int:
    c = int(np.ceil(cf * n_tokens * top_k / num_shards))
    return max(8, int(np.ceil(c / 8)) * 8)


def _local_moe(x: jax.Array, p: Dict[str, Any], *, top_k: int, num_experts: int,
               e_start: jax.Array, e_local: int, capacity: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Route + grouped-GEMM for the experts in [e_start, e_start+e_local).

    x: (n, d) local tokens. Returns (out (n,d) fp32 partial, aux_loss, drops).
    """
    n, d = x.shape
    logits = x.astype(jnp.float32) @ p["router"]                  # (n, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, top_k)                  # (n, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        (jax.nn.one_hot(gate_i, num_experts, dtype=jnp.float32)).sum(1), axis=0)
    aux = num_experts * jnp.sum(me * ce) / top_k

    flat_i = gate_i.reshape(-1)                                   # (n*k,)
    flat_w = gate_w.reshape(-1)
    tok_of = jnp.arange(n * top_k) // top_k
    mine = (flat_i >= e_start) & (flat_i < e_start + e_local)

    # stable partition: my pairs first, take first `capacity`
    order = jnp.argsort(jnp.logical_not(mine), stable=True)
    sel = order[:capacity]
    valid = mine[sel]
    drops = jnp.maximum(jnp.sum(mine) - jnp.sum(valid), 0)

    e_loc = jnp.where(valid, flat_i[sel] - e_start, e_local - 1)  # invalid -> last group
    tok = tok_of[sel]
    xs = jnp.where(valid[:, None], x[tok], 0).astype(x.dtype)     # (C, d)

    # group by local expert id for ragged_dot
    g_order = jnp.argsort(e_loc, stable=True)
    xs_g = xs[g_order]
    group_sizes = jnp.bincount(e_loc, length=e_local).astype(jnp.int32)

    gate = jax.lax.ragged_dot(xs_g, p["w_gate"], group_sizes)
    up = jax.lax.ragged_dot(xs_g, p["w_up"], group_sizes)
    h = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(x.dtype)
    y_g = jax.lax.ragged_dot(h, p["w_down"], group_sizes)         # (C, d)

    inv = jnp.argsort(g_order, stable=True)
    y = y_g[inv].astype(jnp.float32) * (flat_w[sel] * valid)[:, None]
    out = jnp.zeros((n, d), jnp.float32).at[tok].add(y, mode="drop")
    return out, aux, drops.astype(jnp.float32)


def apply_moe(cfg: ModelConfig, params, x: jax.Array, *,
              mesh: Optional[Mesh] = None
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) -> (B, S, d), stats {aux_loss, drop_frac}."""
    m = cfg.moe
    B, S, d = x.shape
    dt = x.dtype

    if mesh is None or "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        n = B * S
        cap = _capacity(n, m.top_k, 1, m.capacity_factor)
        out, aux, drops = _local_moe(
            x.reshape(n, d), params, top_k=m.top_k, num_experts=m.num_experts,
            e_start=jnp.int32(0), e_local=m.num_experts, capacity=cap)
        y = out.reshape(B, S, d).astype(dt)
    else:
        mdl = mesh.shape["model"]
        dp_axes = tuple(a for a in mesh.axis_names if a != "model")
        dp = int(np.prod([mesh.shape[a] for a in dp_axes]))
        n_loc = (B // dp) * S if B % dp == 0 else B * S
        batch_spec = dp_axes if B % dp == 0 else None
        if isinstance(batch_spec, tuple) and len(batch_spec) == 1:
            batch_spec = batch_spec[0]
        e_local = m.num_experts // mdl
        cap = _capacity(n_loc, m.top_k, mdl, m.capacity_factor)
        fsdp = ("pod", "data") if (cfg.fsdp_over_pod and "pod" in mesh.axis_names) else ("data",)
        fs = fsdp if len(fsdp) > 1 else fsdp[0]

        pspec = {
            "router": P(None, None),
            "w_gate": P("model", fs, None),
            "w_up": P("model", fs, None),
            "w_down": P("model", None, fs),
        }
        wp = {k: params[k] for k in pspec}

        def shard_fn(x_blk, w):
            # gather FSDP-sharded expert weights (the FSDP all-gather)
            w = dict(w)
            for key, ax in (("w_gate", 1), ("w_up", 1), ("w_down", 2)):
                g = w[key]
                for a in reversed(fsdp):
                    g = jax.lax.all_gather(g, a, axis=ax, tiled=True)
                w[key] = g
            r = jax.lax.axis_index("model")
            bl, sl, _ = x_blk.shape
            out, aux, drops = _local_moe(
                x_blk.reshape(bl * sl, d), w, top_k=m.top_k,
                num_experts=m.num_experts, e_start=r * e_local,
                e_local=e_local, capacity=cap)
            out = jax.lax.psum(out, "model")
            aux = jax.lax.pmean(aux, "model")
            drops = jax.lax.psum(drops, "model")
            return out.reshape(bl, sl, d), aux, drops

        out, aux, drops = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(batch_spec, None, None), pspec),
            out_specs=(P(batch_spec, None, None), P(), P()),
            check_vma=False,
        )(x, wp)
        y = out.astype(dt)

    if m.num_shared_experts > 0:
        y = y + L.swiglu(params["shared"], x)

    n_total = B * S * m.top_k
    return y, {"aux_loss": aux, "drop_frac": drops / n_total}
