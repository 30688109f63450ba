"""Kernel microbenchmarks: Pallas (interpret) vs jnp oracle, host timings.

Wall-clock on this CPU container measures the *oracle* path realistically;
the Pallas interpret path is a correctness harness (Python-interpreted), so
we report oracle timings + interpret-mode validation deltas, plus the
analytic VMEM footprints the BlockSpecs claim on TPU.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e3


def run(quick: bool = False) -> dict:
    rng = np.random.default_rng(0)
    out = {}

    # edge_motion: 720p-ish segment through the oracle + kernel validation
    from repro import kernels
    from repro.kernels.edge_motion import ops as em
    frames = jnp.asarray(rng.uniform(0, 1, (5, 192, 320)).astype(np.float32))
    t_ref = _time(lambda f: em.segment_motion(f, use_kernel=False), frames)
    a = em.segment_motion(frames, use_kernel=True)
    b = em.segment_motion(frames, use_kernel=False)
    out["edge_motion"] = {
        "oracle_ms": t_ref,
        "kernel_max_err": float(jnp.max(jnp.abs(a - b))),
        "vmem_per_program_kb": (2 * (32 + 2) * (320 + 2) * 4) / 1024,
    }

    # fleet motion interpret-pass cut: full-height tiles collapse the
    # (pairs, row-tiles) grid to (pairs, 1) — H/32x fewer interpreter
    # passes per pallas_call, bit-identical scores.  Interpret-mode only:
    # on compiled backends tile_rows=None resolves back to the same 32-row
    # program and the "comparison" would time one executable twice.
    if kernels.pallas_interpret():
        cams = jnp.asarray(rng.uniform(0, 1, (4, 5, 96, 160))
                           .astype(np.float32))
        t_banded = _time(lambda f: em.segment_motion_fleet(f, tile_rows=32),
                         cams)
        t_full = _time(lambda f: em.segment_motion_fleet(f, tile_rows=None),
                       cams)
        fa = em.segment_motion_fleet(cams, tile_rows=32)
        fb = em.segment_motion_fleet(cams, tile_rows=None)
        out["edge_motion_fleet_interpret"] = {
            "banded32_ms": t_banded,
            "full_height_ms": t_full,
            "passes_cut_speedup": t_banded / t_full,
            "max_err": float(jnp.max(jnp.abs(fa - fb))),
        }

    # knapsack_dp
    from repro.kernels.knapsack_dp import ops as dp
    util = jnp.asarray(rng.uniform(0, 1, (64, 6)).astype(np.float32))
    costs = jnp.asarray(np.array([1, 2, 4, 8, 16, 20], np.int32))
    t_ref = _time(lambda u: dp.solve_values(u, costs, 256, False)[0], util)
    vk, ck = dp.solve_values(util, costs, 256, True)
    vr, cr = dp.solve_values(util, costs, 256, False)
    out["knapsack_dp"] = {
        "oracle_ms": t_ref,
        "kernel_max_err": float(jnp.max(jnp.abs(vk - vr))),
        "vmem_row_kb": 2 * 384 * 4 / 1024,
    }

    # flash_decode
    from repro.kernels.flash_decode import ops as fd
    from repro.kernels.flash_decode import ref as fdref
    B, S, H, KV, hd = (2, 2048, 16, 4, 128) if quick else (4, 8192, 16, 4, 128)
    q = jnp.asarray(rng.normal(0, 1, (B, 1, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)).astype(np.float32))
    vl = jnp.int32(S - 3)
    t_ref = _time(lambda q_: fdref.flash_decode_ref(q_, k, v, kv_valid_len=vl), q)
    got = fd.flash_decode(q, k, v, kv_valid_len=vl, force_kernel=True)
    want = fdref.flash_decode_ref(q, k, v, kv_valid_len=vl)
    out["flash_decode"] = {
        "oracle_ms": t_ref,
        "kernel_max_err": float(jnp.max(jnp.abs(got - want))),
        "vmem_per_program_kb": (2 * 512 * hd * 4 + 2 * (H // KV) * hd * 4) / 1024,
    }

    print("\n[Kernels] oracle wall-times + interpret-mode validation:")
    for k_, v_ in out.items():
        if "oracle_ms" in v_:
            print(f"  {k_:14s} oracle={v_['oracle_ms']:.2f}ms "
                  f"err={v_['kernel_max_err']:.2e} "
                  f"vmem~{list(v_.values())[2]:.0f}KB")
    fi = out.get("edge_motion_fleet_interpret")
    if fi:
        print(f"  fleet motion interpret passes: "
              f"banded(32)={fi['banded32_ms']:.2f}ms"
              f" -> full-height={fi['full_height_ms']:.2f}ms "
              f"({fi['passes_cut_speedup']:.2f}x, err={fi['max_err']:.1e})")
    worst = max(v_["kernel_max_err"] for v_ in out.values()
                if "kernel_max_err" in v_)
    headline = f"worst kernel err {worst:.2e}"
    if fi:
        headline += f"; fleet motion interpret {fi['passes_cut_speedup']:.2f}x"
    return {**out, "headline": headline}
