"""Compile rehearsals for a described (not attached) TPU v5e chip.

The TPU compiler ships with jaxlib, so the served programs can be compiled
for a v5e here, with no chip: what Mosaic or XLA:TPU would refuse fails
here first.  Nothing runs, so these tests say nothing about numbers or
times.  Cases: the three fleet-path Pallas kernels at the served sizes
(5 cameras, 10 frames of 96x160, block 8, DP capacity 255) and the
deepstream and reducto bucket-8 episodes, whose compiled HLO must contain
the kernels (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest workers all
import this file.  The kernel mode is derived from the backend
(``kernels.pallas_interpret``), which is the CPU here, so the fixture
steers it to compiled mode and clears JAX's trace caches on both sides —
a trace made in one mode must never be reused in the other.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.analysis import programs
from repro.core import fleet as fleet_mod
from repro.core.codec import CodecConfig
from repro.kernels.edge_motion import ops as em_ops
from repro.kernels.knapsack_dp import ops as dp_ops
from repro.kernels.tx_codec import ops as tx_ops

C, N, H, W = 5, 10, 96, 160      # SceneConfig defaults: the served fleet
J = len(CodecConfig().bitrates_kbps)


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_mode(monkeypatch):
    """Compiled kernels, fresh traces, persistent cache off (a compile for
    a described chip is written to the cache but cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    monkeypatch.setattr(kernels, "pallas_interpret", lambda: False)
    jax.clear_caches()
    try:
        yield
    finally:
        monkeypatch.undo()
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _assert_kernel_inside(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()


def test_edge_motion_compiles(one_chip, compiled_mode):
    frames = _on(one_chip, programs._sds((C, N, H, W), jnp.float32))
    fn = jax.jit(lambda f: em_ops.segment_motion_fleet(f, block_size=8))
    _assert_kernel_inside(fn.lower(frames).compile())


def test_tx_codec_compiles(one_chip, compiled_mode):
    f32 = jnp.float32
    args = _on(one_chip, (programs._sds((C, N, H, W), f32),
                          programs._sds((C,), f32), programs._sds((C,), f32),
                          programs._sds((C,), f32),
                          programs._sds((C, 2), jnp.uint32)))
    compiled = tx_ops.encode_fleet.lower(CodecConfig(), *args).compile()
    _assert_kernel_inside(compiled)


def test_knapsack_dp_compiles(one_chip, compiled_mode):
    w_cap = programs.Canonical().w_cap
    assert w_cap == 255
    util, costs = _on(one_chip, (programs._sds((C, J), jnp.float32),
                                 programs._sds((J,), jnp.int32)))
    compiled = dp_ops.solve_values.lower(util, costs, w_cap, True).compile()
    _assert_kernel_inside(compiled)


@pytest.mark.parametrize("method", ["deepstream", "reducto"])
def test_episode_b8_compiles(one_chip, compiled_mode, monkeypatch, method):
    monkeypatch.setattr(fleet_mod, "_EXEC_CACHE", {})
    monkeypatch.setattr(fleet_mod, "_EPISODE_COMPILE_COUNTS", {})
    prog, = (p for p in programs.get_programs(kinds=["episode"])
             if p.name == f"episode/{method}/b8")
    compiled = prog.fn.lower(*_on(one_chip, prog.abs_args)).compile()
    _assert_kernel_inside(compiled)
