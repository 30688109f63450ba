"""Distribution-layer tests: optimizer, sharding rules, checkpointing
(incl. elastic restore onto a different mesh), compression, watchdog."""
import os
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import OptimizerConfig
from repro.train.optimizer import adamw_update, init_opt_state, lr_schedule


def test_adamw_converges_quadratic():
    cfg = OptimizerConfig(lr=0.05, warmup_steps=5, total_steps=300,
                          weight_decay=0.0, grad_clip=0.0)
    params = {"w": jnp.array([4.0, -3.0]), "b": jnp.array(2.0)}
    opt = init_opt_state(cfg, params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + p["b"] ** 2

    for _ in range(300):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw_update(cfg, params, g, opt)
    assert float(loss(params)) < 1e-3


def test_adamw_grad_clip_and_schedule():
    cfg = OptimizerConfig(lr=1e-2, warmup_steps=10, total_steps=100, grad_clip=1.0)
    assert float(lr_schedule(cfg, jnp.int32(0))) < float(lr_schedule(cfg, jnp.int32(9)))
    assert float(lr_schedule(cfg, jnp.int32(99))) < float(lr_schedule(cfg, jnp.int32(50)))
    params = {"w": jnp.zeros(3)}
    opt = init_opt_state(cfg, params)
    big_grad = {"w": jnp.full(3, 1e6)}
    p2, _, stats = adamw_update(cfg, params, big_grad, opt)
    assert float(stats["grad_norm"]) > 1e5
    assert np.all(np.isfinite(np.asarray(p2["w"])))


def test_moment_dtype_bf16():
    cfg = OptimizerConfig(moment_dtype="bfloat16")
    opt = init_opt_state(cfg, {"w": jnp.zeros((4, 4), jnp.bfloat16)})
    assert opt.m["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_sharding_rules_divisibility_guard():
    from jax.sharding import PartitionSpec as P
    from repro.sharding import rules as R

    class FakeMesh:  # safe_spec only consults .shape
        shape = {"data": 16, "model": 16}

    # 7 and 13 don't divide 16 -> axes dropped to replication
    assert R.safe_spec((7, 13), P("data", "model"), FakeMesh()) == P(None, None)
    # divisible dims keep their axes
    assert R.safe_spec((32, 64), P("data", "model"), FakeMesh()) == P("data", "model")
    # tuple axes: product must divide
    assert R.safe_spec((32,), P(("data", "model")), FakeMesh()) == P(None)
    assert R.safe_spec((256,), P(("data", "model")), FakeMesh()) == P(("data", "model"))


def test_fit_batch_axes_prefix():
    from repro.sharding import rules as R
    devs = jax.devices()
    mesh = jax.sharding.Mesh(np.array(devs[:1]).reshape(1, 1),
                             ("data", "model"))
    assert R.fit_batch_axes(mesh, 8) == ("data",)
    assert R.fit_batch_axes(mesh, 7) == ("data",)  # 1 divides everything


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_ckpt_roundtrip_and_atomicity(tmp_path):
    from repro.ckpt import checkpoint as ckpt
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "nested": {"b": jnp.ones((2, 2), jnp.bfloat16)}}
    path = tmp_path / "step_1"
    ckpt.save(tree, path, step=7, metadata={"note": "x"})
    assert ckpt.is_committed(path)
    restored, meta = ckpt.restore(path, tree)
    assert meta["step"] == 7
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))
    assert restored["nested"]["b"].dtype == jnp.bfloat16
    # a checkpoint without the COMMIT marker must be invisible
    (path / ckpt.COMMIT_MARKER).unlink()
    assert ckpt.latest_committed(tmp_path) is None
    with pytest.raises(FileNotFoundError):
        ckpt.restore(path, tree)


def test_ckpt_elastic_restore_different_mesh(tmp_path):
    """Save from one layout, restore onto a different mesh: the manifest is
    logical, so topology changes (elastic scaling) are transparent."""
    from repro.ckpt import checkpoint as ckpt
    from jax.sharding import NamedSharding, PartitionSpec as P
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    ckpt.save(tree, tmp_path / "c", step=1)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    sh = {"w": NamedSharding(mesh, P("data", "model"))}
    restored, _ = ckpt.restore(tmp_path / "c", tree, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))


def test_ckpt_async_save(tmp_path):
    from repro.ckpt import checkpoint as ckpt
    tree = {"w": jnp.ones((128, 128))}
    s = ckpt.AsyncSaver()
    s.save(tree, tmp_path / "a", step=1)
    s.wait()
    assert ckpt.is_committed(tmp_path / "a")


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_compressed_psum_error_feedback():
    from repro.train.compression import compressed_psum, init_residuals
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1,),
                             ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(0, 1, (64,)), jnp.float32)}
    r = init_residuals(g)
    # single device: mean == value up to int8 quantization; residual carries
    # the quantization error so the SUM over steps converges to the truth
    acc = jnp.zeros((64,))
    truth = jnp.zeros((64,))
    for _ in range(20):
        out, r = compressed_psum(g, r, mesh, axis="data")
        acc = acc + out["w"]
        truth = truth + g["w"]
    np.testing.assert_allclose(np.asarray(acc), np.asarray(truth),
                               atol=0.05 * 20 * 0.01 + 0.05)


def test_compression_wire_savings():
    from repro.train.compression import wire_bytes
    raw, comp = wire_bytes({"w": jnp.zeros((1000,))}, dtype_bytes=4)
    assert raw == 4000 and comp == 1000


# ---------------------------------------------------------------------------
# watchdog / fault tolerance
# ---------------------------------------------------------------------------

def test_watchdog_flags_stragglers():
    from repro.ft.watchdog import SimulatedFleet, Watchdog
    wd = Watchdog()
    fleet = SimulatedFleet(16, base_step_time=0.1)
    for step in range(20):
        assert wd.record(step, fleet.synchronous_step_time()) == "ok"
    fleet.inject_straggler(3, factor=6.0)
    statuses = [wd.record(20 + i, fleet.synchronous_step_time()) for i in range(4)]
    assert statuses[0] == "straggler"
    assert "replace" in statuses


def test_preemption_checkpointer(tmp_path):
    from repro.ft.watchdog import PreemptionCheckpointer
    saved = []
    pc = PreemptionCheckpointer(lambda s: saved.append(s), every=5,
                                install_signal=False)
    for step in range(1, 12):
        pc.maybe_save(step)
    assert saved == [5, 10]
    pc.preempted = True
    with pytest.raises(SystemExit):
        pc.maybe_save(11)
    assert saved[-1] == 11
