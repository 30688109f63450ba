"""End-to-end behaviour tests for the paper's system: the DeepStream control
loop against baselines at miniature scale, the serve engine, the data
pipeline, and detector F1 plumbing."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scheduler import DeepStreamSystem, SystemConfig
from repro.data.synthetic import MultiCameraScene, SceneConfig, bandwidth_trace


@pytest.fixture(scope="module")
def system(detectors):
    light, server = detectors
    cfg = SystemConfig(scene=SceneConfig(seed=5, num_cameras=3),
                       eval_frames=3)
    sysd = DeepStreamSystem(cfg, light, server)
    prof = MultiCameraScene(SceneConfig(seed=42, num_cameras=3))
    info = sysd.profile(prof, num_slots=3, mlp_steps=300)
    assert info["mlp_mse"] < 0.08
    return sysd


def test_bandwidth_trace_stats():
    tr = bandwidth_trace("low", 500, seed=1)
    assert abs(tr.mean() - 521) < 120
    tr_h = bandwidth_trace("high", 500, seed=1)
    assert tr_h.mean() > tr.mean()


def test_deepstream_beats_static_baseline(system):
    scene_a = MultiCameraScene(SceneConfig(seed=9, num_cameras=3))
    scene_b = MultiCameraScene(SceneConfig(seed=9, num_cameras=3))
    trace = bandwidth_trace("low", 5, seed=2) * 3 / 5  # scale to 3 cameras
    ds = system.run(scene_a, trace, method="deepstream")
    static = system.run(scene_b, trace, method="static")
    assert ds["utility"].mean() > static["utility"].mean()
    assert np.all(ds["utility"] >= 0)
    assert np.all(np.isfinite(ds["bytes"]))


def test_allocations_respect_bandwidth(system):
    scene = MultiCameraScene(SceneConfig(seed=11, num_cameras=3))
    trace = bandwidth_trace("medium", 4, seed=3) * 3 / 5
    logs = system.run(scene, trace, method="deepstream_no_elastic",
                      use_elastic=False)
    # without elastic borrowing, allocated bitrates never exceed the trace
    # (up to the minimum-bitrate feasibility clamp)
    over = logs["alloc_kbps"] - np.maximum(logs["W"], 50 * 3)
    assert np.all(over <= 1e-6)


def test_serve_engine_greedy_matches_manual():
    from repro.configs import smoke_config
    from repro.models.model import LM
    from repro.serve.engine import Request, ServeEngine
    cfg = smoke_config("granite-8b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    prompt = np.arange(10, dtype=np.int32) % cfg.vocab_size
    eng = ServeEngine(lm, params, batch_slots=2, max_seq=32)
    r = Request(rid=0, prompt=prompt, max_new_tokens=5)
    stats = eng.run([r])
    assert stats["requests"] == 1 and len(r.out_tokens) == 5
    # manual greedy decode must match the engine's tokens
    lg, cache = lm.prefill(params, {"tokens": jnp.asarray(prompt[None])}, 32)
    toks = [int(jnp.argmax(lg[0, -1]))]
    pos = len(prompt)
    for _ in range(4):
        lg, cache = lm.decode(params, jnp.asarray([[toks[-1]]], jnp.int32),
                              cache, jnp.int32(pos))
        toks.append(int(jnp.argmax(lg[0, 0])))
        pos += 1
    assert toks == r.out_tokens


def test_serve_engine_mixed_prompt_lengths():
    # regression: two requests with DIFFERENT prompt lengths share the batch
    # — the engine must decode each slot at its own cache position (the old
    # lock-step max(slot_pos) wrote short prompts' KV into the wrong cells)
    from repro.configs import smoke_config
    from repro.models.model import LM
    from repro.serve.engine import Request, ServeEngine
    cfg = smoke_config("granite-8b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    pa = np.arange(10, dtype=np.int32) % cfg.vocab_size
    pb = (np.arange(6, dtype=np.int32) * 3 + 1) % cfg.vocab_size
    eng = ServeEngine(lm, params, batch_slots=2, max_seq=32)
    ra = Request(rid=0, prompt=pa, max_new_tokens=5)
    rb = Request(rid=1, prompt=pb, max_new_tokens=5)
    stats = eng.run([ra, rb])
    assert stats["requests"] == 2
    # each request must match its own single-request greedy decode
    for r, prompt in ((ra, pa), (rb, pb)):
        lg, cache = lm.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                               32)
        toks = [int(jnp.argmax(lg[0, -1]))]
        pos = len(prompt)
        for _ in range(4):
            lg, cache = lm.decode(params,
                                  jnp.asarray([[toks[-1]]], jnp.int32),
                                  cache, jnp.int32(pos))
            toks.append(int(jnp.argmax(lg[0, 0])))
            pos += 1
        assert toks == r.out_tokens, f"rid={r.rid}"


def test_data_pipeline_determinism_and_sharding():
    from repro.data.pipeline import DataConfig, SyntheticTokenSource
    cfg = DataConfig(global_batch=8, seq_len=32, vocab_size=100, seed=3)
    a = SyntheticTokenSource(cfg).batch_at(5)
    b = SyntheticTokenSource(cfg).batch_at(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # host shards draw independent rows
    h0 = SyntheticTokenSource(cfg, host_index=0, host_count=2).batch_at(5)
    h1 = SyntheticTokenSource(cfg, host_index=1, host_count=2).batch_at(5)
    assert h0["tokens"].shape[0] == 4
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_prefetch_loader_yields():
    from repro.data.pipeline import DataConfig, PrefetchLoader, SyntheticTokenSource
    src = SyntheticTokenSource(DataConfig(4, 16, 50))
    loader = PrefetchLoader(src)
    it = iter(loader)
    b1, b2 = next(it), next(it)
    assert b1["tokens"].shape == (4, 16)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b2["tokens"]))
    loader.close()


def test_f1_score_properties():
    from repro.models.detector import f1_score
    gt = [(0, 0, 10, 10), (20, 20, 30, 30)]
    perfect = np.array(gt, np.float32)
    assert f1_score(perfect, np.array([True, True]), gt) == 1.0
    assert f1_score(perfect, np.array([False, False]), gt) == 0.0
    assert f1_score(perfect, np.array([True, True]), []) == 0.0
    assert f1_score(np.zeros((0, 4)), np.zeros((0,), bool), []) == 1.0


@pytest.mark.parametrize("placed", [False, True])
def test_compile_cache_placement(monkeypatch, tmp_path, placed):
    """The entry points' persistent compile cache: a deployment's
    JAX_COMPILATION_CACHE_DIR is used as JAX read it; otherwise one fixed
    directory inside the checkout."""
    from repro.launch import cache

    was = jax.config.jax_compilation_cache_dir
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = cache.enable_compile_cache()
        want = str(tmp_path) if placed else str(cache.REPO_CACHE_DIR)
        assert got == want == jax.config.jax_compilation_cache_dir
        assert cache.REPO_CACHE_DIR.parent == Path(__file__).parents[1]
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
