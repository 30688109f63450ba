"""Sharded-vs-single-device equivalence for the fleet slot-step.

Runs a subprocess under ``--xla_force_host_platform_device_count=4`` (the
parent process is pinned to one device by conftest) and asserts the
camera-mesh shard_map path reproduces the unsharded batched utility logs to
<= 1e-6 — including a NON-divisible camera count (C=5 on 4 devices, padded
with inert cameras), and for both the deepstream and reducto (detection
reuse) routes through the unified executable.
"""
import os
import subprocess
import sys
from pathlib import Path

_SCRIPT = r"""
import os, sys
import numpy as np, jax
sys.path.insert(0, @SRC@)
from repro.core.scheduler import DeepStreamSystem, SystemConfig
from repro.core import fleet as fleet_mod
from repro.core import utility as util_mod
from repro.data.synthetic import MultiCameraScene, SceneConfig, bandwidth_trace
from repro.train.detector_train import train_detector

assert jax.device_count() == 4, jax.device_count()
light = train_detector("light", steps=300, batch=12, cache=True)
server = train_detector("server", steps=600, batch=12, cache=True)

C = 5   # NOT divisible by the 4-device mesh: exercises camera padding
def build(shard):
    cfg = SystemConfig(scene=SceneConfig(seed=5, num_cameras=C),
                       eval_frames=3, batched=True, shard=shard)
    s = DeepStreamSystem(cfg, light, server)
    s.mlp = util_mod.init_utility_mlp(jax.random.PRNGKey(0))
    s.tau_wl, s.tau_wh = 10.0, 50.0
    s.jcab_table = np.linspace(0.2, 0.8, 18).reshape(6, 3).astype(np.float32)
    return s

for method in ("deepstream", "reducto"):
    logs = {}
    for shard in ("off", "auto"):
        s = build(shard)
        assert (s.mesh is not None) == (shard == "auto")
        s._key = jax.random.PRNGKey(1234)
        scene = MultiCameraScene(SceneConfig(seed=33, num_cameras=C))
        trace = bandwidth_trace("medium", 2, seed=8) * 3 / 5
        logs[shard] = s.run(scene, trace, method=method)
    for k in ("utility", "bytes"):
        d = float(np.max(np.abs(logs["off"][k] - logs["auto"][k])))
        assert d <= 1e-6, (method, k, d)
        print(f"OK {method} {k} max|diff|={d:.3e}")
print("SHARDED-EQUIV-PASS")
"""


def _run_subprocess(script: str, marker: str) -> None:
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env.pop("REPRO_FAKE_DEVICES", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    script = script.replace("@SRC@", repr(str(root / "src")))
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=570, env=env, cwd=str(root))
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert marker in proc.stdout, proc.stdout


def test_sharded_matches_single_device(detectors):
    # `detectors` guarantees the checkpoint cache is warm before the
    # subprocess restores it (no duplicate training run)
    _run_subprocess(_SCRIPT, "SHARDED-EQUIV-PASS")


_EPISODE_SCRIPT = r"""
import os, sys
import numpy as np, jax
sys.path.insert(0, @SRC@)
from repro.core.scheduler import DeepStreamSystem, SystemConfig
from repro.core import scheduler as sched_mod
from repro.core import utility as util_mod
from repro.data.synthetic import DeviceScene, SceneConfig, bandwidth_trace
from repro.train.detector_train import train_detector

assert jax.device_count() == 4, jax.device_count()
light = train_detector("light", steps=300, batch=12, cache=True)
server = train_detector("server", steps=600, batch=12, cache=True)

C = 5   # NOT divisible by the 4-device mesh: exercises camera + scene padding
def build(episode, shard):
    cfg = SystemConfig(scene=SceneConfig(seed=5, num_cameras=C),
                       eval_frames=3, batched=True, episode=episode,
                       shard=shard)
    s = DeepStreamSystem(cfg, light, server)
    s.mlp = util_mod.init_utility_mlp(jax.random.PRNGKey(0))
    s.tau_wl, s.tau_wh = 10.0, 50.0
    s.jcab_table = np.linspace(0.2, 0.8, 18).reshape(6, 3).astype(np.float32)
    return s

for method in ("deepstream", "reducto"):
    logs = {}
    for name, (episode, shard) in (("host", (False, "off")),
                                   ("ep", (True, "auto"))):
        s = build(episode, shard)
        assert (s.mesh is not None) == (shard == "auto")
        s._key = jax.random.PRNGKey(1234)
        scene = DeviceScene(SceneConfig(seed=33, num_cameras=C))
        trace = bandwidth_trace("medium", 2, seed=8) * 3 / 5
        n0 = sched_mod.d2h_fetch_counts()
        logs[name] = s.run(scene, trace, method=method)
        if episode:
            n1 = sched_mod.d2h_fetch_counts()
            assert n1["keep"] == n0["keep"], method
            assert n1["control"] == n0["control"], method
    for k in ("utility", "bytes", "alloc_kbps"):
        scale = max(1.0, float(np.max(np.abs(logs["host"][k]))))
        d = float(np.max(np.abs(logs["host"][k] - logs["ep"][k])))
        assert d <= 1e-5 * scale, (method, k, d)
        print(f"OK {method} {k} max|diff|={d:.3e}")
print("EPISODE-SHARDED-PASS")
"""


def test_episode_sharded_matches_pipelined(detectors):
    """The 4-device shard_map episode (C=5 padded to 8) reproduces the
    single-device host-control batched logs for the deepstream and reducto
    routes, with zero per-slot keep/control fetches."""
    _run_subprocess(_EPISODE_SCRIPT, "EPISODE-SHARDED-PASS")
