"""Serving drivers: the LM analytics engine and the fleet stream runner.

LM engine (continuous-batched decode over a zoo backbone)::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b --smoke \
        --requests 6 --slots 4 --prompt-len 24 --max-new 8

Fleet stream (crash-safe windowed serving over the compiled episode
executables, ``serve.stream``; re-run the same command after a kill to
restore from the latest committed checkpoint and continue)::

    PYTHONPATH=src python -m repro.launch.serve --fleet-stream \
        --stream-slots 64 --window-slots 8 --method deepstream \
        --ckpt-dir artifacts/serve_ckpt --ckpt-keep 8

``--source`` switches ingest from the in-process soak stream to a hardened
real source (``serve.ingest``: quarantine lane + slot sequencing +
read backoff): ``--source file:/path/to/stream.txt`` tails a line-protocol
file; ``--source host:port`` reads the same protocol over TCP.  The fleet
expects one ``"<t> <kbps> <live-bits>"`` record per slot.
"""
from __future__ import annotations

import argparse
from typing import Optional

import jax
import numpy as np


def build_fleet_runner(method: str, *, window_slots: int = 8,
                       ckpt_dir: Optional[str] = None,
                       ckpt_keep: Optional[int] = None, shard: str = "auto",
                       use_kernels: bool = True,
                       episode_pipelined: bool = True):
    """The served fleet: the episode-mode system over ``SceneConfig``
    defaults (5 cameras, 96x160, 10 fps) with the committed light/server
    detectors (trained only when no checkpoint is committed) and
    harness-default control artifacts, wrapped in a windowed
    ``StreamingFleetRunner``.  ``shard="off"`` keeps the fleet on
    ``jax.devices()[0]``; ``"auto"`` shards cameras over every visible
    device.  ``use_kernels=False, episode_pipelined=False`` builds the
    kernel-free reference body the served one is compared against."""
    from repro.core import utility as util_mod
    from repro.core.scheduler import DeepStreamSystem, SystemConfig
    from repro.data.synthetic import DeviceScene, SceneConfig
    from repro.serve.stream import StreamConfig, StreamingFleetRunner
    from repro.train.detector_train import train_detector

    scene_cfg = SceneConfig(seed=33)
    sys_cfg = SystemConfig(scene=scene_cfg, episode=True, eval_frames=3,
                           w_cap_kbps=8000.0, shard=shard,
                           use_kernels=use_kernels,
                           episode_pipelined=episode_pipelined)
    system = DeepStreamSystem(
        sys_cfg, train_detector("light", steps=300, batch=12, cache=True),
        train_detector("server", steps=600, batch=12, cache=True))
    system.mlp = util_mod.init_utility_mlp(jax.random.PRNGKey(0))
    system.tau_wl, system.tau_wh = 10.0, 50.0
    system.jcab_table = np.linspace(0.2, 0.8, 18).reshape(6, 3).astype(
        np.float32)
    return StreamingFleetRunner(
        system, DeviceScene(scene_cfg), method=method,
        cfg=StreamConfig(window_slots=window_slots, ckpt_dir=ckpt_dir,
                         ckpt_keep=ckpt_keep,
                         install_signal=ckpt_dir is not None))


def serve_soak_stream(runner, stream_slots: int) -> None:
    """Offer the seeded diurnal + camera-churn soak stream
    (``make_soak_stream``) from the runner's cursor, window by window,
    serving every full window and flushing the tail."""
    from repro.data.scenarios import make_soak_stream

    trace, live = make_soak_stream(
        stream_slots, num_cams=runner.system.cfg.scene.num_cameras)
    step = runner.cfg.window_slots
    t = runner.t_next
    while t < len(trace):
        t += runner.offer(trace[t:t + step], faults=live[t:t + step])
        runner.serve()
    runner.serve(flush=True)


def run_fleet_stream(args) -> None:
    """Windowed fleet serving: restore the latest checkpoint if any, feed
    the soak stream (or a hardened ``--source``), print the SLO stats."""
    from repro.serve import ingest as ingest_mod

    runner = build_fleet_runner(args.method, window_slots=args.window_slots,
                                ckpt_dir=args.ckpt_dir,
                                ckpt_keep=args.ckpt_keep)
    print(f"# fleet on {jax.device_count()} {jax.default_backend()} "
          f"device(s), camera mesh: {runner.system.mesh is not None}")
    with runner:
        if runner.restore():
            print(f"# restored window={runner.window} t_next={runner.t_next}")
        if args.source:
            # hardened path: parse -> quarantine -> sequence -> offer
            if args.source.startswith("file:"):
                src = ingest_mod.FileTailSource(args.source[len("file:"):])
            else:
                host, _, port = args.source.rpartition(":")
                src = ingest_mod.SocketLineSource(host or "127.0.0.1",
                                                  int(port))
            ing = ingest_mod.StreamIngestor(runner, src)
            ing.pump(until_t=args.stream_slots, flush=True)
        else:
            serve_soak_stream(runner, args.stream_slots)
        print({k: round(v, 4) if isinstance(v, float) else v
               for k, v in runner.stats().items()})


def main() -> None:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--fleet-stream", action="store_true",
                    help="serve the multi-camera fleet stream "
                         "(serve.stream) instead of the LM engine")
    ap.add_argument("--stream-slots", type=int, default=64)
    ap.add_argument("--window-slots", type=int, default=8)
    ap.add_argument("--method", default="deepstream")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-keep", type=int, default=None,
                    help="retention: keep the newest N checkpoint "
                         "generations (never the newest valid one)")
    ap.add_argument("--source", default=None,
                    help="hardened ingest source: file:PATH (tail a "
                         "line-protocol file) or HOST:PORT (TCP)")
    args = ap.parse_args()

    if args.fleet_stream:
        run_fleet_stream(args)
        return
    if not args.arch:
        ap.error("--arch is required for the LM engine "
                 "(or pass --fleet-stream)")

    from repro.configs import get_config, smoke_config
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.models.model import LM
    from repro.serve.engine import Request, ServeEngine

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh() if args.smoke else make_production_mesh()
    lm = LM(cfg, mesh)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    with mesh:
        eng = ServeEngine(lm, params, batch_slots=args.slots,
                          max_seq=args.max_seq)
        stats = eng.run(reqs)
    print({k: round(v, 3) if isinstance(v, float) else v
           for k, v in stats.items()})


if __name__ == "__main__":
    main()
