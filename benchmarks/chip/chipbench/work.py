"""The work the algorithm needs, computed from shapes: detector FLOPs per
frame and per served slot.

Only what the algorithm needs is counted, for live cameras only: dead
cameras' compute, padding, and the 0/1 selection matmuls that an
implementation may use to move data are not work, so a change that drops
them reads the same work in less time.
"""
from __future__ import annotations

from typing import Dict


def conv_flops(params_shapes: Dict[str, tuple], height: int, width: int
               ) -> float:
    """Forward FLOPs (2 per multiply-add) of one frame through the detector:
    four 3x3 stride-2 SAME convs and a 1x1 head."""
    h, w = height, width
    flops = 0.0
    for i in (1, 2, 3, 4):
        kh, kw, cin, cout = params_shapes[f"c{i}"]
        h, w = -(-h // 2), -(-w // 2)
        flops += 2.0 * h * w * kh * kw * cin * cout
    kh, kw, cin, cout = params_shapes["head"]
    flops += 2.0 * h * w * kh * kw * cin * cout
    return flops


def detector_shapes(variant: str) -> Dict[str, tuple]:
    c1, c2, c3, c4 = {"light": (8, 16, 32, 32),
                      "server": (16, 32, 64, 64)}[variant]
    return {"c1": (3, 3, 1, c1), "c2": (3, 3, c1, c2), "c3": (3, 3, c2, c3),
            "c4": (3, 3, c3, c4), "head": (1, 1, c4, 5)}


def step_flops(cfg: Dict, live_cam_slots: int) -> float:
    """Detector FLOPs the algorithm needs over slots whose live cameras sum
    to ``live_cam_slots``: the server detector on the scored frames of every
    live camera (plus the reused frame for reducto), the light detector on
    the first and last frame for deepstream."""
    sc = cfg["scene"]
    H, W = sc["height"], sc["width"]
    N = int(sc["fps"] * sc["seg_seconds"])
    F = min(cfg["system"]["eval_frames"], N)
    server = conv_flops(detector_shapes("server"), H, W)
    light = conv_flops(detector_shapes("light"), H, W)
    if cfg["method"] == "reducto":
        per_cam = (F + 1) * server
    else:
        per_cam = F * server + 2 * light
    return per_cam * live_cam_slots
