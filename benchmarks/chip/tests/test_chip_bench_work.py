"""The work functions against hand counts, and the weights' shapes."""
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP_DIR))

from chipbench import spec, work  # noqa: E402

# 2 x multiply-adds of the four 3x3 stride-2 convs and the 1x1 head at
# 96x160 (outputs 48x80, 24x40, 12x20, 6x10), counted by hand
SERVER_FLOPS = 2 * (48 * 80 * 9 * 1 * 16 + 24 * 40 * 9 * 16 * 32
                    + 12 * 20 * 9 * 32 * 64 + 6 * 10 * 9 * 64 * 64
                    + 6 * 10 * 64 * 5)
LIGHT_FLOPS = 2 * (48 * 80 * 9 * 1 * 8 + 24 * 40 * 9 * 8 * 16
                   + 12 * 20 * 9 * 16 * 32 + 6 * 10 * 9 * 32 * 32
                   + 6 * 10 * 32 * 5)


def test_detector_flops_match_hand_counts():
    assert SERVER_FLOPS == 23_262_720 and LIGHT_FLOPS == 6_101_760
    assert work.conv_flops(work.detector_shapes("server"), 96, 160) \
        == SERVER_FLOPS
    assert work.conv_flops(work.detector_shapes("light"), 96, 160) \
        == LIGHT_FLOPS


@pytest.mark.parametrize("variant", ["light", "server"])
def test_detector_shapes_are_the_committed_weights(variant):
    with np.load(CHIP_DIR / "weights" / f"detector_{variant}.npz") as z:
        for k, shape in work.detector_shapes(variant).items():
            assert z[k].shape == shape


def test_step_flops_count_live_cameras_only():
    ds = spec.load_config("s01-deepstream")
    rd = spec.load_config("s01-reducto")
    # deepstream: 3 scored frames on the server + first and last frame on
    # the light detector per live camera; reducto: 3 scored + 1 reused
    assert work.step_flops(ds, 7) == 7 * (3 * SERVER_FLOPS + 2 * LIGHT_FLOPS)
    assert work.step_flops(rd, 7) == 7 * 4 * SERVER_FLOPS
    assert work.step_flops(ds, 0) == 0
