"""The detector stem on a space-to-depth input (``models/detector._stem``).

On frames whose sides are multiples of ``STEM_FOLD`` and that hold at least
``STEM_MIN_PIXELS``, the stem's stride-2 convs run stride-1 on the frame
folded into channels; elsewhere they run as plain convs.  The oracle is the plain formulation, ``detector.forward`` as
it was before the fold, copied here: both paths must give its grid (only
the f32 summation order differs), its gradients and its grid shapes, and
the path must follow from the frames' shape alone."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import programs
from repro.common import obs
from repro.core import fleet as fleet_mod
from repro.models import detector as det


def _conv(x, w, b, stride=2):
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.nn.relu(y + b)


def plain_forward(params, frames):
    """``detector.forward`` before the folded stem."""
    x = frames[..., None]
    x = _conv(x, params["c1"], params["b1"])
    x = _conv(x, params["c2"], params["b2"])
    x = _conv(x, params["c3"], params["b3"])
    x = _conv(x, params["c4"], params["b4"])
    y = jax.lax.conv_general_dilated(
        x, params["head"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + params["bh"]
    return y


def _frames(b, h, w, seed=0):
    return jax.random.uniform(jax.random.PRNGKey(seed), (b, h, w))


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)),
                                                       1e-30))


# (variant, batch, H, W, path): the served 96x160 stand-in (below the
# size), CityFlow's 1080x1920 (a height 16 does not divide), 640x360, and a
# frame the fold does not divide
CASES = [
    ("light", 2, 96, 160, "plain"), ("server", 2, 96, 160, "plain"),
    ("light", 1, 1080, 1920, "folded"), ("server", 1, 1080, 1920, "folded"),
    ("server", 1, 360, 640, "folded"),
    ("light", 2, 50, 70, "plain"), ("server", 2, 50, 70, "plain"),
]


@pytest.mark.parametrize("variant,b,h,w,path", CASES)
def test_forward_matches_the_plain_convs(detectors, variant, b, h, w, path):
    params = dict(zip(("light", "server"), detectors))[variant]
    frames = _frames(b, h, w)
    before = obs.detector_stem_counts()
    got = det.forward(params, frames)
    want = plain_forward(params, frames)
    fold = det.STEM_FOLD if path == "folded" else 1
    key = (path, fold, (b, h, w))
    assert obs.detector_stem_counts()[key] == before.get(key, 0) + 1
    assert got.shape == want.shape == (b, -(-h // 16), -(-w // 16), 5)
    assert _rel(got, want) <= 1e-5
    if path == "plain":
        assert _rel(got, want) == 0.0


def test_1080p_grid_is_68x120(detectors):
    light, _ = detectors
    out = jax.eval_shape(det.forward, light,
                         jax.ShapeDtypeStruct((10, 1080, 1920), jnp.float32))
    assert out.shape == (10, 68, 120, 5)


def test_stem_fold_follows_the_shape():
    f = det.STEM_FOLD
    assert det.stem_fold((15, 1080, 1920)) == f
    assert det.stem_fold((1, 360, 640)) == f
    assert det.stem_fold((1, 192, 320)) == f
    assert det.stem_fold((75, 96, 160)) == 1          # below the size
    assert det.stem_fold((1, 184, 320)) == 1
    assert det.stem_fold((1, 1080, 1920 - f // 2)) == 1
    assert det.stem_fold((2, 50, 70)) == 1


def test_folded_kernel_places_every_weight_once(detectors):
    """Each weight of the 3x3 kernel lands in the folded kernels exactly as
    many times as it has output phases; every other entry is an exact
    zero, so the conv's operands are the committed weights."""
    _, server = detectors
    fold = det.STEM_FOLD
    assert fold == 2 ** det.STEM_CONVS
    for name in ("c1", "c2", "c3")[:det.STEM_CONVS]:
        w = np.asarray(server[name])
        k = np.asarray(det._folded_kernel(server[name], fold))
        fo = fold // 2
        assert k.shape == (2, 2, fold * fold * w.shape[2],
                           fo * fo * w.shape[3])
        assert np.count_nonzero(k) == np.count_nonzero(w) * fo * fo
        assert set(np.unique(k)) <= set(np.unique(w)) | {0.0}
        fold //= 2


def _targets(b, h, w):
    gy, gx = -(-h // det.STRIDE), -(-w // det.STRIDE)
    boxes = [(10, 12, 30, 28), (w - 40, h - 30, w - 12, h - 6)]
    return jnp.asarray(np.stack([det.encode_targets(boxes, gy, gx)] * b))


@pytest.mark.parametrize("b,h,w", [(1, 360, 640), (2, 50, 70)])
def test_detection_loss_gradients_agree(detectors, monkeypatch, b, h, w):
    _, server = detectors
    frames, targets = _frames(b, h, w, seed=1), _targets(b, h, w)
    loss_grad = jax.value_and_grad(det.detection_loss)
    got_l, got_g = loss_grad(server, frames, targets)
    monkeypatch.setattr(det, "forward", plain_forward)
    want_l, want_g = loss_grad(server, frames, targets)
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-5)
    for k in want_g:
        assert _rel(got_g[k], want_g[k]) <= 1e-5, k


# -- the 1080p fleet episode, lowered from shapes -------------------------------

def test_1080p_episode_records_the_folded_stem(monkeypatch):
    """Lowering the deepstream episode at CityFlow's 1080x1920 (nothing
    runs) traces the folded stem for the light detector's first-and-last
    frames of 5 cameras and the server detector's 3 frames of 5 cameras,
    each inside its own layer scope."""
    monkeypatch.setattr(fleet_mod, "_EXEC_CACHE", {})
    monkeypatch.setattr(fleet_mod, "_EPISODE_COMPILE_COUNTS", {})
    canon = programs.Canonical()
    H, W = 1080, 1920
    canon.scfg = dataclasses.replace(canon.scfg, height=H, width=W)
    canon.H, canon.W = H, W
    canon.scene_params = canon.scene_params._replace(
        backgrounds=programs._sds((canon.C, H, W), jnp.float32))
    fn = fleet_mod._get_episode_executable(
        None, **canon.episode_statics("deepstream"))
    before = obs.detector_stem_counts()
    text = fn.lower(*canon.episode_args("deepstream", 8)).as_text(
        debug_info=True)
    new = {k: n - before.get(k, 0)
           for k, n in obs.detector_stem_counts().items()
           if n > before.get(k, 0)}
    f = det.STEM_FOLD
    assert ("folded", f, (10, H, W)) in new
    assert ("folded", f, (15, H, W)) in new
    assert all(path == "folded" for path, _, _ in new)
    assert "roidet/stem/" in text and "detect/stem/" in text
