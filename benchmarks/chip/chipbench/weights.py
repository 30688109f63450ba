"""The weights a cell serves with, owned by the benchmark.

* Detectors: the light (on-camera) and server detectors as the repository
  committed them, copied into ``weights/*.npz`` so that a later change to the
  program's artifacts cannot move the benchmark.  Random weights would make
  every F1, and so every utility, near zero, and the content-aware
  allocation would then degenerate.
* Utility MLP: 4 -> 32 -> 32 -> 1, fan-in scaled normal weights (scale 2)
  and zero biases from ``PRNGKey(key)``, one key per leaf in sorted leaf
  order, made on the device in one jitted call.
"""
from __future__ import annotations

from functools import partial
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

CHIP_DIR = Path(__file__).resolve().parents[1]
HIDDEN = 32


def load_detector(rel_path: str) -> Dict[str, jax.Array]:
    with np.load(CHIP_DIR / rel_path) as z:
        return {k: jnp.asarray(z[k], jnp.float32) for k in sorted(z.files)}


@partial(jax.jit, static_argnums=0)
def make_mlp(key: int) -> Dict[str, jax.Array]:
    shapes = {"b1": (HIDDEN,), "b2": (HIDDEN,), "b3": (1,),
              "w1": (4, HIDDEN), "w2": (HIDDEN, HIDDEN), "w3": (HIDDEN, 1)}
    keys = jax.random.split(jax.random.PRNGKey(key), len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.startswith("b"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = jax.random.normal(k, shape, jnp.float32) * (
                2.0 / np.sqrt(shape[0]))
    return out
