# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
import jax
import jax.numpy as jnp


def pallas_interpret() -> bool:
    """Kernel mode for every Pallas wrapper, read when the wrapper traces:
    compiled (Mosaic) exactly when the default backend is a TPU, the Pallas
    interpreter everywhere else (the CPU test backend)."""
    return jax.default_backend() != "tpu"


def f32_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """In-kernel 2-D matmul at fp32 contract precision.  The 0/1 pooling
    and selection matrices of the kernels rely on it: a single bf16 MXU
    pass would round the other operand."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
