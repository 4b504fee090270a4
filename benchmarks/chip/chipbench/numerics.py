"""How the plain reference multiplies.

``f32`` is the reference itself: float32 operands at the highest matmul
precision (a float32 matmul on a TPU otherwise runs in bfloat16 passes).
``fp8`` is its control, the step below the bfloat16 the configurations
state: both operands of every matrix product are rounded to float8 e4m3
with one scale per tensor (its largest magnitude mapped to e4m3's 448),
then multiplied in float32. Everything that is not a matrix product
(norms, gates, softmax, scans) stays float32 in both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
E4M3_MAX = 448.0


class Numerics:
    def __init__(self, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown reference precision {mode!r}")
        self.mode = mode

    def q(self, a):
        a = a.astype(F32)
        if self.mode == "f32":
            return a
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / E4M3_MAX
        scale = jax.lax.stop_gradient(scale)
        r = (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
        # straight-through: the rounding is part of the forward only
        return a + jax.lax.stop_gradient(r - a)

    def ein(self, spec, *ops):
        return jnp.einsum(spec, *[self.q(o) for o in ops], precision=HIGHEST,
                          preferred_element_type=F32)

    def mm(self, a, b):
        return jnp.matmul(self.q(a), self.q(b), precision=HIGHEST,
                          preferred_element_type=F32)
