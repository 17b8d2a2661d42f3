"""The matrix products the reference models are written with.

``f32``: float32 operands at the highest matmul precision, the reference.
``fp8``: the control, the same products with operands rounded to float8:
e4m3 forward, e5m2 for the incoming gradient, each tensor scaled by its own
absolute maximum (the usual fp8 training recipe).  It is the precision step
below the bfloat16 the configurations state, and the correctness check
must reject it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def einsum_f32(spec: str, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _quantize(x, dtype):
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = float(jnp.finfo(dtype).max) / jnp.maximum(amax, 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def einsum_fp8(spec: str, a, b):
    return einsum_f32(spec, _quantize(a, jnp.float8_e4m3fn),
                      _quantize(b, jnp.float8_e4m3fn))


def _fp8_fwd(spec, a, b):
    qa = _quantize(a, jnp.float8_e4m3fn)
    qb = _quantize(b, jnp.float8_e4m3fn)
    return einsum_f32(spec, qa, qb), (qa, qb)


def _fp8_bwd(spec, res, ct):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: einsum_f32(spec, x, y), qa, qb)
    return vjp(_quantize(ct, jnp.float8_e5m2))


einsum_fp8.defvjp(_fp8_fwd, _fp8_bwd)

EINSUMS = {"f32": einsum_f32, "fp8": einsum_fp8}
