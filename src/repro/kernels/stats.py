"""Blocked L2-norm reduction kernel (the DBench in-step probe).

DBench reads the L2 norm of every parameter tensor on every node each
iteration (paper §3.1.2, ``torch.tensor.norm()``).  At 10⁹-parameter scale
that probe is itself a full HBM sweep, so it gets a kernel: rows are
reduced block-by-block into an SMEM accumulator (f32), one grid row per
tensor.  Layout: tensors are flattened and zero-padded into an (R, P) matrix
(R = number of probed tensors); zero padding does not change an L2 norm.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["l2_norms"]


def _kernel(x_ref, o_ref, acc_ref, *, nblocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[0] = 0.0

    x = x_ref[0].astype(jnp.float32)
    acc_ref[0] += jnp.sum(x * x)

    @pl.when(j == nblocks - 1)
    def _fin():
        o_ref[0] = jnp.sqrt(acc_ref[0])


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def l2_norms(x: jax.Array, *, block: int = 2048,
             interpret: bool | None = None) -> jax.Array:
    """Row L2 norms of (R, P) -> (R,) float32.  ``interpret=None`` compiles
    the kernel on a TPU backend and interprets it everywhere else."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    r, p = x.shape
    block = min(block, p)
    if p % block:
        pad = (-p) % block
        x = jnp.pad(x, ((0, 0), (0, pad)))
        p += pad
    nblocks = p // block
    return pl.pallas_call(
        functools.partial(_kernel, nblocks=nblocks),
        grid=(r, nblocks),
        in_specs=[pl.BlockSpec((1, block), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((r,), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
        name="l2_norms",
    )(x)
