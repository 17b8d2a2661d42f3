"""Pallas TPU flash-attention kernel (causal, GQA, optional sliding window).

TPU-native adaptation (DESIGN.md §2): the CUDA flash-attention tiling is
re-expressed for the TPU memory hierarchy — HBM→VMEM block streaming with
MXU-aligned (128×128) tiles and an online-softmax accumulator held in VMEM
scratch across the sequential K grid dimension.  One grid step computes one
(q-block × k-block) tile; the K dimension is the innermost ("arbitrary")
grid axis so the scratch accumulators carry across it.

Layouts:
  q:    (B, H, Sq, D)
  k/v:  (B, KV, Sk, D)      (GQA: KV | H, mapped via h // (H // KV))
  out:  (B, H, Sq, D)

Validated against ``ref.flash_attention_ref`` in interpret mode on CPU
(tests/test_kernels.py).  ``interpret=None`` (the default) compiles the
kernel on a TPU backend and interprets it everywhere else.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int | None, bq: int, bk: int, nk: int
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                   # (bq, bk)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.where(m_prev <= _NEG_INF / 2, 0.0, jnp.exp(m_prev - m_safe))
        l_scr[...] = l_prev * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    if causal:
        # whole block strictly above the diagonal: nothing to do
        pl.when(ki * bk <= qi * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """q: (B, H, Sq, D); k/v: (B, KV, Sk, D) with H % KV == 0 -> (B, H, Sq, D)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, sq, d = q.shape
    kv, sk = k.shape[1], k.shape[2]
    group = h // kv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"seq lens ({sq},{sk}) must tile by ({bq},{bk})")
    nq, nk = sq // bq, sk // bk
    scale = 1.0 / math.sqrt(d)

    grid = (b, h, nq, nk)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale, causal=causal, window=window, bq=bq, bk=bk, nk=nk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),   # running max m
            pltpu.VMEM((bq,), jnp.float32),   # running sum l
            pltpu.VMEM((bq, d), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out
