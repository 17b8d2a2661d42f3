"""Jitted public wrappers for the Pallas kernels.

``interpret`` defaults to True on non-TPU backends (this container is
CPU-only; interpret mode executes the kernel bodies exactly, so tests are
bit-meaningful) and False on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gossip_update import gossip_update as _gossip
from repro.kernels.stats import l2_norms as _l2
from repro.kernels.wkv import wkv as _wkv

__all__ = [
    "flash_attention",
    "gossip_update",
    "gossip_program_update",
    "l2_norms",
    "wkv",
    "default_interpret",
]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, window=None, block_q=128, block_k=128,
                    interpret=None):
    """(B, H, Sq, D) x (B, KV, Sk, D)² -> (B, H, Sq, D)."""
    itp = default_interpret() if interpret is None else interpret
    return _flash(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=itp,
    )


def gossip_update(theta, neighbors, weights, grad, momentum, *, lr, beta,
                  block=1024, interpret=None, mix_order="post"):
    """One node's leaf over deg leaf-shaped neighbor buffers (a sequence,
    or one array stacked on a leading axis); lr/beta are runtime scalars
    (LR schedules do not retrigger compiles); interpret=None auto-detects
    the backend inside the kernel module."""
    return _gossip(
        theta, neighbors, weights, grad, momentum,
        lr=lr, beta=beta, block=block, interpret=interpret,
        mix_order=mix_order,
    )


def gossip_program_update(theta, neighbors, weights, grad, momentum, *, lr,
                          beta, block=1024, interpret=None, mix_order="post"):
    """(n, P) stacked executor with per-node (deg+1,) weight rows;
    ``neighbors`` is a sequence of deg (n, P) landing buffers."""
    from repro.kernels.gossip_update import gossip_program_update as _prog

    return _prog(
        theta, neighbors, weights, grad, momentum,
        lr=lr, beta=beta, block=block, interpret=interpret,
        mix_order=mix_order,
    )


def l2_norms(x, *, block=2048, interpret=None):
    itp = default_interpret() if interpret is None else interpret
    return _l2(x, block=block, interpret=itp)


def wkv(r, k, v, logw, u, s0, *, interpret=None):
    """RWKV-6's WKV recurrence, forward and backward kernels bound by a
    ``custom_vjp``: r/k/v/logw (B, L, H, N), u (H, N), s0 (B, H, N, N) ->
    (o (B, L, H, N), final state float32), as ``ref.wkv_ref``."""
    itp = default_interpret() if interpret is None else interpret
    return _wkv(r, k, v, logw, u, s0, interpret=itp)
