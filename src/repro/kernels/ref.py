"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# the WKV kernel's oracle: the step-by-step scan of RWKV-6's recurrence
from repro.models.recurrence import rwkv_scan_reference as wkv_ref

__all__ = ["flash_attention_ref", "gossip_update_ref", "l2_norms_ref", "wkv_ref"]


def flash_attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
) -> jax.Array:
    """q: (B, H, Sq, D); k/v: (B, KV, Sk, D) -> (B, H, Sq, D)."""
    b, h, sq, d = q.shape
    kv, sk = k.shape[1], k.shape[2]
    group = h // kv
    qg = q.reshape(b, kv, group, sq, d).astype(jnp.float32) / math.sqrt(d)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k.astype(jnp.float32))
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows -> zero output
    p = jnp.where(mask.any(-1)[None, None, None, :, None], p, 0.0)
    o = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    return o.reshape(b, h, sq, d).astype(q.dtype)


def gossip_update_ref(
    theta: jax.Array,       # (P,) this node's post-backward params
    neighbors: jax.Array,   # (deg, P) neighbor params (post their updates)
    weights: jax.Array,     # (deg + 1,): [self, n_1, ..., n_deg]
    grad: jax.Array,        # (P,)
    momentum: jax.Array,    # (P,)
    *,
    lr: float,
    beta: float,
) -> tuple[jax.Array, jax.Array]:
    """Fused decentralized-SGD apply:

      m'     = beta * m + g
      theta* = theta - lr * m'          (local descent)
      theta' = w_0 * theta* + sum_i w_i * n_i   (gossip average)
    """
    tf = theta.astype(jnp.float32)
    m_new = beta * momentum.astype(jnp.float32) + grad.astype(jnp.float32)
    local = tf - lr * m_new
    mixed = weights[0] * local + jnp.einsum(
        "n,np->p", weights[1:].astype(jnp.float32), neighbors.astype(jnp.float32)
    )
    return mixed.astype(theta.dtype), m_new


def l2_norms_ref(x: jax.Array) -> jax.Array:
    """Row L2 norms of a (R, P) matrix -> (R,) float32 (DBench probe)."""
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1))
