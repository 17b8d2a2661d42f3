"""Plain float32 reference of the dense decoder family (granite-8b).

Pre-norm blocks: RMSNorm, grouped-query attention with rotary positions
(the rotation pairs the two halves of each head), causal softmax, output
projection, residual; RMSNorm, SwiGLU feed-forward (silu(x W_gate) * x W_up,
then W_down), residual.  A final RMSNorm and an untied output head; the
loss is the mean next-token cross entropy over positions whose target is
not -1.

Written from these equations alone, in float32, with attention and the
loss taken in blocks of rows so that the reference fits one chip at the
timed sequence length.  It imports nothing of the program; the parameter
tree uses the program's leaf names because both sides start from the same
weights, which ``init`` makes from the seed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchlib.losses import ROW_BLOCK, cross_entropy


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {
        "d": d, "h": h, "kv": cfg["num_key_value_heads"], "dh": d // h,
        "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
    }


def program_sizes(cfg: dict) -> dict:
    """The configuration's sizes under the program's ArchConfig names."""
    n = dims(cfg)
    return {"n_layers": n["layers"], "d_model": n["d"], "n_heads": n["h"],
            "n_kv": n["kv"], "d_ff": n["f"], "vocab": n["v"],
            "rope_theta": cfg["rope_theta"]}


def init(cfg: dict, key, dtype):
    """Random weights from ``key``: matrices normal with std sqrt(2/fan_in),
    embedding and head normal with std 0.02, norm gains 1."""
    n = dims(cfg)
    d, h, kv, dh, f, v, L = (n[k] for k in ("d", "h", "kv", "dh", "f", "v", "layers"))
    k = iter(jax.random.split(key, 10))

    def normal(shape, std):
        return (std * jax.random.normal(next(k), shape, jnp.float32)).astype(dtype)

    ones = lambda shape: jnp.ones(shape, dtype)
    return {
        "embed": normal((v, d), 0.02),
        "final_norm": {"g": ones((d,))},
        "head": normal((d, v), 0.02),
        "blocks": {
            "ln1": {"g": ones((L, d))},
            "wq": normal((L, d, h, dh), math.sqrt(2 / d)),
            "wk": normal((L, d, kv, dh), math.sqrt(2 / d)),
            "wv": normal((L, d, kv, dh), math.sqrt(2 / d)),
            "wo": normal((L, h, dh, d), math.sqrt(2 / (h * dh))),
            "ln2": {"g": ones((L, d))},
            "ffn": {
                "w_up": normal((L, d, f), math.sqrt(2 / d)),
                "w_gate": normal((L, d, f), math.sqrt(2 / d)),
                "w_down": normal((L, f, d), math.sqrt(2 / f)),
            },
        },
    }


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotate(x, pos, theta):
    """Rotary positions on (B, S, H, Dh): pair element i with i + Dh/2."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * freqs[None]                      # (S, half)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, ein):
    """Causal grouped-query attention, one block of query rows at a time.
    q: (B, S, H, Dh); k, v: (B, S, KV, Dh)."""
    b, s, h, dh = q.shape
    kv = k.shape[2]
    qb = min(ROW_BLOCK, s)
    q = q.reshape(b, s // qb, qb, kv, h // kv, dh).swapaxes(0, 1) * dh ** -0.5
    kpos = jnp.arange(s)

    def block(args):
        qi, start = args
        scores = ein("bqkgd,bskd->bkgqs", qi, k)
        allowed = kpos[None, :] <= (start + jnp.arange(qb))[:, None]
        scores = jnp.where(allowed, scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return ein("bkgqs,bskd->bqkgd", p, v)

    out = jax.lax.map(jax.checkpoint(block), (q, jnp.arange(s // qb) * qb))
    return out.swapaxes(0, 1).reshape(b, s, h, dh)


def loss(params, batch, cfg: dict, ein):
    """Mean next-token cross entropy; params float32, batch (B, S) int."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    tokens, targets = batch["tokens"], batch["targets"]
    b, s = tokens.shape
    pos = jnp.arange(s, dtype=jnp.float32)
    x = params["embed"][tokens]

    def layer(x, p):
        hn = rms_norm(x, p["ln1"]["g"], eps)
        q = rotate(ein("bsd,dhk->bshk", hn, p["wq"]), pos, theta)
        k = rotate(ein("bsd,dhk->bshk", hn, p["wk"]), pos, theta)
        v = ein("bsd,dhk->bshk", hn, p["wv"])
        x = x + ein("bshk,hkd->bsd", attention(q, k, v, ein), p["wo"])
        hn = rms_norm(x, p["ln2"]["g"], eps)
        ff = p["ffn"]
        gated = jax.nn.silu(ein("bsd,df->bsf", hn, ff["w_gate"])) * ein(
            "bsd,df->bsf", hn, ff["w_up"])
        return x + ein("bsf,fd->bsd", gated, ff["w_down"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"])
    x = rms_norm(x, params["final_norm"]["g"], eps)
    return cross_entropy(x, targets, params["head"], ein)
