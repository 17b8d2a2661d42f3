"""RWKV6 ("Finch", arXiv:2404.05892) block: data-dependent decay WKV.

Faithful structure: LayerNormed sublayers, token-shift lerps, LoRA-modulated
data-dependent decay ``w_t = exp(-exp(w0 + lora_w(x̄_t)))``, bonus ``u``,
per-head group norm, SiLU-gated output, squared-ReLU channel mix.  One
departure: the full Finch also LoRA-modulates the token-shift lerp
coefficients (ddlerp); here they are static.  That LoRA adds parameter
leaves, and the chip benchmark's plain reference (``bench/reference/``)
pins today's parameter tree, so it waits for the benchmark to move with
it; the decay, Finch's headline data-dependence, is fully dynamic.

Device scopes (``repro.telemetry.profile``): the time mix runs under
``time_mix``, its WKV recurrence under ``time_mix/wkv``, and the channel
mix, the block's feed-forward, under ``mlp``.

State per layer: (wkv (B, H, N, N), previous *normed* token for each of the
two token-shifted sublayers).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.common import (
    ParamDef,
    he_normal,
    layer_norm,
    normal_init,
    ones_init,
    zeros_init,
)
from repro.models.recurrence import rwkv_chunked, rwkv_step
from repro.telemetry import profile

__all__ = ["rwkv_block_defs", "apply_rwkv_block", "rwkv_block_decode", "RWKVState"]

_LORA_RANK = 64
# The per-head norm's eps, as the published RWKV-6 code sets it: 1e-5 times
# the square of its head_size_divisor, 8.  The norm divides each head's WKV
# output by its spread, and the first token's output is the bonus term
# alone, a scalar times v: where that scalar is near zero, the eps bounds
# how steeply the gradient depends on it.
GROUP_NORM_EPS = 64e-5


class RWKVState(NamedTuple):
    wkv: jax.Array       # (B, H, N, N)
    shift_tm: jax.Array  # (B, D) previous normed token (time mix)
    shift_cm: jax.Array  # (B, D) previous normed token (channel mix)

    @classmethod
    def empty(cls, batch, n_heads, d_head, d_model, dtype=jnp.float32):
        return cls(
            wkv=jnp.zeros((batch, n_heads, d_head, d_head), jnp.float32),
            shift_tm=jnp.zeros((batch, d_model), dtype),
            shift_cm=jnp.zeros((batch, d_model), dtype),
        )


def rwkv_block_defs(d_model: int, n_heads: int, d_ff: int, dtype=jnp.float32):
    d, h = d_model, n_heads
    n = d // h
    lin = lambda i, o: ParamDef((i, o), he_normal((-2,)), (None, "model"), dtype)
    vec1 = lambda init: ParamDef((d,), init, (None,), dtype)
    return {
        "ln1_g": vec1(ones_init()),
        "ln1_b": vec1(zeros_init()),
        "ln2_g": vec1(ones_init()),
        "ln2_b": vec1(zeros_init()),
        "time_mix": {
            "mu": ParamDef((5, d), normal_init(0.1), (None, None), dtype),
            "w_r": lin(d, d),
            "w_k": lin(d, d),
            "w_v": lin(d, d),
            "w_g": lin(d, d),
            "w_o": ParamDef((d, d), he_normal((-2,)), ("model", None), dtype),
            "decay_w0": vec1(zeros_init()),
            "decay_a": ParamDef((d, _LORA_RANK), normal_init(0.02), (None, None), dtype),
            "decay_b": ParamDef((_LORA_RANK, d), zeros_init(), (None, None), dtype),
            "bonus_u": ParamDef((h, n), normal_init(0.1), (None, None), dtype),
            "gn_g": vec1(ones_init()),
            "gn_b": vec1(zeros_init()),
        },
        "channel_mix": {
            "mu": ParamDef((2, d), normal_init(0.1), (None, None), dtype),
            "w_k": lin(d, d_ff),
            "w_v": ParamDef((d_ff, d), he_normal((-2,)), ("model", None), dtype),
            "w_r": ParamDef((d, d), he_normal((-2,)), (None, None), dtype),
        },
    }


def _shift(x: jax.Array, prev: jax.Array) -> jax.Array:
    """Token shift: x̄_t = x_{t-1} (prev fills t=0). x: (B, S, D), prev: (B, D)."""
    return jnp.concatenate([prev[:, None], x[:, :-1]], axis=1)


def _lerp(x, xx, mu):
    return x + (xx - x) * mu


def _decay_logw(tm, xw: jax.Array) -> jax.Array:
    """log w_t = -exp(w0 + lora(x)) < 0; clipped for stability."""
    lora = jnp.tanh(xw @ tm["decay_a"]) @ tm["decay_b"]
    return -jnp.exp(jnp.clip(tm["decay_w0"] + lora, -8.0, 6.0))


def _group_norm(x: jax.Array, n_heads: int, g, b, eps=GROUP_NORM_EPS) -> jax.Array:
    """Per-head LayerNorm of (B, S, D)."""
    bsz, s, d = x.shape
    xh = x.reshape(bsz, s, n_heads, d // n_heads).astype(jnp.float32)
    mu = xh.mean(-1, keepdims=True)
    var = xh.var(-1, keepdims=True)
    xh = (xh - mu) * jax.lax.rsqrt(var + eps)
    return (xh.reshape(bsz, s, d) * g + b).astype(x.dtype)


def _time_mix_inputs(tm, x, shifted, n_heads):
    b, s, d = x.shape
    n = d // n_heads
    mu = tm["mu"]
    xr, xk, xv, xg, xw = (_lerp(x, shifted, mu[i]) for i in range(5))
    r = (xr @ tm["w_r"]).reshape(b, s, n_heads, n)
    k = (xk @ tm["w_k"]).reshape(b, s, n_heads, n)
    v = (xv @ tm["w_v"]).reshape(b, s, n_heads, n)
    g = jax.nn.silu(xg @ tm["w_g"])
    logw = _decay_logw(tm, xw).reshape(b, s, n_heads, n)
    return r, k, v, g, logw


def _channel_mix(cm, xn, shifted):
    mu = cm["mu"]
    xk = _lerp(xn, shifted, mu[0])
    xr = _lerp(xn, shifted, mu[1])
    kk = jnp.square(jax.nn.relu(xk @ cm["w_k"]))
    return jax.nn.sigmoid(xr @ cm["w_r"]) * (kk @ cm["w_v"])


def apply_rwkv_block(
    params, x: jax.Array, state: RWKVState, *, n_heads: int, chunk: int = 32
) -> tuple[jax.Array, RWKVState]:
    """Full block (time mix + channel mix, own norms/residuals). x: (B, S, D)."""
    b, s, d = x.shape
    tm, cm = params["time_mix"], params["channel_mix"]

    xn = layer_norm(x, params["ln1_g"], params["ln1_b"])
    with profile.scope("time_mix"):
        shifted = _shift(xn, state.shift_tm)
        r, k, v, g, logw = _time_mix_inputs(tm, xn, shifted, n_heads)
        with profile.scope("wkv"):
            o, wkv = rwkv_chunked(r, k, v, logw, tm["bonus_u"], state.wkv, chunk=chunk)
        o = _group_norm(o.reshape(b, s, d), n_heads, tm["gn_g"], tm["gn_b"])
        h = x + (o * g) @ tm["w_o"]

    hn = layer_norm(h, params["ln2_g"], params["ln2_b"])
    with profile.scope("mlp"):
        shifted_c = _shift(hn, state.shift_cm)
        out = h + _channel_mix(cm, hn, shifted_c)

    return out, RWKVState(wkv=wkv, shift_tm=xn[:, -1], shift_cm=hn[:, -1])


def rwkv_block_decode(
    params, x: jax.Array, state: RWKVState, *, n_heads: int
) -> tuple[jax.Array, RWKVState]:
    """Single-token step. x: (B, D)."""
    b, d = x.shape
    tm, cm = params["time_mix"], params["channel_mix"]

    xn = layer_norm(x[:, None], params["ln1_g"], params["ln1_b"])[:, 0]
    r, k, v, g, logw = _time_mix_inputs(
        tm, xn[:, None], state.shift_tm[:, None], n_heads
    )
    o, wkv = rwkv_step(r[:, 0], k[:, 0], v[:, 0], logw[:, 0], tm["bonus_u"], state.wkv)
    o = _group_norm(o.reshape(b, 1, d), n_heads, tm["gn_g"], tm["gn_b"])[:, 0]
    h = x + (o * g[:, 0]) @ tm["w_o"]

    hn = layer_norm(h[:, None], params["ln2_g"], params["ln2_b"])[:, 0]
    out = h + _channel_mix(cm, hn[:, None], state.shift_cm[:, None])[:, 0]

    return out, RWKVState(wkv=wkv, shift_tm=xn, shift_cm=hn)
