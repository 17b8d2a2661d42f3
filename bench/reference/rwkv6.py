"""Plain float32 reference of the RWKV-6 ("Finch", arXiv:2404.05892) family,
initialised as the published RWKV-6 code initialises the time mix.

Per block, with LayerNorm (eps ``layer_norm_epsilon``) and residuals:

  time mix    x̄_t = LN(x)_{t-1} (zero at t = 0); five static lerps
              x_i = LN(x) + (x̄ - LN(x)) * mu_i give r, k, v, g and the decay
              input; r, k, v = x_i W_i per head of n = head_size channels;
              g = silu(x_g W_g); log w_t = -exp(clip(w0 + tanh(x_w A) B, -8, 6))
              S_t = diag(w_t) S_{t-1} + k_t v_tᵀ
              o_t = r_tᵀ (S_{t-1} + diag(u) k_t v_tᵀ)
              o -> per-head LayerNorm (eps ``group_norm_epsilon``, gain,
              bias) -> (o * g) W_o
  channel mix x̄ as above from LN2(x); k = relu(x_k W_k)²;
              out = sigmoid(x_r W_r) * (k W_v)

Then LayerNorm and the output head; the loss is the mean next-token cross
entropy.  Departure from Finch, shared with the program: the token-shift
lerps are static (Finch makes them data-dependent with a LoRA), and the
decay exponent is clipped to [-8, 6].

The per-head norm's eps is the published code's, 1e-5 times the square of
its ``head_size_divisor`` 8.  The weights follow the published
initialisation wherever it sets a scale: with layer i of the published
``L`` (the first layers of the stack are the ones held here), the lerp
coefficients are 1 - (c/d)^p with the published powers, the decay offset
of channel c is -6 + 5 (c/(d-1))^(0.7 + 1.3 i/(L-1)), the bonus is
i/(L-1) (1 - c/(d-1)) plus a zigzag of 0.1, and the r, k, v and g
projections have the published gains 1, 0.1, 1 and 0.1 (as normals of std
gain / sqrt(fan_in)).  Where the published code starts a weight at zero
(the time mix's output, the channel mix's receptance and value) or the
decay LoRA's first factor at zero, the weight is random here, so that
every leaf has a gradient at the first step; these, the channel mix's key,
the embedding and the head are normals as ``init`` says.

The recurrence runs one token at a time, the plainest form; each layer is
recomputed in the backward pass so that one chip holds it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchlib.losses import cross_entropy

LORA_RANK = 64
SAVE_EVERY = 64


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    n = cfg["head_size"]
    return {"d": d, "h": d // n, "n": n, "f": cfg["intermediate_size"],
            "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def program_sizes(cfg: dict) -> dict:
    """The configuration's sizes under the program's ArchConfig names."""
    n = dims(cfg)
    return {"n_layers": n["layers"], "d_model": n["d"], "n_heads": n["h"],
            "d_ff": n["f"], "vocab": n["v"]}


def published_layers(cfg: dict) -> int:
    """The published depth, which the initialisation's layer ratios use."""
    cut = cfg.get("reduced", {}).get("num_hidden_layers")
    return cut["published"] if cut else cfg["num_hidden_layers"]


def init(cfg: dict, key, dtype):
    """Weights from ``key`` and the published initialisation (module
    docstring): the r, k, v and g projections normal with std gain /
    sqrt(fan_in); the time mix's output, the channel mix's projections
    normal with std sqrt(2/fan_in); the decay LoRA normal with std 0.02;
    embedding and head normal with std 0.02; gains 1, biases 0."""
    n = dims(cfg)
    d, h, hn, f, v, L = (n[k] for k in ("d", "h", "n", "f", "v", "layers"))
    big_l = published_layers(cfg)
    k = iter(jax.random.split(key, 20))

    def normal(shape, std):
        return (std * jax.random.normal(next(k), shape, jnp.float32)).astype(dtype)

    ones = lambda shape: jnp.ones(shape, dtype)
    zeros = lambda shape: jnp.zeros(shape, dtype)
    lin = lambda i, o, gain=math.sqrt(2): normal((L, i, o), gain / math.sqrt(i))

    layer = jnp.arange(L, dtype=jnp.float32)[:, None]
    r01 = layer / (big_l - 1)              # 0 at the first layer, 1 at the last
    r1a0 = 1.0 - layer / big_l             # 1 at the first layer, towards 0
    ddd = jnp.arange(d, dtype=jnp.float32)[None] / d
    ramp = jnp.arange(d, dtype=jnp.float32)[None] / (d - 1)
    zigzag = ((jnp.arange(d)[None] + 1) % 3 - 1) * 0.1
    mu_tm = jnp.stack([1 - ddd ** (0.5 * r1a0),             # r
                       1 - ddd ** r1a0,                     # k
                       1 - (ddd ** r1a0 + 0.3 * r01),       # v
                       1 - ddd ** (0.5 * r1a0),             # g
                       1 - ddd ** r1a0], axis=1)            # decay input
    mu_cm = jnp.stack([1 - ddd ** r1a0, 1 - ddd ** r1a0], axis=1)
    w0 = -6 + 5 * ramp ** (0.7 + 1.3 * r01)
    u = (r01 * (1 - ramp) + zigzag).reshape(L, h, hn)
    return {
        "embed": normal((v, d), 0.02),
        "final_norm": {"g": ones((d,)), "b": zeros((d,))},
        "head": normal((d, v), 0.02),
        "blocks": {
            "ln1_g": ones((L, d)), "ln1_b": zeros((L, d)),
            "ln2_g": ones((L, d)), "ln2_b": zeros((L, d)),
            "time_mix": {
                "mu": mu_tm.astype(dtype),
                "w_r": lin(d, d, 1.0), "w_k": lin(d, d, 0.1), "w_v": lin(d, d, 1.0),
                "w_g": lin(d, d, 0.1), "w_o": lin(d, d),
                "decay_w0": w0.astype(dtype),
                "decay_a": normal((L, d, LORA_RANK), 0.02),
                "decay_b": normal((L, LORA_RANK, d), 0.02),
                "bonus_u": u.astype(dtype),
                "gn_g": ones((L, d)), "gn_b": zeros((L, d)),
            },
            "channel_mix": {
                "mu": mu_cm.astype(dtype),
                "w_k": lin(d, f), "w_v": lin(f, d), "w_r": lin(d, d),
            },
        },
    }


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def wkv(r, k, v, logw, u):
    """The recurrence, token by token. r, k, v, logw: (B, S, H, N).  The
    backward pass keeps the state every SAVE_EVERY tokens and recomputes
    the tokens between."""
    b, s, h, n = r.shape
    c = min(SAVE_EVERY, s)

    def step(state, inp):
        rt, kt, vt, wt = inp                                # (B, H, N)
        kv = kt[..., :, None] * vt[..., None, :]            # (B, H, N, N)
        o = jnp.sum(rt[..., :, None] * (state + u[..., None] * kv), axis=-2)
        return jnp.exp(wt)[..., None] * state + kv, o

    def stretch(state, inp):
        return jax.lax.scan(step, state, inp, unroll=8)

    xs = tuple(a.swapaxes(0, 1).reshape(s // c, c, b, h, n) for a in (r, k, v, logw))
    _, o = jax.lax.scan(jax.checkpoint(stretch),
                        jnp.zeros((b, h, n, n), jnp.float32), xs)
    return o.reshape(s, b, h, n).swapaxes(0, 1)


def loss(params, batch, cfg: dict, ein):
    eps = cfg["layer_norm_epsilon"]
    gn_eps = cfg["group_norm_epsilon"]
    nd = dims(cfg)
    h, n = nd["h"], nd["n"]
    tokens, targets = batch["tokens"], batch["targets"]
    b, s = tokens.shape
    x = params["embed"][tokens]

    def layer(x, p):
        tm, cm = p["time_mix"], p["channel_mix"]
        xn = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
        xs = shift(xn)
        xr, xk, xv, xg, xw = (xn + (xs - xn) * tm["mu"][i] for i in range(5))
        heads = lambda y: y.reshape(b, s, h, n)
        r = heads(ein("bsd,de->bse", xr, tm["w_r"]))
        k = heads(ein("bsd,de->bse", xk, tm["w_k"]))
        v = heads(ein("bsd,de->bse", xv, tm["w_v"]))
        g = jax.nn.silu(ein("bsd,de->bse", xg, tm["w_g"]))
        lora = ein("bsr,rd->bsd", jnp.tanh(ein("bsd,dr->bsr", xw, tm["decay_a"])),
                   tm["decay_b"])
        logw = heads(-jnp.exp(jnp.clip(tm["decay_w0"] + lora, -8.0, 6.0)))
        o = wkv(r, k, v, logw, tm["bonus_u"])               # (B, S, H, N)
        mu = jnp.mean(o, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(o - mu), axis=-1, keepdims=True)
        o = ((o - mu) * jax.lax.rsqrt(var + gn_eps)).reshape(b, s, -1)
        o = o * tm["gn_g"] + tm["gn_b"]
        x = x + ein("bsd,de->bse", o * g, tm["w_o"])

        xn = layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
        xs = shift(xn)
        xk = xn + (xs - xn) * cm["mu"][0]
        xr = xn + (xs - xn) * cm["mu"][1]
        kk = jnp.square(jax.nn.relu(ein("bsd,df->bsf", xk, cm["w_k"])))
        gate = jax.nn.sigmoid(ein("bsd,de->bse", xr, cm["w_r"]))
        return x + gate * ein("bsf,fd->bsd", kk, cm["w_v"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"])
    x = layer_norm(x, params["final_norm"]["g"], params["final_norm"]["b"], eps)
    return cross_entropy(x, targets, params["head"], ein)
