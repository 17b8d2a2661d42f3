"""Model assembly for all assigned families.

Families:
  dense / moe / audio / vlm : token (+stub patch) embeddings → pre-norm GQA
      attention blocks (MLP or MoE) scanned over layers → norm → LM head.
  ssm    : RWKV6 blocks (attention-free) scanned over layers.
  hybrid : Mamba2 blocks with one *shared-weight* attention block every
      ``attn_every``-th position (Zamba2 pattern) — the shared weights are a
      closure constant of the group scan, so weight sharing is structural.

Tensor-parallel partition specs are chosen per weight at definition time:
head-dim sharding when the head count divides ``tp_size``, otherwise the
contraction (d_model) dim is sharded (row-parallel; GSPMD inserts the
partial-sum all-reduce).  See DESIGN.md §4.

All step functions are pure; caches/recurrent states are explicit
pytrees stacked over layers so ``lax.scan`` threads them.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models.common import (
    ParamDef,
    apply_rope,
    he_normal,
    init_params,
    layer_norm,
    normal_init,
    ones_init,
    rms_norm,
    rope,
    zeros_init,
)
from repro.kernels.wkv import heads_per_row
from repro.models.mamba2 import (
    MambaState,
    apply_mamba_block,
    mamba_block_decode,
    mamba_block_defs,
    mamba_n_heads,
)
from repro.models.mlp import apply_mlp, mlp_defs
from repro.models.moe import apply_moe, apply_moe_manual_ep, moe_defs
from repro.models.rwkv6 import (
    RWKVState,
    apply_rwkv_block,
    resolve_wkv,
    rwkv_block_decode,
    rwkv_block_defs,
)
from repro.telemetry import profile

PyTree = Any

__all__ = [
    "model_defs",
    "init_model",
    "loss_fn",
    "forward",
    "prefill",
    "decode_step",
    "init_decode_state",
]


# ---------------------------------------------------------------------------
# Param stacking for lax.scan over layers
# ---------------------------------------------------------------------------

def stack_defs(defs: PyTree, n: int) -> PyTree:
    """Prepend a layer axis (n, ...) to every ParamDef (vmapped init)."""

    def _stack(d: ParamDef) -> ParamDef:
        def init(key, shape, dtype):
            keys = jax.random.split(key, n)
            return jax.vmap(lambda k: d.init(k, d.shape, dtype))(keys)

        return ParamDef((n,) + d.shape, init, (None,) + d.spec, d.dtype)

    return jax.tree.map(_stack, defs, is_leaf=lambda x: isinstance(x, ParamDef))


# ---------------------------------------------------------------------------
# Norm helper (rmsnorm | layernorm)
# ---------------------------------------------------------------------------

def _norm_defs(cfg: ArchConfig, d: int):
    if cfg.norm == "layernorm":
        return {
            "g": ParamDef((d,), ones_init(), (None,), cfg.dtype),
            "b": ParamDef((d,), zeros_init(), (None,), cfg.dtype),
        }
    return {"g": ParamDef((d,), ones_init(), (None,), cfg.dtype)}


def _apply_norm(cfg: ArchConfig, p, x):
    if cfg.norm == "layernorm":
        return layer_norm(x, p["g"], p["b"])
    return rms_norm(x, p["g"])


# ---------------------------------------------------------------------------
# Attention block (dense / moe / audio / vlm, and zamba's shared block)
# ---------------------------------------------------------------------------

def _head_spec(n: int, tp: int, tail: tuple = (None,)):
    """('model' on head dim) if divisible else contraction-dim fallback."""
    if n % tp == 0:
        return (None, "model") + tail
    return ("model", None) + tail


def attn_dims(cfg: ArchConfig, tp_size: int) -> tuple[int, int]:
    """(h, kv) actually materialized — padded when cfg.pad_heads (exact
    semantics via masking; see attention.head_padding)."""
    if not (cfg.pad_heads or cfg.pad_kv):
        return cfg.n_heads, cfg.n_kv
    h_pad, kv_pad, _ = attn_lib.head_padding(
        cfg.n_heads, cfg.n_kv, tp_size, pad_kv=cfg.pad_kv
    )
    return h_pad, kv_pad


def _pad_mask(cfg: ArchConfig, params) -> Optional[jax.Array]:
    """Active-head mask (h_pad,) or None when no padding is present."""
    h_pad = params["wq"].shape[1]
    kv_pad = params["wk"].shape[1]
    if h_pad == cfg.n_heads and kv_pad == cfg.n_kv:
        return None
    g_pad = h_pad // kv_pad
    return attn_lib.active_head_mask(cfg.n_heads, cfg.n_kv, h_pad, kv_pad, g_pad)


def attn_block_defs(cfg: ArchConfig, tp_size: int, *, with_ffn: bool = True):
    d, dh, dt = cfg.d_model, cfg.head_dim, cfg.dtype
    h, kv = attn_dims(cfg, tp_size)
    defs = {
        "ln1": _norm_defs(cfg, d),
        "wq": ParamDef((d, h, dh), he_normal((-3,)), _head_spec(h, tp_size), dt),
        "wk": ParamDef((d, kv, dh), he_normal((-3,)), _head_spec(kv, tp_size), dt),
        "wv": ParamDef((d, kv, dh), he_normal((-3,)), _head_spec(kv, tp_size), dt),
        "wo": ParamDef(
            (h, dh, d),
            he_normal((-3, -2)),
            ("model", None, None) if h % tp_size == 0 else (None, None, "model"),
            dt,
        ),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h, dh), zeros_init(), (None, None), dt)
        defs["bk"] = ParamDef((kv, dh), zeros_init(), (None, None), dt)
        defs["bv"] = ParamDef((kv, dh), zeros_init(), (None, None), dt)
    if with_ffn:
        defs["ln2"] = _norm_defs(cfg, d)
        if cfg.n_experts:
            defs["ffn"] = moe_defs(
                d, cfg.d_ff, cfg.n_experts, n_shared=cfg.n_shared_experts,
                shard_ff=cfg.moe_shard_ff, dtype=dt,
            )
        else:
            defs["ffn"] = mlp_defs(d, cfg.d_ff, dtype=dt)
    return defs


def _qkv(p, cfg: ArchConfig, hn: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", hn, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", hn, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", hn, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def apply_attn_block(
    p,
    cfg: ArchConfig,
    h: jax.Array,
    *,
    positions: jax.Array,
    window: Optional[int],
    collect_cache: bool,
    impl: str,
):
    """Train/prefill attention block. h: (B, S, D); positions: (B, S).

    ``impl``: the attention path, ``cfg.attn_impl`` as the caller resolved
    it for these positions (``_attention_impl``).
    Returns (h', cache_entry_or_None, aux_loss).
    """
    hn = _apply_norm(cfg, p["ln1"], h)
    with profile.scope("attention"):
        q, k, v = _qkv(p, cfg, hn)
        sin, cos = rope(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
        out = attn_lib.multihead_attention(
            q,
            k,
            v,
            q_positions=positions,
            k_positions=positions,
            causal=True,
            window=window,
            impl=impl,
            chunk_size=cfg.attn_chunk,
        )
        mask = _pad_mask(cfg, p)
        if mask is not None:
            out = out * mask[None, None, :, None].astype(out.dtype)
        h = h + jnp.einsum("bshk,hkd->bsd", out, p["wo"])

    aux = jnp.zeros((), jnp.float32)
    if "ffn" in p:
        hn2 = _apply_norm(cfg, p["ln2"], h)
        with profile.scope("mlp"):
            if cfg.n_experts:
                moe_fn = (
                    apply_moe_manual_ep if cfg.moe_impl == "manual_ep"
                    else partial(apply_moe, buf_constraint=cfg.moe_buf_constraint)
                )
                ff, aux = moe_fn(
                    p["ffn"],
                    hn2,
                    top_k=cfg.top_k,
                    capacity_factor=cfg.capacity_factor,
                )
            else:
                ff = apply_mlp(p["ffn"], hn2, act=cfg.act)
            h = h + ff

    cache_entry = (k, v, positions) if collect_cache else None
    return h, cache_entry, aux


def decode_attn_block(
    p,
    cfg: ArchConfig,
    h: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cache_pos: jax.Array,
    *,
    pos: jax.Array,
    window: Optional[int],
):
    """Single-token attention block against a cache.

    h: (B, 1, D); cache_k/v: (B, slots, KV, Dh); cache_pos: (B, slots).
    """
    hn = _apply_norm(cfg, p["ln1"], h)
    q, k, v = _qkv(p, cfg, hn)
    b = h.shape[0]
    posb = jnp.broadcast_to(pos[None, None], (b, 1))
    sin, cos = rope(posb, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    cache_k, cache_v, cache_pos = attn_lib.cache_update(
        cache_k, cache_v, cache_pos, k, v, pos, ring=window is not None
    )
    out = attn_lib.decode_attention(
        q, cache_k, cache_v, cache_pos, pos=pos, window=window
    )
    mask = _pad_mask(cfg, p)
    if mask is not None:
        out = out * mask[None, None, :, None].astype(out.dtype)
    h = h + jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if "ffn" in p:
        hn2 = _apply_norm(cfg, p["ln2"], h)
        if cfg.n_experts:
            moe_fn = (
                apply_moe_manual_ep if cfg.moe_impl == "manual_ep" else apply_moe
            )
            ff, _ = moe_fn(
                p["ffn"], hn2, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor
            )
        else:
            ff = apply_mlp(p["ffn"], hn2, act=cfg.act)
        h = h + ff
    return h, (cache_k, cache_v, cache_pos)


# ---------------------------------------------------------------------------
# Model definition
# ---------------------------------------------------------------------------

def model_defs(cfg: ArchConfig, tp_size: int = 16) -> PyTree:
    dt = cfg.dtype
    defs: dict[str, Any] = {
        "embed": ParamDef((cfg.vocab, cfg.d_model), normal_init(0.02), (None, "model"), dt),
        "final_norm": _norm_defs(cfg, cfg.d_model),
        "head": ParamDef(
            (cfg.d_model, cfg.vocab), normal_init(0.02), (None, "model"), dt
        ),
    }
    if cfg.family == "ssm":
        defs["blocks"] = stack_defs(
            rwkv_block_defs(cfg.d_model, cfg.n_heads or cfg.d_model // 64, cfg.d_ff, dt),
            cfg.n_layers,
        )
    elif cfg.family == "hybrid":
        group = cfg.attn_every
        n_groups, tail = divmod(cfg.n_layers, group)
        mdefs = mamba_block_defs(cfg.d_model, cfg.ssm_state, dtype=dt)
        defs["mamba_groups"] = stack_defs(stack_defs(mdefs, group - 1), n_groups)
        defs["shared_attn"] = attn_block_defs(cfg, tp_size, with_ffn=True)
        if tail:
            defs["tail_mamba"] = stack_defs(mdefs, tail)
    else:  # dense | moe | audio | vlm
        defs["blocks"] = stack_defs(attn_block_defs(cfg, tp_size), cfg.n_layers)
    return defs


def init_model(cfg: ArchConfig, key: jax.Array, tp_size: int = 16) -> PyTree:
    return init_params(model_defs(cfg, tp_size), key)


# ---------------------------------------------------------------------------
# Embedding / head / loss
# ---------------------------------------------------------------------------

@profile.scope("embed")
def _embed(params, cfg: ArchConfig, tokens: jax.Array, patch_embeds=None):
    h = jnp.take(params["embed"], tokens, axis=0)
    if cfg.input_kind == "vlm" and patch_embeds is not None:
        # decode steps carry no new patches; prefill/train prepend them
        h = jnp.concatenate([patch_embeds.astype(h.dtype), h], axis=1)
    return h


@profile.scope("head")
def _logits(params, cfg: ArchConfig, h: jax.Array) -> jax.Array:
    h = _apply_norm(cfg, params["final_norm"], h)
    return jnp.einsum("bsd,dv->bsv", h, params["head"])


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean CE over valid (target >= 0) positions; f32 math."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    tgt = jnp.take_along_axis(
        lf, jnp.maximum(targets, 0)[..., None], axis=-1
    )[..., 0]
    valid = (targets >= 0).astype(jnp.float32)
    return jnp.sum((lse - tgt) * valid) / jnp.maximum(valid.sum(), 1.0)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _maybe_remat(cfg, f):
    if not cfg.remat:
        return f
    if cfg.remat_policy == "dots":
        return jax.remat(f, policy=jax.checkpoint_policies.dots_saveable)
    return jax.remat(f)


def _attention_impl(cfg: ArchConfig, seq_len: int, window, n_calls: int) -> str:
    """``cfg.attn_impl`` resolved for a forward over ``arange(seq_len)``,
    counting its ``n_calls`` attention blocks under ``attention.path.<impl>``."""
    impl = attn_lib.resolve_impl(
        cfg.attn_impl, seq_len=seq_len, head_dim=cfg.head_dim,
        window=window, aligned=True,
    )
    profile.count("attention.path." + impl, n_calls)
    return impl


def forward(
    params,
    cfg: ArchConfig,
    tokens: jax.Array,
    *,
    patch_embeds=None,
    window: Optional[int] = None,
    collect_cache: bool = False,
):
    """Full-sequence forward.

    Returns (logits (B, S_total, V), cache_or_states_or_None, aux_loss).
    For ssm/hybrid, states are always returned (zero-initialized at entry).
    """
    h = _embed(params, cfg, tokens, patch_embeds)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    if cfg.family == "ssm":
        n_heads = cfg.n_heads or cfg.d_model // 64
        wkv_impl = resolve_wkv()
        profile.count("wkv." + wkv_impl, cfg.n_layers)
        if wkv_impl == "pallas" and heads_per_row(n_heads, cfg.d_model // n_heads) > 1:
            profile.count("wkv.pallas.packed", cfg.n_layers)

        def body(carry, layer_p):
            st0 = RWKVState.empty(b, n_heads, cfg.d_model // n_heads, cfg.d_model, h.dtype)
            out, st = _maybe_remat(cfg, partial(
                apply_rwkv_block, n_heads=n_heads, chunk=cfg.rec_chunk,
                wkv_impl=wkv_impl,
            ))(layer_p, carry, st0)
            return out, st

        h, states = jax.lax.scan(body, h, params["blocks"])
        return _logits(params, cfg, h), states, jnp.zeros((), jnp.float32)

    if cfg.family == "hybrid":
        return _hybrid_forward(params, cfg, h, positions, window, collect_cache)

    impl = _attention_impl(cfg, s, window, cfg.n_layers)

    def attn_apply(layer_p, hh):
        return apply_attn_block(
            layer_p, cfg, hh,
            positions=positions, window=window, collect_cache=collect_cache,
            impl=impl,
        )

    def body(carry, layer_p):
        hh, aux = carry
        hh, cache_e, a = _maybe_remat(cfg, attn_apply)(layer_p, hh)
        return (hh, aux + a), cache_e

    (h, aux), cache = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)), params["blocks"])
    return _logits(params, cfg, h), (cache if collect_cache else None), aux


def _hybrid_forward(params, cfg, h, positions, window, collect_cache):
    b = h.shape[0]
    group = cfg.attn_every
    mk_state = lambda: MambaState.empty(
        b, mamba_n_heads(cfg.d_model), cfg.ssm_state, cfg.d_model * 2, h.dtype
    )
    shared = params["shared_attn"]
    aux0 = jnp.zeros((), jnp.float32)
    n_groups = jax.tree.leaves(params["mamba_groups"])[0].shape[0]
    impl = _attention_impl(cfg, h.shape[1], window, n_groups)

    def group_body(carry, group_p):
        hh, aux = carry
        m_states = []
        for i in range(group - 1):
            lp = jax.tree.map(lambda x: x[i], group_p)
            hh, st = _maybe_remat(cfg, partial(
                apply_mamba_block, d_state=cfg.ssm_state, chunk=cfg.rec_chunk
            ))(lp, hh, mk_state())
            m_states.append(st)
        hh, cache_e, a = _maybe_remat(
            cfg,
            lambda sp, hhh: apply_attn_block(
                sp, cfg, hhh,
                positions=positions, window=window, collect_cache=collect_cache,
                impl=impl,
            ),
        )(shared, hh)
        m_states = jax.tree.map(lambda *xs: jnp.stack(xs), *m_states)
        return (hh, aux + a), (m_states, cache_e)

    (h, aux), (m_states, caches) = jax.lax.scan(
        group_body, (h, aux0), params["mamba_groups"]
    )

    tail_states = None
    if "tail_mamba" in params:
        n_tail = jax.tree.leaves(params["tail_mamba"])[0].shape[0]
        tails = []
        for i in range(n_tail):
            lp = jax.tree.map(lambda x: x[i], params["tail_mamba"])
            h, st = _maybe_remat(cfg, partial(
                apply_mamba_block, d_state=cfg.ssm_state, chunk=cfg.rec_chunk
            ))(lp, h, mk_state())
            tails.append(st)
        tail_states = jax.tree.map(lambda *xs: jnp.stack(xs), *tails)

    states = {"mamba": m_states, "attn_cache": caches, "tail": tail_states}
    return _logits(params, cfg, h), states, aux


# ---------------------------------------------------------------------------
# Training loss
# ---------------------------------------------------------------------------

def loss_fn(params, cfg: ArchConfig, batch: dict) -> jax.Array:
    """Next-token CE (+ MoE aux).  batch: tokens/targets (B, S) [+ patch_embeds]."""
    logits, _, aux = forward(
        params, cfg, batch["tokens"], patch_embeds=batch.get("patch_embeds")
    )
    if cfg.input_kind == "vlm":
        logits = logits[:, cfg.n_patches :]
    with profile.scope("head"):
        ce = cross_entropy(logits, batch["targets"])
    return ce + cfg.aux_loss_weight * aux


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    """Family-polymorphic decode state (exactly one field is not None)."""

    kv: Optional[tuple] = None      # (k, v, pos) each (L, B, slots, ...) stacked
    rwkv: Optional[RWKVState] = None      # leaves (L, B, ...)
    hybrid: Optional[dict] = None


def init_decode_state(
    cfg: ArchConfig, batch: int, seq_len: int, *, window: Optional[int] = None,
    tp_size: int = 1,
) -> DecodeState:
    """Zero/empty decode state sized for a ``seq_len`` context.

    ``tp_size`` matters only for ``cfg.pad_heads`` (the cache must match the
    padded kv head count)."""
    slots = min(window, seq_len) if window else seq_len
    _, kv = attn_dims(cfg, tp_size)
    kvd = (kv, cfg.head_dim)
    mk_kv = lambda n: (
        jnp.zeros((n, batch, slots) + kvd, cfg.dtype),
        jnp.zeros((n, batch, slots) + kvd, cfg.dtype),
        jnp.full((n, batch, slots), -1, jnp.int32),
    )
    if cfg.family == "ssm":
        n_heads = cfg.n_heads or cfg.d_model // 64
        st = RWKVState.empty(batch, n_heads, cfg.d_model // n_heads, cfg.d_model, cfg.dtype)
        return DecodeState(
            rwkv=jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), st
            )
        )
    if cfg.family == "hybrid":
        group = cfg.attn_every
        n_groups, tail = divmod(cfg.n_layers, group)
        mst = MambaState.empty(
            batch, mamba_n_heads(cfg.d_model), cfg.ssm_state, cfg.d_model * 2, cfg.dtype
        )
        bc = lambda lead: jax.tree.map(
            lambda x: jnp.broadcast_to(x[None] if len(lead) == 1 else x[None, None],
                                       lead + x.shape), mst
        )
        return DecodeState(
            hybrid={
                "mamba": bc((n_groups, group - 1)),
                "attn_cache": mk_kv(n_groups),
                "tail": bc((tail,)) if tail else None,
            }
        )
    return DecodeState(kv=mk_kv(cfg.n_layers))


def prefill(params, cfg: ArchConfig, tokens: jax.Array, *, patch_embeds=None):
    """Process a prompt; returns (last-token logits (B, V), DecodeState)."""
    logits, st, _ = forward(
        params, cfg, tokens, patch_embeds=patch_embeds, collect_cache=True
    )
    last = logits[:, -1]
    if cfg.family == "ssm":
        return last, DecodeState(rwkv=st)
    if cfg.family == "hybrid":
        kc = st["attn_cache"]
        # (k, v, positions) tuples from scan: k (G, B, S, KV, Dh), pos (G?, B, S)
        k, v, p = kc
        return last, DecodeState(
            hybrid={"mamba": st["mamba"], "attn_cache": (k, v, p), "tail": st["tail"]}
        )
    k, v, p = st
    return last, DecodeState(kv=(k, v, p))


def decode_step(
    params,
    cfg: ArchConfig,
    tokens: jax.Array,
    pos: jax.Array,
    state: DecodeState,
    *,
    window: Optional[int] = None,
):
    """One token for every sequence in the batch.

    tokens: (B, 1); pos: scalar int32 (current absolute position).
    Returns (logits (B, V), new DecodeState).
    """
    h = _embed(params, cfg, tokens)  # (B, 1, D)

    if cfg.family == "ssm":
        n_heads = cfg.n_heads or cfg.d_model // 64

        def body(carry, xs):
            layer_p, st = xs
            out, st2 = rwkv_block_decode(layer_p, carry, st, n_heads=n_heads)
            return out, st2

        h1, new_states = jax.lax.scan(body, h[:, 0], (params["blocks"], state.rwkv))
        logits = _logits(params, cfg, h1[:, None])[:, 0]
        return logits, DecodeState(rwkv=new_states)

    if cfg.family == "hybrid":
        return _hybrid_decode(params, cfg, h, pos, state, window)

    def body(carry, xs):
        layer_p, ck, cv, cp = xs
        out, (ck, cv, cp) = decode_attn_block(
            layer_p, cfg, carry, ck, cv, cp, pos=pos, window=window
        )
        return out, (ck, cv, cp)

    k, v, p = state.kv
    h, new_kv = jax.lax.scan(body, h, (params["blocks"], k, v, p))
    logits = _logits(params, cfg, h)[:, 0]
    return logits, DecodeState(kv=new_kv)


def _hybrid_decode(params, cfg, h, pos, state, window):
    group = cfg.attn_every
    shared = params["shared_attn"]
    hst = state.hybrid

    def group_body(carry, xs):
        group_p, m_st, ck, cv, cp = xs
        hh = carry
        new_m = []
        for i in range(group - 1):
            lp = jax.tree.map(lambda x: x[i], group_p)
            st = jax.tree.map(lambda x: x[i], m_st)
            hh1, st2 = mamba_block_decode(lp, hh[:, 0], st, d_state=cfg.ssm_state)
            hh = hh1[:, None]
            new_m.append(st2)
        hh, (ck, cv, cp) = decode_attn_block(
            shared, cfg, hh, ck, cv, cp, pos=pos, window=window
        )
        new_m = jax.tree.map(lambda *xs_: jnp.stack(xs_), *new_m)
        return hh, (new_m, ck, cv, cp)

    k, v, p = hst["attn_cache"]
    h, (new_m, nk, nv, np_) = jax.lax.scan(
        group_body, h, (params["mamba_groups"], hst["mamba"], k, v, p)
    )

    new_tail = None
    if hst.get("tail") is not None:
        n_tail = jax.tree.leaves(hst["tail"])[0].shape[0]
        tails = []
        for i in range(n_tail):
            lp = jax.tree.map(lambda x: x[i], params["tail_mamba"])
            st = jax.tree.map(lambda x: x[i], hst["tail"])
            h1, st2 = mamba_block_decode(lp, h[:, 0], st, d_state=cfg.ssm_state)
            h = h1[:, None]
            tails.append(st2)
        new_tail = jax.tree.map(lambda *xs_: jnp.stack(xs_), *tails)

    logits = _logits(params, cfg, h)[:, 0]
    return logits, DecodeState(
        hybrid={"mamba": new_m, "attn_cache": (nk, nv, np_), "tail": new_tail}
    )
