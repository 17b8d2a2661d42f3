"""GQA attention: reference, chunked (flash-style XLA), splash, and decode.

Implementations (selected by ``impl``):
  * "reference" — full (B, H, Q, S) score materialization in float32.  The
    oracle, and every path the splash kernel does not take.
  * "chunked"   — online-softmax over KV chunks via ``lax.scan`` (the flash
    algorithm expressed in XLA): O(chunk) score memory, CPU-compilable.
    Used for the 32k shapes in the dry-run and for serving prefill.
  * "chunked_skip" — "chunked" over causal query blocks, each attending
    only to its KV prefix.
  * "splash"    — jax's Pallas TPU flash kernel
    (``jax.experimental.pallas.ops.tpu.splash_attention``), forward and
    fused backward, GQA native, skipping the KV blocks the causal mask
    hides.  Causal self-attention over aligned ``arange(S)`` positions only.

``ArchConfig.attn_impl`` defaults to "auto": ``resolve_impl`` takes
"splash" where the kernel computes exactly what "reference" does and the
chip can run it (a TPU, no window or cache validity mask, aligned
positions, S a multiple of 128, head_dim a multiple of 128, the default
matmul precision, and no mesh axis of size > 1 left to the partitioner),
else "reference".  The repo's own forward-only kernel in
``repro.kernels.flash_attention`` is used by no path.

Supports causal masking, sliding windows (the long-context carve-in for
full-attention archs on ``long_500k``), GQA head grouping, and single-token
decode against a (optionally ring-buffered) KV cache.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "multihead_attention",
    "resolve_impl",
    "splash_attention",
    "decode_attention",
    "KVCache",
    "head_padding",
]

_NEG_INF = -1e30


def head_padding(
    n_heads: int, n_kv: int, tp: int, *, pad_kv: bool = False
) -> tuple[int, int, int]:
    """Grouped head padding so heads shard on a ``tp``-way model axis.

    Returns (h_pad, kv_pad, group_pad) with h_pad = kv_pad * group_pad.
    Semantics stay exact: query head ``h`` maps to kv head ``h // group_pad``;
    a head is *active* iff its kv index is an original kv head AND its
    within-group index is below the original group size — padded heads are
    masked out of the output, so forward values and gradients of the original
    parameters are untouched.

      * default: grow the per-group size until kv * g_pad % tp == 0
        (q heads shard; kv stays as-is).
      * pad_kv: additionally pad kv itself to a multiple of tp (so KV caches
        shard on the kv-head dim — the decode-path fix).
    """
    group = n_heads // max(n_kv, 1)
    kv_pad = n_kv
    if pad_kv and n_kv % tp:
        kv_pad = -(-n_kv // tp) * tp
    g_pad = group
    while (kv_pad * g_pad) % tp:
        g_pad += 1
    return kv_pad * g_pad, kv_pad, g_pad


def active_head_mask(n_heads: int, n_kv: int, h_pad: int, kv_pad: int, g_pad: int):
    """(h_pad,) bool — True for original heads under the padded grouping."""
    group = n_heads // max(n_kv, 1)
    idx = jnp.arange(h_pad)
    return ((idx // g_pad) < n_kv) & ((idx % g_pad) < group)


def _split_gqa(q: jax.Array, n_kv: int) -> jax.Array:
    """(B, Q, H, D) -> (B, Q, KV, G, D)."""
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def _mask(
    q_pos: jax.Array,
    k_pos: jax.Array,
    causal: bool,
    window: Optional[int],
    k_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """Boolean (..., Q, S) mask of allowed attention pairs."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), bool)
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if causal:
        m &= kp <= qp
    if window is not None:
        m &= kp > qp - window
    if k_valid is not None:
        m &= k_valid[..., None, :]
    return m


# ---------------------------------------------------------------------------
# Splash: jax's Pallas TPU flash kernel, and the choice to take it
# ---------------------------------------------------------------------------

# the kernel's q and kv blocks, fastest first: on a v5e at S 4096, 1024
# steps the granite-8b cell in 0.2502 s, 512 in 0.2521 s, 256 in 0.2795 s
_SPLASH_BLOCKS = (1024, 512, 256, 128)


def splash_block(seq_len: int) -> Optional[int]:
    """The splash kernel's q and kv block for a sequence: the largest of
    ``_SPLASH_BLOCKS`` that tiles it, else None (the kernel cannot run)."""
    return next((b for b in _SPLASH_BLOCKS if seq_len % b == 0), None)


def _mesh_platform(mesh) -> str:
    """``"tpu"``, ``"cpu"``, ... for the devices of the mesh being traced for,
    else the default backend."""
    device = None if mesh.empty else mesh.abstract_device
    if device is None:
        return jax.default_backend()
    kind = device.device_kind.lower()
    return "tpu" if kind.startswith("tpu") else kind


def _auto_partitioned(mesh) -> bool:
    """Whether a mesh axis of size > 1 is left to the partitioner, which
    cannot split a Pallas call (with no mesh: whether there is more than one
    device)."""
    if mesh.empty:
        return jax.device_count() > 1
    manual = set(mesh.manual_axes)
    return any(n > 1 for a, n in mesh.shape.items() if a not in manual)


def resolve_impl(
    impl: str,
    *,
    seq_len: int,
    head_dim: int,
    aligned: bool,
    causal: bool = True,
    window: Optional[int] = None,
    k_valid: Optional[jax.Array] = None,
    mesh=None,
    platform: Optional[str] = None,
) -> str:
    """The attention path for ``impl``: an explicit name is kept; ``"auto"``
    is ``"splash"`` where the kernel computes what the reference does and
    can run, else ``"reference"``.

    ``aligned``: q and k positions are both ``arange(S)``, a fact the call
    site knows statically (the kernel's causal mask ignores positions).
    ``mesh``: the abstract mesh traced under (default: the ambient one),
    which says which axes are manual; ``platform`` defaults to its devices'.
    A raised ``jax_default_matmul_precision`` keeps the reference: the
    kernel's Mosaic matmuls take bfloat16 operands at the default only.
    """
    if impl != "auto":
        return impl
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    eligible = (
        causal
        and window is None
        and k_valid is None
        and aligned
        and splash_block(seq_len) is not None
        and head_dim % 128 == 0  # the kernel's lanes
        and jax.config.jax_default_matmul_precision in (None, "default")
        and (platform or _mesh_platform(mesh)) == "tpu"
        and not _auto_partitioned(mesh)
    )
    return "splash" if eligible else "reference"


@functools.cache
def _splash_spec(seq_len: int, n_heads: int, block: int):
    """(mask, block sizes) of causal attention over ``n_heads`` heads."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    mask = sa.MultiHeadMask([sa.CausalMask((seq_len, seq_len))] * n_heads)
    sizes = sa.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True,
    )
    return mask, sizes


def splash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal GQA attention through the splash kernel, forward and backward.

    q: (B, S, H, D); k/v: (B, S, KV, D), positions ``arange(S)``.  q is
    scaled by ``D ** -0.5`` in its dtype, as in the reference; QK^T
    accumulates in float32 from the same values, softmax statistics and PV
    are float32.  ``block`` (default ``splash_block(S)``) must tile S and
    be a multiple of 128.
    The kernel is built in each trace, as its block tables become arrays of
    that trace; jax caches the mask processing behind them, so only the
    first build per shape costs mask work.
    """
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    _, s, h, d = q.shape
    mask, sizes = _splash_spec(s, h, block or splash_block(s))
    kernel = sa.make_splash_mha(
        mask, block_sizes=sizes, head_shards=1, q_seq_shards=1,
        interpret=interpret,
    )
    heads_major = lambda x: x.transpose(0, 2, 1, 3)  # (B, heads, S, D)
    out = jax.vmap(kernel)(
        heads_major(q * d ** -0.5), heads_major(k), heads_major(v)
    )
    return heads_major(out)


def multihead_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_positions: jax.Array,
    k_positions: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    k_valid: Optional[jax.Array] = None,
    impl: str = "reference",
    chunk_size: int = 1024,
) -> jax.Array:
    """GQA attention.

    Args:
      q: (B, Q, H, D); k/v: (B, S, KV, D) with H % KV == 0.
      q_positions/k_positions: (B, Q) / (B, S) absolute positions (drive the
        causal/window masks; RoPE is applied by the caller).
      k_valid: optional (B, S) validity mask (cache slots in use).
    Returns:
      (B, Q, H, D).
    """
    b, sq, h, d = q.shape
    n_kv = k.shape[2]
    scale = d ** -0.5
    qg = _split_gqa(q, n_kv) * scale  # (B, Q, KV, G, D)

    if impl == "reference":
        scores = jnp.einsum(
            "bqhgd,bshd->bhgqs", qg.astype(jnp.float32), k.astype(jnp.float32)
        )
        m = _mask(q_positions, k_positions, causal, window, k_valid)
        scores = jnp.where(m[:, None, None], scores, _NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgqs,bshd->bqhgd", p.astype(v.dtype), v)
        return out.reshape(b, sq, h, d)

    if impl == "splash":
        if not causal or window is not None or k_valid is not None:
            raise ValueError("splash attention is causal, with no window or k_valid")
        return splash_attention(q, k, v)

    if impl == "chunked":
        return _chunked_attention(
            qg, k, v, q_positions, k_positions, causal, window, k_valid, chunk_size
        ).reshape(b, sq, h, d)

    if impl == "chunked_skip":
        # Causal block skipping: q processed in blocks, each attending only
        # to its kv prefix (and, with a window, only the kv suffix in range).
        # Cuts the full-S² chunked compute to ~S²/2 (less with windows).
        # Assumes aligned, monotone positions (training/prefill layout).
        s = k.shape[1]
        qb = max(chunk_size, 1)
        nq = -(-sq // qb)
        outs = []
        for i in range(nq):
            q_sl = qg[:, i * qb : (i + 1) * qb]
            qp = q_positions[:, i * qb : (i + 1) * qb]
            hi = min((i + 1) * qb, s) if causal else s
            lo = max(0, i * qb - (window or 0)) if window is not None else 0
            outs.append(
                _chunked_attention(
                    q_sl,
                    k[:, lo:hi],
                    v[:, lo:hi],
                    qp,
                    k_positions[:, lo:hi],
                    causal,
                    window,
                    None if k_valid is None else k_valid[:, lo:hi],
                    chunk_size,
                )
            )
        return jnp.concatenate(outs, axis=1).reshape(b, sq, h, d)

    raise ValueError(f"unknown attention impl {impl!r}")


def _chunked_attention(
    qg, k, v, q_pos, k_pos, causal, window, k_valid, chunk: int
) -> jax.Array:
    """Online-softmax (flash) over KV chunks; O(Q * chunk) score memory."""
    b, sq, n_kv, g, d = qg.shape
    s = k.shape[1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
        pad_valid = jnp.pad(
            jnp.ones((b, s), bool) if k_valid is None else k_valid,
            ((0, 0), (0, pad)),
        )
        k_valid = pad_valid
    n_chunks = k.shape[1] // chunk

    kc = k.reshape(b, n_chunks, chunk, n_kv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, n_kv, d).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    valc = (
        k_valid.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
        if k_valid is not None
        else jnp.ones((n_chunks, b, chunk), bool)
    )

    qf = qg.astype(jnp.float32)
    m0 = jnp.full((b, n_kv, g, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, n_kv, g, d), jnp.float32)

    def body(carry, inp):
        m_prev, l_prev, acc = carry
        kb, vb, pb, valb = inp
        scores = jnp.einsum("bqhgd,bshd->bhgqs", qf, kb.astype(jnp.float32))
        msk = _mask(q_pos, pb, causal, window, valb)  # (B, Q, C)
        scores = jnp.where(msk[:, None, None], scores, _NEG_INF)
        m_new = jnp.maximum(m_prev, scores.max(axis=-1))
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(scores - m_safe[..., None])
        p = jnp.where(msk[:, None, None], p, 0.0)
        corr = jnp.exp(
            jnp.where(m_prev <= _NEG_INF / 2, _NEG_INF, m_prev) - m_safe
        )
        corr = jnp.where(m_prev <= _NEG_INF / 2, 0.0, corr)
        l_new = l_prev * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhgqs,bshd->bqhgd", p, vb.astype(jnp.float32))
        acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kc, vc, pc, valc))
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / l.transpose(0, 3, 1, 2)[..., None]
    return out.astype(v.dtype)


# ---------------------------------------------------------------------------
# KV cache & decode
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    """Per-layer KV cache.

    k/v: (L, B, S_slots, KV, D).  For sliding-window archs ``S_slots`` is the
    window and slots are a ring buffer indexed by ``pos % window``;
    otherwise ``S_slots == max_seq`` and slot == absolute position.
    ``positions``: (L, B, S_slots) absolute position stored in each slot
    (-1 = empty).  RoPE is applied to K *before* caching.
    """

    k: jax.Array
    v: jax.Array
    positions: jax.Array

    @property
    def n_slots(self) -> int:
        return self.k.shape[2]

    @classmethod
    def empty(cls, n_layers, batch, n_slots, n_kv, d_head, dtype=jnp.bfloat16):
        return cls(
            k=jnp.zeros((n_layers, batch, n_slots, n_kv, d_head), dtype),
            v=jnp.zeros((n_layers, batch, n_slots, n_kv, d_head), dtype),
            positions=jnp.full((n_layers, batch, n_slots), -1, jnp.int32),
        )


def cache_update(
    cache_k: jax.Array,
    cache_v: jax.Array,
    cache_pos: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    pos: jax.Array,
    *,
    ring: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Insert one step (B, 1, KV, D) at absolute position ``pos`` (scalar)."""
    n_slots = cache_k.shape[1]
    slot = jnp.where(ring, pos % n_slots, jnp.minimum(pos, n_slots - 1))
    ck = jax.lax.dynamic_update_slice(cache_k, k_new, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v_new, (0, slot, 0, 0))
    b = cache_pos.shape[0]
    cp = jax.lax.dynamic_update_slice(
        cache_pos, jnp.full((b, 1), pos, jnp.int32), (0, slot)
    )
    return ck, cv, cp


def decode_attention(
    q: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    cache_pos: jax.Array,
    *,
    pos: jax.Array,
    window: Optional[int] = None,
) -> jax.Array:
    """Single-token attention against the cache.

    q: (B, 1, H, D); cache_k/v: (B, S_slots, KV, D); cache_pos: (B, S_slots).
    ``pos``: scalar absolute position of the query token.
    """
    b = q.shape[0]
    q_positions = jnp.full((b, 1), pos, jnp.int32)
    valid = cache_pos >= 0
    if window is not None:
        valid &= cache_pos > pos - window
    return multihead_attention(
        q,
        cache_k,
        cache_v,
        q_positions=q_positions,
        k_positions=jnp.maximum(cache_pos, 0),
        causal=True,
        window=window,
        k_valid=valid,
        impl="reference",
    )
