"""Pallas TPU kernels for RWKV-6's WKV recurrence, forward and backward.

    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ
    o_t = r_tᵀ (S_{t-1} + diag(u) k_t v_tᵀ)

The chunked form (``models/recurrence.rwkv_chunked``) in one kernel pair
bound by a ``custom_vjp``.  Grid: (batch, head group) ``parallel`` x chunks
``arbitrary``; each step holds one chunk of a group of p heads in VMEM and
carries the group's float32 state (or, backward, its gradient) across
chunks in VMEM scratch.  Nothing of size chunk² x N reaches HBM: besides
the inputs, the backward reads only each chunk's starting state, which the
forward writes.

Layout: p = 128 // N heads side by side on a block's lanes, where N divides
128 and p divides H (``heads_per_row``; else p = 1).  The kernels read and
write (chunk, p·N) blocks of the model's own (B, L, H·N) layout, a bitcast
of its (B, L, H, N): at N = 64 each block holds two heads.  Where p·N does
not fill whole 128-lane rows (p = 1, N < 128) the wrapper transposes to
(B·H, L, N) instead, a head to a block.  Each head's state is kept
transposed, (v-dim, k-dim), so the per-channel decay of its k-dim scales
lanes; the group's is one (p·N, p·N) block-diagonal tile, its off-diagonal
blocks held at zero, so the readout and update are single dots.  In HBM
the states are the diagonal blocks only, a group's side by side:
(B, H/p, N, p·N), per chunk (B, H/p, nc, N, p·N), the bytes of (B, H, N, N).

Within a chunk, la = Σ_{j≤t} log w_j and its exclusive form lp are
triangular-ones dots.  Intra-chunk pairs (t, s < t) weigh
exp(lp_t − la_s) ≤ 1, split in sub-chunks:

* across sub-chunks (s before the sub-chunk of t, which starts at t0): MXU
  dots of r ⊙ exp(lp − ref) and k ⊙ exp(ref − la), ref = la_{t0−1}; both
  exponents are ≤ 0.  Their contractions over N stay within a head: one
  operand is stacked once per head, zero outside that head's lanes;
* within a sub-chunk: the pairwise decays, one column s at a time, both
  heads of a block in each vector op; the sums over N are per head.

Every exponent is clamped at 0 (only rounding can push one above), so no
log decay down to the program's clip floor overflows.  All state,
accumulation and dot operands are float32 at ``HIGHEST`` precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv", "chunk_sizes", "heads_per_row"]

_F32 = jnp.float32
# chunk and sub-chunk, from a sweep on a v5e at the RWKV-6 cell's shapes
# (PERF.md): chunk 64, the XLA scan's, keeps the cumulative log decays, and
# so the rounding of their differences, as small as that path's
CHUNK = 64
SUB_CHUNK = 32
_ALIGN = 16  # a bfloat16 block's rows
_LANES = 128


def chunk_sizes(seq_len: int) -> tuple[int, int]:
    """(chunk, sub-chunk) for a sequence: ``CHUNK``, or the sequence rounded
    up to whole bfloat16 tiles where it is shorter; ``SUB_CHUNK`` where it
    divides the chunk, else the chunk."""
    c = min(CHUNK, -(-seq_len // _ALIGN) * _ALIGN)
    sc = SUB_CHUNK if c % SUB_CHUNK == 0 else c
    return c, sc


def heads_per_row(n_heads: int, head_size: int) -> int:
    """p, the heads a block lays side by side on its lanes: 128 // N where
    N divides 128 and p divides the head count, else 1."""
    p = _LANES // head_size if _LANES % head_size == 0 else 1
    return p if n_heads % p == 0 else 1


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=_F32,
    )


def _mm(a, b):    # a @ b
    return _dot(a, b, ((1,), (0,)))


def _mm_t(a, b):  # a @ bᵀ
    return _dot(a, b, ((1,), (1,)))


def _t_mm(a, b):  # aᵀ @ b
    return _dot(a, b, ((0,), (0,)))


def _decay(x):
    """exp of a log decay that is ≤ 0 but for rounding."""
    return lax.exp(lax.min(x, 0.0))


# The in-sub-chunk loops below unroll into a few thousand operations, so
# they call ``lax`` directly: each ``jax.numpy`` call is a nested trace,
# and tracing them cost seconds of a program's set-up.

def _wide(x, shape):
    """A (m, 1) column or (1, n) row broadcast to ``shape``."""
    return lax.broadcast_in_dim(x, shape, (0, 1))


def _row(x, s):
    """Row ``s`` of ``x``, broadcast to its shape."""
    return _wide(lax.slice_in_dim(x, s, s + 1, axis=0), x.shape)


def _row_sums(x):
    """(m, n) -> (m, n): each row's sum, across the row."""
    return _wide(lax.broadcast_in_dim(lax.reduce_sum(x, (1,)), (x.shape[0], 1), (0,)),
                 x.shape)


def _col_sums(x):
    """(m, n) -> (m, n): each column's sum, down the column."""
    return _wide(lax.broadcast_in_dim(lax.reduce_sum(x, (0,)), (1, x.shape[1]), (1,)),
                 x.shape)


def _heads(shape, n):
    """Per head of a block of ``shape`` whose lanes hold heads of ``n``, a
    mask of its lanes; None where the block is one head."""
    if shape[-1] == n:
        return None
    head = lax.div(lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1),
                   lax.full(shape, n, jnp.int32))
    return tuple(lax.eq(head, h) for h in range(shape[-1] // n))


def _head_sums(x, heads):
    """(m, p·N) -> (m, p·N): each row's sum over each head's lanes, across
    that head's lanes (``heads``: ``_heads`` of x's shape)."""
    if heads is None:
        return _row_sums(x)
    zeros = lax.full(x.shape, 0.0, _F32)
    out = None
    for mine in heads:
        part = _row_sums(lax.select(mine, x, zeros))
        out = part if out is None else lax.select(mine, part, out)
    return out


def _stack(x, heads):
    """(m, p·N) -> (p·m, p·N): x once per head, zero outside that head's
    lanes (``heads``: ``_heads`` of a (1, p·N) row).  A dot that contracts
    over the lanes of a stacked operand keeps each head's sum to itself;
    a state's diagonal blocks, stacked, are its block-diagonal tile."""
    if heads is None:
        return x
    return jnp.concatenate([jnp.where(mine, x, 0.0) for mine in heads], axis=0)


def _fold(x, heads):
    """(p·m, p·N) -> (m, p·N): head h's lanes from row block h; the inverse
    of ``_stack``."""
    if heads is None:
        return x
    m = x.shape[0] // len(heads)
    out = x[:m]
    for h, mine in enumerate(heads[1:], 1):
        out = jnp.where(mine, x[h * m:(h + 1) * m], out)
    return out


def _own_blocks(x, n):
    """A (p·N, p·N) tile with its off-diagonal (cross-head) blocks zeroed."""
    if x.shape[0] == n:
        return x
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) // n
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) // n
    return jnp.where(rows == cols, x, 0.0)


def _tri(c, strict, upper=False):
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    if upper:
        row, col = col, row
    keep = col < row if strict else col <= row
    return keep.astype(_F32)


def _log_decays(w):
    """(la, lp): the inclusive and exclusive cumulative log decay."""
    c = w.shape[0]
    return _mm(_tri(c, False), w), _mm(_tri(c, True), w)


def _diag_out(r, k, v, lp, la, heads):
    """The sub-chunk's own pairs (s < t), column by column: Σ_s A[t, s] v_s
    with A[t, s] each head's sum of r_t ⊙ k_s ⊙ exp(lp_t − la_s)."""
    sc, _ = r.shape
    rows = lax.broadcasted_iota(jnp.int32, r.shape, 0)
    zeros = lax.full(r.shape, 0.0, _F32)
    o = zeros
    for s in range(sc):
        e = lax.select(lax.gt(rows, s), _decay(lax.sub(lp, _row(la, s))), zeros)
        a = _head_sums(lax.mul(lax.mul(r, e), _row(k, s)), heads)
        o = lax.add(o, lax.mul(a, _row(v, s)))
    return o


def _fwd_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                o_ref, sn_ref, *rest, n, sc):
    """One chunk of one head group: o, the carried state's update, and
    (where ``rest`` holds that output) the chunk's starting state."""
    st_ref = rest[0] if len(rest) == 2 else None
    s_scr = rest[-1]
    j = pl.program_id(2)
    lanes = _heads((1, r_ref.shape[-1]), n)

    @pl.when(j == 0)
    def _():
        s_scr[...] = _stack(s0_ref[0, 0], lanes)

    r, k, v, w = (x[0].astype(_F32) for x in (r_ref, k_ref, v_ref, w_ref))
    u = u_ref[0]
    c, width = r.shape
    s = s_scr[...]                                   # (p·N_v, p·N_k)
    if st_ref is not None:
        st_ref[0, 0, 0] = _fold(s, lanes)
    la, lp = _log_decays(w)
    o = _mm_t(r * _decay(lp), s) + _head_sums(r * u * k, _heads((c, width), n)) * v
    heads = _heads((sc, width), n)
    for i in range(c // sc):
        t0 = i * sc
        blk = slice(t0, t0 + sc)
        oi = o[blk] + _diag_out(r[blk], k[blk], v[blk], lp[blk], la[blk], heads)
        if i:
            ref = la[t0 - 1:t0]
            q = r[blk] * _decay(lp[blk] - ref)
            kk = _stack(k[:t0] * _decay(ref - la[:t0]), lanes)
            oi = oi + _mm(_mm_t(q, kk), _stack(v[:t0], lanes))
        o_ref[0, blk] = oi.astype(o_ref.dtype)
    last = la[c - 1:]
    s_scr[...] = s * _decay(last) + _own_blocks(_t_mm(v, k * _decay(last - la)), n)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        sn_ref[0, 0] = _fold(s_scr[...], lanes)


def _bwd_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, st_ref, do_ref, dsn_ref,
                dr_ref, dk_ref, dv_ref, dw_ref, du_ref, ds0_ref,
                ds_scr, dra_scr, dka_scr, dv_scr, *, n, sc):
    """One chunk of one head group, chunks in reverse: the gradients of r,
    k, v and the log decay, the bonus's summed over chunks, and the carried
    state's.  ``dra``/``dka`` gather the in-chunk scores' parts of dr and
    dk, which the log decay's gradient reuses: d lp = r ⊙ dra + (readout
    term), d la = −k ⊙ dka − (state-update term), d log w their reverse
    cumulative sums plus the end-of-chunk decay's gradient."""
    j = pl.program_id(2)
    lanes = _heads((1, r_ref.shape[-1]), n)

    @pl.when(j == 0)
    def _():
        ds_scr[...] = _stack(dsn_ref[0, 0], lanes)
        du_ref[...] = jnp.zeros_like(du_ref)

    r, k, v, w, do = (x[0].astype(_F32)
                      for x in (r_ref, k_ref, v_ref, w_ref, do_ref))
    u = u_ref[0]
    c, width = r.shape
    s = _stack(st_ref[0, 0, 0], lanes)   # this chunk's starting state
    ds = ds_scr[...]                     # the gradient of its end state
    la, lp = _log_decays(w)
    last = la[c - 1:]
    e_last = _decay(last)
    e_lp = _decay(lp)
    e_kt = _decay(last - la)
    dr_in = r * e_lp                     # the readout's operand
    kt = k * e_kt                        # the state update's operand
    chunk_heads = _heads((c, width), n)
    db = _head_sums(do * v, chunk_heads)  # the bonus scalar's gradient
    d_dr_in = _mm(do, s)
    d_kt = _mm(v, ds)
    dv_scr[...] = _head_sums(r * u * k, chunk_heads) * do + _mm_t(kt, ds)
    dka_scr[...] = jnp.zeros_like(dka_scr)
    heads = _heads((sc, width), n)
    rows = lax.broadcasted_iota(jnp.int32, (sc, width), 0)
    zeros = lax.full((sc, width), 0.0, _F32)
    for i in range(c // sc):
        t0 = i * sc
        blk = slice(t0, t0 + sc)
        ri, ki, vi, doi = r[blk], k[blk], v[blk], do[blk]
        lpi, lai = lp[blk], la[blk]
        dra = dka = dvd = zeros
        for s_ in range(sc):
            k_s = _row(ki, s_)
            e = lax.select(lax.gt(rows, s_), _decay(lax.sub(lpi, _row(lai, s_))), zeros)
            re = lax.mul(ri, e)
            da_col = _head_sums(lax.mul(doi, _row(vi, s_)), heads)
            a_col = _head_sums(lax.mul(re, k_s), heads)
            dra = lax.add(dra, lax.mul(lax.mul(da_col, e), k_s))
            at_s = lax.eq(rows, s_)
            dka = lax.select(at_s, _col_sums(lax.mul(da_col, re)), dka)
            dvd = lax.select(at_s, _col_sums(lax.mul(a_col, doi)), dvd)
        dka_scr[blk] += dka
        dv_scr[blk] += dvd
        if i:
            ref = la[t0 - 1:t0]
            eq = _decay(lpi - ref)
            q = ri * eq
            ek = _decay(ref - la[:t0])
            kk = _stack(k[:t0] * ek, lanes)
            da = _mm_t(doi, _stack(v[:t0], lanes))   # (sc, p·t0): head h's block h
            dra = dra + eq * _mm(da, kk)
            dka_scr[:t0] += ek * _fold(_t_mm(da, q), lanes)
            dv_scr[:t0] += _fold(_t_mm(_mm_t(q, kk), doi), lanes)
        dra_scr[blk] = dra
    dra = dra_scr[...]
    dka = dka_scr[...]
    dr_ref[0] = (dra + db * u * k + d_dr_in * e_lp).astype(dr_ref.dtype)
    dk_ref[0] = (dka + db * u * r + d_kt * e_kt).astype(dk_ref.dtype)
    dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
    d_lp = r * dra + d_dr_in * dr_in
    d_la = -(k * dka) - d_kt * kt
    d_last = (jnp.sum(d_kt * kt, axis=0, keepdims=True)
              + jnp.sum(ds * s, axis=0, keepdims=True) * e_last)
    dw = (_mm(_tri(c, False, upper=True), d_la)
          + _mm(_tri(c, True, upper=True), d_lp) + d_last)
    dw_ref[0] = dw.astype(dw_ref.dtype)
    du_ref[0, 0] += jnp.sum(db * r * k, axis=0, keepdims=True)
    ds_scr[...] = _own_blocks(_t_mm(do, dr_in), n) + ds * e_last

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        ds0_ref[0, 0] = _fold(ds_scr[...], lanes)


def _specs(x_shape, u_shape, s_shape, c, reverse=False):
    """Block specs of a (batch, head group, chunk) grid over the kernel's
    operands: sequences x (B, L, H·N) with a group's p·N lanes at its
    index, or (B·H, L, N) a head to a row; the bonus u (H/p, 1, p·N); a
    state (B, H/p, N, p·N); per-chunk states (B, H/p, nc, N, p·N)."""
    groups, _, width = u_shape
    n = s_shape[2]
    nc = x_shape[1] // c
    chunk = (lambda j: nc - 1 - j) if reverse else (lambda j: j)
    if x_shape[-1] == width * groups:
        seq = pl.BlockSpec((1, c, width), lambda bi, g, j: (bi, chunk(j), g))
    else:
        seq = pl.BlockSpec((1, c, width),
                           lambda bi, g, j: (bi * groups + g, chunk(j), 0))
    head = pl.BlockSpec((1, 1, width), lambda bi, g, j: (g, 0, 0))
    state = pl.BlockSpec((1, 1, n, width), lambda bi, g, j: (bi, g, 0, 0))
    states = pl.BlockSpec((1, 1, 1, n, width),
                          lambda bi, g, j: (bi, g, chunk(j), 0, 0))
    return nc, seq, head, state, states


_PARAMS = dict(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(r, k, v, w, u, s0, sizes, *, save, interpret):
    c, sc = sizes
    b, groups, n, width = s0.shape
    nc, seq, head, state, states = _specs(r.shape, u.shape, s0.shape, c)
    out_shape = [jax.ShapeDtypeStruct(r.shape, v.dtype),
                 jax.ShapeDtypeStruct(s0.shape, _F32)]
    out_specs = [seq, state]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((b, groups, nc, n, width), _F32))
        out_specs.append(states)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n=n, sc=sc),
        grid=(b, groups, nc),
        in_specs=[seq, seq, seq, seq, head, state],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((width, width), _F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name="wkv_fwd_states" if save else "wkv_fwd",
    )(r, k, v, w, u, s0)


def _backward(r, k, v, w, u, states, do, dsn, sizes, *, interpret):
    c, sc = sizes
    b, groups, n, width = dsn.shape
    nc, seq, head, state, st = _specs(r.shape, u.shape, dsn.shape, c, reverse=True)
    du = pl.BlockSpec((1, 1, 1, width), lambda bi, g, j: (bi, g, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, sc=sc),
        grid=(b, groups, nc),
        in_specs=[seq, seq, seq, seq, head, st, seq, state],
        out_specs=[seq, seq, seq, seq, du, state],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (r, k, v, w)]
        + [jax.ShapeDtypeStruct((b, groups, 1, width), _F32),
           jax.ShapeDtypeStruct(dsn.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((width, width), _F32)]
        + [pltpu.VMEM((c, width), _F32)] * 3,
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name="wkv_bwd",
    )(r, k, v, w, u, states, do, dsn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _wkv(r, k, v, w, u, s0, sizes, interpret):
    return tuple(_forward(r, k, v, w, u, s0, sizes, save=False, interpret=interpret))


def _wkv_fwd(r, k, v, w, u, s0, sizes, interpret):
    o, sn, states = _forward(r, k, v, w, u, s0, sizes, save=True,
                             interpret=interpret)
    return (o, sn), (r, k, v, w, u, states)


def _wkv_bwd(sizes, interpret, res, cot):
    r, k, v, w, u, states = res
    do, dsn = cot
    dr, dk, dv, dw, du, ds0 = _backward(
        r, k, v, w, u, states, do.astype(v.dtype), dsn, sizes,
        interpret=interpret)
    return dr, dk, dv, dw, du.sum(0), ds0


_wkv.defvjp(_wkv_fwd, _wkv_bwd)


def wkv(r, k, v, logw, u, s0, *, interpret: bool | None = None):
    """The WKV recurrence of ``models/recurrence.rwkv_chunked``, same
    arguments and results: r/k/v/logw (B, L, H, N), u (H, N), s0 (B, H, N, N)
    (k-dim x v-dim) -> (o (B, L, H, N) in v's dtype, final state float32).
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, l, h, n = r.shape
    p = heads_per_row(h, n)
    g = h // p
    c, sc = chunk_sizes(l)
    l_pad = l + (-l) % c
    packed = p * n % _LANES == 0

    def seq(x):
        x = jnp.pad(x, ((0, 0), (0, l_pad - l), (0, 0), (0, 0)))
        if packed:
            return x.reshape(b, l_pad, h * n)
        return x.swapaxes(1, 2).reshape(b * h, l_pad, n)

    # a state (B, H, N_k, N_v) <-> each group's (v-dim, k-dim) blocks side
    # by side, (B, H/p, N_v, p·N_k)
    def state_in(x):
        x = x.astype(_F32).swapaxes(-1, -2).reshape(b, g, p, n, n)
        return x.swapaxes(2, 3).reshape(b, g, n, p * n)

    def state_out(x):
        return x.reshape(b, g, n, p, n).transpose(0, 1, 3, 4, 2).reshape(b, h, n, n)

    o, sn = _wkv(seq(r), seq(k), seq(v), seq(logw),
                 u.astype(_F32).reshape(g, 1, p * n), state_in(s0),
                 (c, sc), interpret)
    if packed:
        o = o[:, :l].reshape(b, l, h, n)
    else:
        o = o.reshape(b, h, l_pad, n)[:, :, :l].swapaxes(1, 2)
    return o, state_out(sn)
