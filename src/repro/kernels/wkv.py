"""Pallas TPU kernels for RWKV-6's WKV recurrence, forward and backward.

    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ
    o_t = r_tᵀ (S_{t-1} + diag(u) k_t v_tᵀ)

The chunked form (``models/recurrence.rwkv_chunked``) in one kernel pair
bound by a ``custom_vjp``.  Grid: (batch, head) ``parallel`` x chunks
``arbitrary``; each step holds one chunk of one head in VMEM and carries the
head's float32 state (or, backward, its gradient) across chunks in VMEM
scratch.  Nothing of size chunk² x N reaches HBM: besides the inputs, the
backward reads only each chunk's starting state, which the forward writes.

Layout: (B, H, L, N) blocks of (chunk, N), N = 64 on the lanes (half a lane
row); the wrapper transposes from the model's (B, L, H, N).  The state is
kept transposed, (v-dim, k-dim), so the per-channel decay of its k-dim
scales lanes.

Within a chunk, la = Σ_{j≤t} log w_j and its exclusive form lp are
triangular-ones dots.  Intra-chunk pairs (t, s < t) weigh
exp(lp_t − la_s) ≤ 1, split in sub-chunks:

* across sub-chunks (s before the sub-chunk of t, which starts at t0): MXU
  dots of r ⊙ exp(lp − ref) and k ⊙ exp(ref − la), ref = la_{t0−1}; both
  exponents are ≤ 0;
* within a sub-chunk: the pairwise decays, one column s at a time.

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

__all__ = ["wkv", "chunk_sizes"]

_F32 = jnp.float32
# chunk and sub-chunk, from a sweep on a v5e at the RWKV-6 cell's shapes
# (PERF.md): chunk 64, the XLA scan's, keeps the cumulative log decays, and
# so the rounding of their differences, as small as that path's
CHUNK = 64
SUB_CHUNK = 32
_ALIGN = 16  # a bfloat16 block's rows


def chunk_sizes(seq_len: int) -> tuple[int, int]:
    """(chunk, sub-chunk) for a sequence: ``CHUNK``, or the sequence rounded
    up to whole bfloat16 tiles where it is shorter; ``SUB_CHUNK`` where it
    divides the chunk, else the chunk."""
    c = min(CHUNK, -(-seq_len // _ALIGN) * _ALIGN)
    sc = SUB_CHUNK if c % SUB_CHUNK == 0 else c
    return c, sc



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


def _diag_scores(r, k, lp, la):
    """The sub-chunk's own scores A[t, s] (s < t), column by column."""
    sc, n = r.shape
    rows = lax.broadcasted_iota(jnp.int32, (sc, n), 0)
    cols = lax.broadcasted_iota(jnp.int32, (sc, sc), 1)
    zeros = lax.full((sc, n), 0.0, _F32)
    a = lax.full((sc, sc), 0.0, _F32)
    for s in range(sc):
        e = lax.select(lax.gt(rows, s), _decay(lax.sub(lp, _row(la, s))), zeros)
        col = lax.slice_in_dim(_row_sums(lax.mul(lax.mul(r, e), _row(k, s))), 0, 1, axis=1)
        a = lax.select(lax.eq(cols, s), _wide(col, (sc, sc)), a)
    return a


def _fwd_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                o_ref, sn_ref, *rest, sc):
    """One chunk of one head: o, the carried state's update, and (where
    ``rest`` holds that output) the chunk's starting state."""
    st_ref = rest[0] if len(rest) == 2 else None
    s_scr = rest[-1]
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        s_scr[...] = s0_ref[0, 0]

    r, k, v, w = (x[0, 0].astype(_F32) for x in (r_ref, k_ref, v_ref, w_ref))
    u = u_ref[0]
    c = r.shape[0]
    s = s_scr[...]                                   # (N_v, N_k)
    if st_ref is not None:
        st_ref[0, 0, 0] = s
    la, lp = _log_decays(w)
    o = _mm_t(r * _decay(lp), s) + jnp.sum(r * u * k, axis=1, keepdims=True) * v
    for i in range(c // sc):
        t0 = i * sc
        blk = slice(t0, t0 + sc)
        oi = o[blk] + _mm(_diag_scores(r[blk], k[blk], lp[blk], la[blk]), v[blk])
        if i:
            ref = la[t0 - 1:t0]
            q = r[blk] * _decay(lp[blk] - ref)
            kk = k[:t0] * _decay(ref - la[:t0])
            oi = oi + _mm(_mm_t(q, kk), v[:t0])
        o_ref[0, 0, blk] = oi.astype(o_ref.dtype)
    last = la[c - 1:]
    s_scr[...] = s * _decay(last) + _t_mm(v, k * _decay(last - la))

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        sn_ref[0, 0] = s_scr[...]


def _bwd_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, st_ref, do_ref, dsn_ref,
                dr_ref, dk_ref, dv_ref, dw_ref, du_ref, ds0_ref,
                ds_scr, dra_scr, dka_scr, dv_scr, *, sc):
    """One chunk of one head, chunks in reverse: the gradients of r, k, v
    and the log decay, the bonus's summed over chunks, and the carried
    state's.  ``dra``/``dka`` gather the in-chunk scores' parts of dr and
    dk, which the log decay's gradient reuses: d lp = r ⊙ dra + (readout
    term), d la = −k ⊙ dka − (state-update term), d log w their reverse
    cumulative sums plus the end-of-chunk decay's gradient."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        ds_scr[...] = dsn_ref[0, 0]
        du_ref[...] = jnp.zeros_like(du_ref)

    r, k, v, w, do = (x[0, 0].astype(_F32)
                      for x in (r_ref, k_ref, v_ref, w_ref, do_ref))
    u = u_ref[0]
    c, n = r.shape
    s = st_ref[0, 0, 0]                  # this chunk's starting state
    ds = ds_scr[...]                     # the gradient of its end state
    la, lp = _log_decays(w)
    last = la[c - 1:]
    e_last = _decay(last)
    e_lp = _decay(lp)
    e_kt = _decay(last - la)
    dr_in = r * e_lp                     # the readout's operand
    kt = k * e_kt                        # the state update's operand
    db = jnp.sum(do * v, axis=1, keepdims=True)   # the bonus scalar's gradient
    d_dr_in = _mm(do, s)
    d_kt = _mm(v, ds)
    dv_scr[...] = jnp.sum(r * u * k, axis=1, keepdims=True) * do + _mm_t(kt, ds)
    dka_scr[...] = jnp.zeros_like(dka_scr)
    rows = lax.broadcasted_iota(jnp.int32, (sc, n), 0)
    zeros = lax.full((sc, n), 0.0, _F32)
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
            da_col = _row_sums(lax.mul(doi, _row(vi, s_)))
            a_col = _row_sums(lax.mul(re, k_s))
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
            kk = k[:t0] * ek
            da = _mm_t(doi, v[:t0])      # (sc, t0)
            dra = dra + eq * _mm(da, kk)
            dka_scr[:t0] += ek * _t_mm(da, q)
            dv_scr[:t0] += _t_mm(_mm_t(q, kk), doi)
        dra_scr[blk] = dra
    dra = dra_scr[...]
    dka = dka_scr[...]
    dr_ref[0, 0] = (dra + db * u * k + d_dr_in * e_lp).astype(dr_ref.dtype)
    dk_ref[0, 0] = (dka + db * u * r + d_kt * e_kt).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)
    d_lp = r * dra + d_dr_in * dr_in
    d_la = -(k * dka) - d_kt * kt
    d_last = (jnp.sum(d_kt * kt, axis=0, keepdims=True)
              + jnp.sum(ds * s, axis=0, keepdims=True) * e_last)
    dw = (_mm(_tri(c, False, upper=True), d_la)
          + _mm(_tri(c, True, upper=True), d_lp) + d_last)
    dw_ref[0, 0] = dw.astype(dw_ref.dtype)
    du_ref[0, 0] += jnp.sum(db * r * k, axis=0, keepdims=True)
    ds_scr[...] = _t_mm(do, dr_in) + ds * e_last

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        ds0_ref[0, 0] = ds_scr[...]


def _specs(nc, c, n, reverse=False):
    """Block specs of a (batch, head, chunk) grid: sequences, per-head rows,
    states and per-chunk states."""
    chunk = (lambda j: nc - 1 - j) if reverse else (lambda j: j)
    seq = pl.BlockSpec((1, 1, c, n), lambda bi, hi, j: (bi, hi, chunk(j), 0))
    head = pl.BlockSpec((1, 1, n), lambda bi, hi, j: (hi, 0, 0))
    state = pl.BlockSpec((1, 1, n, n), lambda bi, hi, j: (bi, hi, 0, 0))
    states = pl.BlockSpec((1, 1, 1, n, n), lambda bi, hi, j: (bi, hi, chunk(j), 0, 0))
    return seq, head, state, states


_PARAMS = dict(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(r, k, v, w, u, s0, sizes, *, save, interpret):
    c, sc = sizes
    b, h, l, n = r.shape
    nc = l // c
    seq, head, state, states = _specs(nc, c, n)
    out_shape = [jax.ShapeDtypeStruct(r.shape, v.dtype),
                 jax.ShapeDtypeStruct((b, h, n, n), _F32)]
    out_specs = [seq, state]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((b, h, nc, n, n), _F32))
        out_specs.append(states)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sc=sc),
        grid=(b, h, nc),
        in_specs=[seq, seq, seq, seq, head, state],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, n), _F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name="wkv_fwd_states" if save else "wkv_fwd",
    )(r, k, v, w, u, s0)


def _backward(r, k, v, w, u, states, do, dsn, sizes, *, interpret):
    c, sc = sizes
    b, h, l, n = r.shape
    nc = l // c
    seq, head, state, st = _specs(nc, c, n, reverse=True)
    du = pl.BlockSpec((1, 1, 1, n), lambda bi, hi, j: (bi, hi, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sc=sc),
        grid=(b, h, nc),
        in_specs=[seq, seq, seq, seq, head, st, seq, state],
        out_specs=[seq, seq, seq, seq, du, state],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (r, k, v, w)]
        + [jax.ShapeDtypeStruct((b, h, 1, n), _F32),
           jax.ShapeDtypeStruct((b, h, n, n), _F32)],
        scratch_shapes=[pltpu.VMEM((n, n), _F32)]
        + [pltpu.VMEM((c, n), _F32)] * 3,
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
    c, sc = chunk_sizes(l)
    pad = (-l) % c
    heads = lambda x: jnp.pad(x.swapaxes(1, 2), ((0, 0), (0, 0), (0, pad), (0, 0)))
    o, sn = _wkv(heads(r), heads(k), heads(v), heads(logw),
                 u.astype(_F32)[:, None], s0.astype(_F32).swapaxes(-1, -2),
                 (c, sc), interpret)
    return o[:, :, :l].swapaxes(1, 2), sn.swapaxes(-1, -2)
