"""Fused gossip-apply kernels: momentum-SGD step + weighted neighbor average.

The decentralized inner loop ends with three elementwise passes over the
full parameter vector (optimizer update, then the weighted sum of self +
deg neighbor buffers delivered by the collective-permutes).  Unfused that
costs ``(deg + 5)`` HBM reads + 3 writes of P; this kernel fuses it into
``(deg + 3)`` reads + 2 writes with one VMEM-tiled pass:

    m'     = beta * m + g
    theta* = theta - lr * m'
    theta' = w_0 * theta* + Σ_i w_i * n_i         (mix_order="post")

(or, for ``mix_order="pre"``, mix the raw params first and descend after:
``theta' = w_0·theta + Σ_i w_i·n_i − lr·m'``, which needs no pre-send
materialization of theta*).

Two granularities share one kernel math (``_mix_block``):

  * ``gossip_update``          — one node's parameter leaf, any shape: deg
    leaf-shaped neighbor buffers, weights (deg+1,) in SMEM.  The
    production-path round (``fused_apply_shard``) runs it per leaf.
  * ``gossip_program_update``  — a whole stacked replica axis: theta
    (n, P), deg (n, P) neighbor buffers, per-node weights (n, deg+1); the
    grid runs (node group, block) and each cell mixes its nodes' rows with
    their (deg+1,) weight rows, read as one column per neighbor slot and
    broadcast across the lanes.  This is the executor for
    compiled PPermute programs (circulant offsets, matchings, and
    edge-colored irregular graphs alike) — ``fused_apply_stacked`` feeds
    it straight from a ``GossipProgram``.

``lr``/``beta`` ride in a (2,) SMEM vector at *runtime* — LR schedules do
not retrigger compiles — and ``interpret`` auto-detects the backend
(compiled on TPU, interpreter elsewhere).  The per-node weight row is a
runtime operand too, and a second per-node (deg+1,) SMEM *fault row*
``[update, edge_1..edge_deg]`` gates the local update (stragglers/dead)
and masks permute edges, renormalizing dropped weight onto self in-kernel
(``degraded_matrix`` semantics): one executable serves every transient
fault realization, and the all-ones row reproduces the fault-free math
bit-for-bit.  The same row carries the elastic extremes: a *ghost* rank
(``faults.SparePool`` spare — all-zero row) degrades to the identity and
idles until its activation flips the row live, and a *deadline-benched*
straggler keeps ``update = 1`` with edges masked — it descends locally
while sitting out the gossip round.

Layout: ``gossip_update`` tiles a leaf's 2-D view in (8k, 128j) VMEM tiles
of at most max(block, 8 x 1024) elements; a leaf whose rows are a multiple
of 8 and whose last dim is a multiple of 128 is viewed in place, any other
is flattened and zero-padded to whole (8, 1024) tiles.  Its neighbor
buffers are separate operands — on TPU the ppermute landing buffers, so no
stack copy — and its weights and fault row live in SMEM.  Both kernels
take their neighbors as a sequence of deg landing buffers.
``gossip_program_update`` tiles the (n, P) matrices in place
as (rows, block) with rows = n, or 16 when 16 divides n: the TPU's tiling
rule takes a whole dimension or a multiple of 16 rows (8 for f32), and
refuses the (1, block) tile of one node.  Its weight and fault tables ride
in VMEM as (rows, deg+1) tiles.  Viewing (n, P) as (n, P/128, 128) instead
would make XLA relayout every operand, and the TPU compiler's bf16 relayout
of a stacked (n, deg, P) neighbor array takes time that grows with P
(minutes at one 4096 x 14336 matrix).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "gossip_update",
    "gossip_program_update",
    "fused_apply_stacked",
    "fused_apply_shard",
    "fused_bucket_update",
]


def _auto_interpret(interpret):
    """Compiled Pallas on TPU; interpreter everywhere else (exact semantics,
    so CPU tests stay bit-meaningful)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _auto_block(block, interpret):
    """Default tile: 1024 (8·128-aligned VMEM tile) when compiled; 2^20 in
    interpreter mode, whose grid is a host-level loop — the tile bound is
    correctness-irrelevant there and small tiles make the loop the
    bottleneck (~1 ms per grid cell on CPU)."""
    if block is not None:
        return block
    return (1 << 20) if interpret else 1024


def _check_budget(deg, block, interpret):
    """Validate the dispatch signature against the documented SMEM/VMEM
    budgets (``analysis/budget.py``) before building the Pallas call.
    The check is lru-cached per (deg, block) signature over there, so the
    hot path pays one dict lookup."""
    from repro.analysis.budget import check_kernel_budget

    check_kernel_budget(int(deg), int(block), interpret=bool(interpret))


def _mix_block(w, f, theta, nbrs, grad, mom, lr, beta, *, deg, mix_order,
               out_dtype):
    """Shared kernel math on one VMEM tile; ``w[k]`` scalar-indexes SMEM.

    ``f`` is the *fault row* accessor (SMEM, runtime): ``f(0)`` gates this
    node's local update (0 = straggler/dead: gradient discarded, momentum
    untouched) and ``f(i+1)`` masks permute round i's edge.  A dropped edge
    zeroes its weight and renormalizes IN-KERNEL — the lost mass moves onto
    the self weight, keeping the realized row stochastic — so one compiled
    executable serves every transient-fault realization (the all-ones row
    reproduces the fault-free math bit-for-bit).
    """
    g = grad.astype(jnp.float32)
    mom32 = mom.astype(jnp.float32)
    u = f(0)
    m_new = u * (beta * mom32 + g) + (1.0 - u) * mom32
    base = theta.astype(jnp.float32)
    self_w = w(0)
    for i in range(deg):
        self_w = self_w + (1.0 - f(i + 1)) * w(i + 1)
    if mix_order == "post":
        acc = self_w * (base - lr * u * m_new)
    else:  # pre: mix raw params, descend afterwards
        acc = self_w * base
    for i in range(deg):
        acc = acc + f(i + 1) * w(i + 1) * nbrs(i).astype(jnp.float32)
    if mix_order == "pre":
        acc = acc - lr * u * m_new
    return acc.astype(out_dtype), m_new


def _program_kernel(sc_ref, w_ref, f_ref, theta_ref, *refs, deg: int,
                    mix_order: str):
    nbr_refs, (grad_ref, mom_ref, out_ref, mom_out_ref) = refs[:deg], refs[deg:]
    # this tile's (rows, deg+1) weight and fault rows: column k is one
    # value per node, broadcast across the node's lanes
    out, m_new = _mix_block(
        lambda k: w_ref[:, k:k + 1], lambda k: f_ref[:, k:k + 1],
        theta_ref[...], lambda i: nbr_refs[i][...], grad_ref[...],
        mom_ref[...], sc_ref[0], sc_ref[1],
        deg=deg, mix_order=mix_order, out_dtype=out_ref.dtype,
    )
    out_ref[...] = out
    mom_out_ref[...] = m_new


def _leaf_kernel(sc_ref, w_ref, f_ref, theta_ref, *refs, deg: int,
                 mix_order: str):
    nbr_refs, (grad_ref, mom_ref, out_ref, mom_out_ref) = refs[:deg], refs[deg:]
    out, m_new = _mix_block(
        lambda k: w_ref[k], lambda k: f_ref[k], theta_ref[...],
        lambda i: nbr_refs[i][...], grad_ref[...], mom_ref[...],
        sc_ref[0], sc_ref[1],
        deg=deg, mix_order=mix_order, out_dtype=out_ref.dtype,
    )
    out_ref[...] = out
    mom_out_ref[...] = m_new


_LANES = 1024  # lane width of the padded 2-D view of a leaf that cannot tile


def _leaf_view(shape) -> tuple[int, int, int]:
    """(rows, cols, pad) of the 2-D view the kernel tiles a leaf in.

    A leaf of 2+ dims whose row count is a multiple of 8 and whose last dim
    is a multiple of 128 is viewed (rows, last dim): merging leading dims
    keeps the TPU's tiled layout, so the view costs no copy.  Any other
    leaf (1-D, an odd row count, an odd vocab) is flattened and
    zero-padded by ``pad`` elements to whole (8, _LANES) tiles."""
    size = math.prod(shape)
    cols = shape[-1] if shape else 1
    rows = size // cols
    if len(shape) >= 2 and rows % 8 == 0 and cols % 128 == 0:
        return rows, cols, 0
    pad = -size % (8 * _LANES)
    return (size + pad) // _LANES, _LANES, pad


def _leaf_tile(rows: int, cols: int, block: int) -> tuple[int, int]:
    """(8k, 128j) tile of a (rows, cols) leaf view: at most 1024 lanes,
    and the most rows (a multiple of 8 dividing ``rows``) that keep the
    tile within ``block`` elements, or 8 rows when one 8-row tile is
    already larger.  So a tile never exceeds max(block, 8 x 1024)."""
    bc = math.gcd(cols, 1024)
    q = rows // 8
    d = max(1, min(q, block // (8 * bc)))
    while q % d:
        d -= 1
    return 8 * d, bc


@functools.partial(
    jax.jit, static_argnames=("block", "interpret", "mix_order")
)
def _leaf_update(theta, neighbors, weights, fault, grad, momentum, scalars,
                 *, block: int, interpret: bool, mix_order: str):
    """One parameter leaf in its own shape, over ``neighbors``, a tuple of
    deg leaf-shaped landing buffers.  The leaf goes through its
    ``_leaf_view``; only a leaf that cannot tile is padded, and the
    outputs overwrite theta and momentum."""
    shape = theta.shape
    rows, cols, pad = _leaf_view(shape)
    br, bc = _leaf_tile(rows, cols, block)
    _check_budget(len(neighbors), br * bc, interpret)

    def view(x):
        if pad:
            x = jnp.pad(x.reshape(-1), (0, pad))
        return x.reshape(rows, cols)

    def unview(x):
        return (x.reshape(-1)[:theta.size] if pad else x).reshape(shape)

    tile = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    out, m_new = pl.pallas_call(
        functools.partial(_leaf_kernel, deg=len(neighbors), mix_order=mix_order),
        grid=(rows // br, cols // bc),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 3
        + [tile] * (len(neighbors) + 3),
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), theta.dtype),
            jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        ],
        # in place: theta -> theta', momentum -> m' (the tiles are disjoint)
        input_output_aliases={3: 0, 5 + len(neighbors): 1},
        interpret=interpret,
        name="gossip_leaf_update",
    )(scalars, weights.astype(jnp.float32), fault.astype(jnp.float32),
      view(theta), *map(view, neighbors), view(grad), view(momentum))
    return unview(out), unview(m_new)


def gossip_update(
    theta: jax.Array,      # one leaf, any shape
    neighbors,             # deg landing buffers shaped like theta
    weights: jax.Array,    # (deg + 1,) [self, n_1..n_deg]
    grad: jax.Array,       # like theta
    momentum: jax.Array,   # like theta, float32
    *,
    lr,
    beta,
    fault: jax.Array | None = None,  # (deg + 1,) [update, edge_1..edge_deg]
    block: int | None = None,
    interpret: bool | None = None,
    mix_order: str = "post",
) -> tuple[jax.Array, jax.Array]:
    """Returns (theta', m') for one node's leaf.  ``neighbors`` is any
    sequence of deg buffers, so a (deg, *theta.shape) array unstacks into
    one; a tuple of ppermute results is used as it is.  lr/beta/weights/
    fault are runtime values — LR schedules, degraded weight rows, and
    fault masks never recompile."""
    interpret = _auto_interpret(interpret)
    neighbors = tuple(neighbors)
    scalars = jnp.stack(
        [jnp.asarray(lr, jnp.float32), jnp.asarray(beta, jnp.float32)]
    )
    if fault is None:
        fault = jnp.ones((len(neighbors) + 1,), jnp.float32)
    return _leaf_update(
        theta, neighbors, weights, fault, grad, momentum, scalars,
        block=_auto_block(block, interpret), interpret=interpret,
        mix_order=mix_order,
    )


def _node_rows(n: int) -> int:
    """Nodes per tile: all n, or 16 when n is a multiple of 16 (the TPU's
    tiling rule takes a full dimension, or a multiple of 16 rows for bf16
    and of 8 for f32)."""
    return 16 if n % 16 == 0 else n


@functools.partial(
    jax.jit, static_argnames=("block", "interpret", "mix_order")
)
def _gossip_program_update(theta, neighbors, weights, fault, grad, momentum,
                           scalars, *, block: int, interpret: bool,
                           mix_order: str):
    """``neighbors`` is a tuple of deg (n, P) landing buffers."""
    n, p = theta.shape
    deg = len(neighbors)
    block = min(block, p)
    if p % block:
        raise ValueError(f"param length {p} must tile by block {block}")
    rows = _node_rows(n)
    _check_budget(deg, rows * block, interpret)
    tile = pl.BlockSpec((rows, block), lambda i, j: (i, j))
    table = pl.BlockSpec((rows, deg + 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_program_kernel, deg=deg, mix_order=mix_order),
        grid=(n // rows, p // block),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # [lr, beta]
            table,                                  # weights (n, deg+1)
            table,                                  # fault rows, same layout
            tile,                                   # theta
            *[tile] * deg,                          # neighbors
            tile,                                   # grad
            tile,                                   # momentum
        ],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((n, p), theta.dtype),
            jax.ShapeDtypeStruct((n, p), jnp.float32),
        ],
        interpret=interpret,
        name="gossip_program_update",
    )(scalars, weights.astype(jnp.float32), fault.astype(jnp.float32),
      theta, *neighbors, grad, momentum)


def gossip_program_update(
    theta: jax.Array,      # (n, P) stacked replicas
    neighbors,             # deg (n, P) landing buffers
    weights: jax.Array,    # (n, deg + 1) per-node [self, w_1..w_deg]
    grad: jax.Array,       # (n, P)
    momentum: jax.Array,   # (n, P) float32
    *,
    lr,
    beta,
    fault: jax.Array | None = None,  # (n, deg + 1) [update, edge_1..edge_deg]
    block: int | None = None,
    interpret: bool | None = None,
    mix_order: str = "post",
) -> tuple[jax.Array, jax.Array]:
    """Per-node-weight program executor over the stacked axis.

    ``weights`` and ``fault`` are runtime operands: degraded weight rows
    and per-realization edge/update masks reuse the one cached executable
    (the zero-recompile invariant under faults).
    """
    interpret = _auto_interpret(interpret)
    scalars = jnp.stack(
        [jnp.asarray(lr, jnp.float32), jnp.asarray(beta, jnp.float32)]
    )
    neighbors = tuple(neighbors)
    if fault is None:
        fault = jnp.ones((theta.shape[0], len(neighbors) + 1), jnp.float32)
    return _gossip_program_update(
        theta, neighbors, weights, fault, grad, momentum, scalars,
        block=_auto_block(block, interpret), interpret=interpret,
        mix_order=mix_order,
    )


# ---------------------------------------------------------------------------
# Program-level glue: one decentralized SGD round for stacked pytrees
# ---------------------------------------------------------------------------

def _flatten_stacked(tree, n):
    leaves = jax.tree.leaves(tree)
    flat = [x.reshape(n, -1) for x in leaves]
    sizes = [f.shape[1] for f in flat]
    return jnp.concatenate(flat, axis=1), sizes


def _unflatten_stacked(mat, tree, sizes):
    leaves = jax.tree.leaves(tree)
    out, off = [], 0
    for leaf, size in zip(leaves, sizes):
        out.append(mat[:, off:off + size].reshape(leaf.shape).astype(leaf.dtype))
        off += size
    return jax.tree.unflatten(jax.tree.structure(tree), out)


def _landing_buffers(wire, srcs):
    """deg (n, P) neighbor buffers: row i of slot k is node srcs[i, k]'s
    wire."""
    return tuple(
        jnp.concatenate([wire[int(src):int(src) + 1] for src in srcs[:, k]])
        for k in range(srcs.shape[1])
    )


def _fault_rows_stacked(fault, srcs, n):
    """(n, deg+1) kernel fault rows [update, edge_1..deg] from runtime masks.

    ``fault`` is the engines' mask pytree ({"update", "alive", "link"});
    edge k of node i is up iff both endpoints are alive and the link
    survives.  Idle slots (srcs[i, k] == i) carry zero weight, so their
    mask value is irrelevant.
    """
    af = fault["alive"].astype(jnp.float32)
    m = af[jnp.asarray(srcs)] * af[:, None]
    link = fault.get("link")
    if link is not None:
        m = m * link.astype(jnp.float32)[
            jnp.arange(n)[:, None], jnp.asarray(srcs)
        ]
    u = fault["update"].astype(jnp.float32)
    return jnp.concatenate([u[:, None], m], axis=1)


def fused_apply_stacked(
    program,
    params,     # pytree, leaves (n, ...)
    grads,      # matching pytree
    momentum,   # matching pytree (float32), or () when beta == 0
    *,
    lr,
    beta,
    fault=None,  # {"update": (n,), "alive": (n,), "link": (n, n)} or None
    mix_order: str = "post",
    block: int | None = None,
    interpret: bool | None = None,
):
    """One fused momentum-SGD + gossip round for a compiled PPermute program.

    The kernel's single-process test entry, not a trainer path: the tests
    drive the kernel through it against the dense oracle.  Flattens the
    stacked trees to (n, P) (zero-padded to a block multiple),
    gathers each node's neighbor landing buffers per the program's
    ``permute_tables`` — for ``mix_order="post"`` the wire carries the
    *post-update* θ\\*, for ``"pre"`` the raw θ, so nothing extra is
    materialized — and runs ``gossip_program_update``.  Returns
    ``(new_params, new_momentum)`` with the input tree structure.

    ``fault`` carries runtime masks (``core/faults.realization_arrays``):
    straggling/dead nodes skip the update, dropped edges renormalize onto
    self inside the kernel — same executable for every realization.

    Raises ``ValueError`` for programs with non-permute ops (AllReduce /
    GatherRow / fused multi-round): those keep the interpreter path.
    """
    tables = program.permute_tables()
    if tables is None:
        raise ValueError(
            f"program {program.name!r} is not an all-PPermute single round; "
            "fused apply supports permute programs only"
        )
    srcs, weights = tables
    interpret = _auto_interpret(interpret)
    block = _auto_block(block, interpret)
    n = program.n
    theta, sizes = _flatten_stacked(params, n)
    g_mat, _ = _flatten_stacked(grads, n)
    if momentum == () or momentum is None:
        m_mat = jnp.zeros(theta.shape, jnp.float32)
        had_momentum = False
    else:
        m_mat, _ = _flatten_stacked(momentum, n)
        had_momentum = True
    p = theta.shape[1]
    block = min(block, p)
    _check_budget(srcs.shape[1], block, interpret)
    pad = (-p) % block
    if pad:
        theta = jnp.pad(theta, ((0, 0), (0, pad)))
        g_mat = jnp.pad(g_mat, ((0, 0), (0, pad)))
        m_mat = jnp.pad(m_mat, ((0, 0), (0, pad)))

    lr32 = jnp.asarray(lr, jnp.float32)
    beta32 = jnp.asarray(beta, jnp.float32)
    fault_rows = None if fault is None else _fault_rows_stacked(fault, srcs, n)
    if mix_order == "post":
        # the buffers on the wire are the senders' post-update params
        m_wire = beta32 * m_mat + g_mat.astype(jnp.float32)
        if fault is not None:  # stragglers/dead send their un-updated params
            m_wire = m_wire * fault["update"].astype(jnp.float32)[:, None]
        wire = (theta.astype(jnp.float32) - lr32 * m_wire).astype(theta.dtype)
    else:
        wire = theta
    nbrs = _landing_buffers(wire, srcs)

    out, m_new = gossip_program_update(
        theta, nbrs, jnp.asarray(weights), g_mat, m_mat,
        lr=lr32, beta=beta32, fault=fault_rows, block=block,
        interpret=interpret, mix_order=mix_order,
    )
    if pad:
        out = out[:, :p]
        m_new = m_new[:, :p]
    new_params = _unflatten_stacked(out, params, sizes)
    if not had_momentum:
        return new_params, ()
    return new_params, _unflatten_stacked(m_new, momentum, sizes)


def fused_bucket_update(
    program,
    theta_b,    # (n, w_b) one bucket's stacked slice (BucketLayout view)
    grad_b,     # (n, w_b)
    mom_b,      # (n, w_b) float32 (zeros when the optimizer is momentum-free)
    *,
    lr,
    beta,
    fault=None,  # {"update": (n,), "alive": (n,), "link": (n, n)} or None
    mix_order: str = "post",
    block: int | None = None,
    interpret: bool | None = None,
):
    """One bucket's fused SGD + gossip round on raw (n, w_b) matrices.

    The bucket boundary is the kernel's *outer dispatch unit*: the engines
    slice the flattened tree with a ``BucketLayout`` and call this once per
    bucket, so bucket i's permute-landing gathers and kernel pass carry no
    data dependency on bucket i+1's — the dispatches pipeline.  Inside,
    the (node, block) grid of ``gossip_program_update`` runs unchanged over
    the bucket's width, and each node's (deg+1,) SMEM weight/fault rows are
    byte-identical across buckets (width never enters them), so the rows
    are re-selected, never re-built, per bucket.  Skips the pytree
    flatten/unflatten of ``fused_apply_stacked`` — the layout already did
    it once for all buckets.  Returns ``(theta_b', mom_b')``.
    """
    tables = program.permute_tables()
    if tables is None:
        raise ValueError(
            f"program {program.name!r} is not an all-PPermute single round; "
            "fused apply supports permute programs only"
        )
    srcs, weights = tables
    interpret = _auto_interpret(interpret)
    block = _auto_block(block, interpret)
    n = program.n
    theta = theta_b
    g_mat = grad_b
    m_mat = mom_b.astype(jnp.float32)
    p = theta.shape[1]
    block = min(block, max(p, 1))
    _check_budget(srcs.shape[1], block, interpret)
    pad = (-p) % block
    if pad:
        theta = jnp.pad(theta, ((0, 0), (0, pad)))
        g_mat = jnp.pad(g_mat, ((0, 0), (0, pad)))
        m_mat = jnp.pad(m_mat, ((0, 0), (0, pad)))

    lr32 = jnp.asarray(lr, jnp.float32)
    beta32 = jnp.asarray(beta, jnp.float32)
    fault_rows = None if fault is None else _fault_rows_stacked(fault, srcs, n)
    if mix_order == "post":
        m_wire = beta32 * m_mat + g_mat.astype(jnp.float32)
        if fault is not None:
            m_wire = m_wire * fault["update"].astype(jnp.float32)[:, None]
        wire = (theta.astype(jnp.float32) - lr32 * m_wire).astype(theta.dtype)
    else:
        wire = theta
    nbrs = _landing_buffers(wire, srcs)

    out, m_new = gossip_program_update(
        theta, nbrs, jnp.asarray(weights), g_mat, m_mat,
        lr=lr32, beta=beta32, fault=fault_rows, block=block,
        interpret=interpret, mix_order=mix_order,
    )
    if pad:
        out = out[:, :p]
        m_new = m_new[:, :p]
    return out, m_new


def fused_apply_shard(
    program,
    params,     # pytree of THIS node's values (inside shard_map)
    grads,
    momentum,   # matching pytree (float32), or () when beta == 0
    axis_names,
    *,
    lr,
    beta,
    fault=None,  # {"update": (n,), "alive": (n,), "link": (n, n)} or None
    mix_order: str = "post",
    block: int | None = None,
    interpret: bool | None = None,
):
    """The production-path twin of ``fused_apply_stacked``: one fused
    momentum-SGD + gossip round on per-node values inside ``shard_map``.

    Runs ``gossip_update`` leaf by leaf in each leaf's own shape, so the
    round makes no flattened copy of the tree (only a leaf that cannot
    tile, such as a norm gain, is padded on its own).  One
    ``jax.lax.ppermute`` per compiled permute delivers each
    leaf's landing buffers (non-participating nodes receive zeros, matching
    the zero weight in their SMEM row); this node's (deg+1,) weight row is
    selected by its flat axis index.  ``fault`` carries the replicated
    runtime masks — this node slices its own update flag and edge-mask row,
    so every realization reuses the one executable.  Returns
    ``(new_params, new_momentum)``.
    """
    from repro.core.schedule import _flat_axis_index  # avoid import cycle

    tables = program.permute_tables()
    if tables is None:
        raise ValueError(
            f"program {program.name!r} is not an all-PPermute single round; "
            "fused apply supports permute programs only"
        )
    srcs, weights = tables
    interpret = _auto_interpret(interpret)
    block = _auto_block(block, interpret)
    had_momentum = momentum is not None and not (
        isinstance(momentum, tuple) and not momentum
    )

    idx = _flat_axis_index(axis_names)
    lr32 = jnp.asarray(lr, jnp.float32)
    beta32 = jnp.asarray(beta, jnp.float32)
    frow = None
    if fault is not None:
        # this node's row of the shared edge-up mask formula
        frow = _fault_rows_stacked(fault, srcs, srcs.shape[0])[idx]
    wrow = jnp.asarray(weights)[idx]

    def leaf_round(x, g, m):
        m32 = jnp.zeros(x.shape, jnp.float32) if m is None else m.astype(jnp.float32)
        if mix_order == "post":
            m_wire = beta32 * m32 + g.astype(jnp.float32)
            if fault is not None:
                m_wire = m_wire * frow[0]
            wire = (x.astype(jnp.float32) - lr32 * m_wire).astype(x.dtype)
        else:
            wire = x
        nbrs = tuple(
            jax.lax.ppermute(wire, axis_names, list(op.perm))
            for op in program.ops
        )
        return gossip_update(
            x, nbrs, wrow, g, m32, lr=lr32, beta=beta32, fault=frow,
            block=block, interpret=interpret, mix_order=mix_order,
        )

    leaves, treedef = jax.tree.flatten(params)
    g_leaves = jax.tree.leaves(grads)
    m_leaves = jax.tree.leaves(momentum) if had_momentum else [None] * len(leaves)
    rounds = [leaf_round(*a) for a in zip(leaves, g_leaves, m_leaves)]
    new_params = jax.tree.unflatten(treedef, [o for o, _ in rounds])
    if not had_momentum:
        return new_params, ()
    return new_params, jax.tree.unflatten(
        jax.tree.structure(momentum),
        [mn.astype(m.dtype) for (_, mn), m in zip(rounds, m_leaves)],
    )
