"""Bucketed gossip execution: partition the parameter vector, pipeline it.

All gossip used to run as ONE monolithic dispatch over the full flattened
parameter vector, with the Ξ_t consensus probe as a separate tiny dispatch
— so communication serialized entirely behind compute on the hot path.
This module supplies the two pieces that break that tail barrier
("From Promise to Practice", arXiv:2410.11998; decent-dp's
``param_as_bucket_view`` / ``bucket_size_in_mb``):

``BucketLayout``
    A *deterministic, size-targeted* partition of the flattened parameter
    pytree into contiguous buckets of ~``bucket_mb`` MiB (float32
    accounting, so the layout is dtype- and value-independent).  Buckets
    may cross leaf boundaries; a segment table maps each bucket to its
    ``(leaf, start, stop)`` slices, and ``split_stacked`` /
    ``merge_stacked`` round-trip exactly.  The layout is built from
    abstract leaf shapes, so the same tree always buckets identically.

``build_bucket_step``
    The per-bucket executor: one jitted dispatch that runs bucket *b*'s
    plain-SGD update AND its gossip mixing rounds (interpreter or fused
    Pallas kernel), plus this bucket's partial Ξ_t sum, accumulated into
    a tiny (n,) token threaded bucket-to-bucket.  Bucket *i*'s (n, w)
    parameter/gradient payload carries NO dependency on bucket *i−1*'s
    output — only the token does — so the simulator issues all B dispatches
    back-to-back, the token pins a consistent cross-device execution
    order (required: independent collective-bearing executables can
    otherwise start in different per-device orders and deadlock at the
    permute rendezvous), and the runtime pipelines the payload work.  On a TPU mesh the
    same structure overlaps bucket *i*'s PPermutes with bucket *i+1*'s
    update; on the 2-CPU XLA box it lands as dispatch pipelining plus
    cache blocking (each bucket's update output is still cache-hot when
    its mixing pass reads it — the monolithic step streams the full
    multi-MB vector through memory once per pass instead).

Executable accounting: every full bucket has the same width, so jax's
shape-keyed jit cache compiles ONE executable per (program, width) — at
most two per program (full width + tail) regardless of bucket count, and
fault masks stay runtime operands, so executables scale with distinct
programs, not with buckets × faults.

The Ξ_t probe fold: each bucket's dispatch returns the per-node partial
sum  Σ_{c ∈ bucket} (x_ic − x̄_c)²  over its POST-MIX values.  Summing the
partials over buckets equals ``consensus_sq_stacked`` of the new params
exactly (the consensus distance decomposes per coordinate), so the simulator
caches the folded (n,) vector and the next probe takes a host-side √mean
instead of dispatching the standalone probe executable.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.telemetry import profile

PyTree = Any

__all__ = [
    "BucketLayout",
    "MAX_INFLIGHT_BUCKETS",
    "build_bucket_step",
    "bucket_eligible_optimizer",
    "xi_from_folded_sq",
]

_F32_BYTES = 4  # layout accounting is dtype-independent by design

# Dispatch-window depth for the per-bucket pipeline.  The Ξ² token chain
# orders bucket executables per device, but XLA's CPU runtime matches
# cross-module collectives at a global rendezvous, and queueing hundreds
# of collective-bearing launches at once can strand a rank there (7 of 8
# waiting at a permute while the scheduler never runs the 8th) even with
# the token chain in place.  The simulator therefore blocks on the token of
# the bucket leaving the window before dispatching a new one: at most
# this many bucket launches are in flight — plenty to overlap bucket i's
# permutes with bucket i+1's compute — and the host sync is on a tiny
# (n,) f32 vector, so the payload transfers stay asynchronous.  This also
# bounds staging memory to window × bucket bytes per node.
MAX_INFLIGHT_BUCKETS = 4


def _leaf_sizes_stacked(tree: PyTree) -> tuple[int, ...]:
    """Per-node flat element count of each leaf (leading axis = node axis)."""
    sizes = []
    for leaf in jax.tree.leaves(tree):
        shape = leaf.shape
        size = 1
        for d in shape[1:]:
            size *= int(d)
        sizes.append(size)
    return tuple(sizes)


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Deterministic size-targeted partition of a flattened parameter tree.

    ``sizes`` is the per-node flat element count of each leaf in tree
    order; ``bucket_elems`` the target elements per bucket.  The partition
    is contiguous equal-width slices of the concatenated [0, P) vector —
    every bucket but the last has exactly ``bucket_elems`` elements, so
    the jit shape cache shares one executable across all full buckets.
    Build via ``for_stacked`` (works on concrete arrays
    and ``ShapeDtypeStruct`` trees alike — only shapes are read).
    """

    sizes: tuple[int, ...]
    bucket_elems: int

    def __post_init__(self):
        if self.bucket_elems < 1:
            raise ValueError(f"bucket_elems must be >= 1, got {self.bucket_elems}")
        if any(s < 0 for s in self.sizes):
            raise ValueError(f"negative leaf size in {self.sizes}")

    # -- constructors --------------------------------------------------------
    @staticmethod
    def elems_for_mb(bucket_mb: float) -> int:
        """Target elements per bucket for a MiB budget (float32 accounting)."""
        return max(1, int(float(bucket_mb) * (1 << 20)) // _F32_BYTES)

    @classmethod
    def for_stacked(cls, tree: PyTree, bucket_mb: float) -> "BucketLayout":
        """Layout for trees whose leaves carry a leading (n, ...) node axis."""
        return cls(_leaf_sizes_stacked(tree), cls.elems_for_mb(bucket_mb))

    # -- derived views -------------------------------------------------------
    @property
    def total(self) -> int:
        return sum(self.sizes)

    @property
    def num_buckets(self) -> int:
        p = self.total
        if p == 0:
            return 1
        return -(-p // self.bucket_elems)

    @property
    def bounds(self) -> tuple[int, ...]:
        """Bucket boundaries 0 = b_0 < b_1 < ... < b_B = P."""
        cached = self.__dict__.get("_bounds")
        if cached is None:
            p = self.total
            cuts = list(range(0, p, self.bucket_elems)) + [p]
            if len(cuts) == 1:  # empty tree: one empty bucket
                cuts = [0, 0]
            cached = tuple(cuts)
            object.__setattr__(self, "_bounds", cached)
        return cached

    @property
    def widths(self) -> tuple[int, ...]:
        b = self.bounds
        return tuple(b[i + 1] - b[i] for i in range(len(b) - 1))

    @property
    def segments(self) -> tuple[tuple[tuple[int, int, int], ...], ...]:
        """Per bucket: ``(leaf_index, start, stop)`` slices in leaf-local flat
        coordinates.  Buckets freely cross leaf boundaries."""
        cached = self.__dict__.get("_segments")
        if cached is None:
            starts = []  # global offset of each leaf
            off = 0
            for s in self.sizes:
                starts.append(off)
                off += s
            out = []
            b = self.bounds
            for k in range(len(b) - 1):
                lo, hi = b[k], b[k + 1]
                segs = []
                for li, (s0, sz) in enumerate(zip(starts, self.sizes)):
                    s, e = max(lo, s0), min(hi, s0 + sz)
                    if e > s:
                        segs.append((li, s - s0, e - s0))
                out.append(tuple(segs))
            cached = tuple(out)
            object.__setattr__(self, "_segments", cached)
        return cached

    def describe(self) -> str:
        return (
            f"BucketLayout(P={self.total}, target={self.bucket_elems}, "
            f"buckets={self.num_buckets}, widths={self.widths})"
        )

    # -- stacked (n, ...) views ----------------------------------------------
    def _check(self, sizes) -> None:
        if tuple(sizes) != self.sizes:
            raise ValueError(
                f"tree leaf sizes {tuple(sizes)} do not match layout {self.sizes}"
            )

    def split_stacked(self, tree: PyTree) -> list[jax.Array]:
        """Bucket matrices [(n, w_0), (n, w_1), ...] of the stacked tree."""
        leaves = jax.tree.leaves(tree)
        self._check(_leaf_sizes_stacked(tree))
        n = leaves[0].shape[0]
        flat = [x.reshape(n, -1) for x in leaves]
        out = []
        for segs in self.segments:
            parts = [flat[li][:, s:e] for li, s, e in segs]
            if not parts:
                out.append(jnp.zeros((n, 0), jnp.float32))
            elif len(parts) == 1:
                out.append(parts[0])
            else:
                out.append(jnp.concatenate(parts, axis=1))
        return out

    def merge_stacked(self, mats: Sequence[jax.Array], tree_like: PyTree) -> PyTree:
        """Inverse of ``split_stacked``: bucket matrices back into the tree."""
        leaves = jax.tree.leaves(tree_like)
        self._check(_leaf_sizes_stacked(tree_like))
        pieces: list[list[jax.Array]] = [[] for _ in leaves]
        for mat, segs in zip(mats, self.segments):
            off = 0
            for li, s, e in segs:
                pieces[li].append(mat[:, off:off + (e - s)])
                off += e - s
        out = []
        for leaf, ps in zip(leaves, pieces):
            if not ps:  # zero-size leaf
                n = mats[0].shape[0] if mats else leaf.shape[0]
                flat = jnp.zeros((n, 0), jnp.float32)
            elif len(ps) == 1:
                flat = ps[0]
            else:
                flat = jnp.concatenate(ps, axis=1)
            out.append(flat.reshape(leaf.shape).astype(leaf.dtype))
        return jax.tree.unflatten(jax.tree.structure(tree_like), out)


# ---------------------------------------------------------------------------
# The per-bucket executor (the simulator's bucketed path)
# ---------------------------------------------------------------------------

def bucket_eligible_optimizer(optimizer) -> bool:
    """Can this optimizer's update be re-run independently per bucket?

    True for the SGD family: the update is elementwise (momentum state
    mirrors the params leaf-for-leaf, so it buckets identically, and
    weight decay / Nesterov stay elementwise too).  AdamW (global step
    counter in its state tree) and LARS (per-*layer* trust ratios that a
    bucket boundary would corrupt) keep the monolithic path.
    """
    hyper = optimizer.hyper or {}
    return hyper.get("kind") == "sgd"


def xi_from_folded_sq(folded_sq) -> float:
    """Host-side Ξ_t from the accumulated per-node partial sums (final √)."""
    import numpy as np

    sq = np.asarray(folded_sq)
    return float(np.sqrt(np.mean(sq))) if sq.size else 0.0


@profile.scope("probe")
def _bucket_partial_sq(out_mat: jax.Array) -> jax.Array:
    """This bucket's per-node partial Σ_c (x_ic - x̄_c)² — (n,) float32.

    Summed over buckets this equals ``consensus_sq_stacked`` of the merged
    tree exactly: the consensus distance decomposes per coordinate.
    """
    xf = out_mat.astype(jnp.float32)
    d = xf - xf.mean(axis=0, keepdims=True)
    return jnp.sum(d * d, axis=1)


def build_bucket_step(
    program,
    *,
    hyper: dict,
    has_momentum: bool,
    mix_order: str = "post",
    faulty: bool = False,
    kernel_split=None,
):
    """Build the jittable per-bucket dispatch: SGD update + mixing rounds.

    The returned function operates on one bucket's (n, w) matrices::

        fn(theta_b, mom_b, grad_b, lr, tok[, fault]) -> (theta_b', mom_b', tok')

    (``mom_b`` / ``mom_b'`` omitted when ``has_momentum`` is False).
    ``tok`` is the running (n,) Ξ² accumulator: ``tok' = tok + partial_b``
    where ``partial_b`` is this bucket's per-node post-mix Σ(x−x̄)².  It is
    deliberately threaded bucket-to-bucket even though the payload slices
    are independent: the tiny (n,) dependency pins a CONSISTENT execution
    order across devices (independent executables that each contain
    collectives may otherwise start in different orders on different
    devices and deadlock at the permute rendezvous — observed on the XLA
    CPU client), while the (n, w) parameter/gradient payloads still carry
    no cross-bucket dependency, so runtimes with per-op dependency
    tracking overlap bucket *i*'s permutes with bucket *i+1*'s update.
    The last bucket's ``tok'`` is the full folded Ξ² vector — the probe
    fold costs zero extra dispatches.  ``fault`` is the simulator's
    runtime-mask pytree (``realization_arrays``): update gating and edge
    renormalization ride as runtime values, so every realization reuses
    the one executable.

    ``kernel_split=(first, rest)`` routes the update + first mixing round
    through the fused Pallas kernel (``fused_bucket_update`` — the bucket
    boundary is the kernel's outer dispatch unit) and the remaining fused
    stages through the interpreter; ``None`` runs all-interpreter.  The
    kernel path supports plain momentum-SGD only (the fused-apply gate);
    the interpreter path additionally handles weight decay and Nesterov.

    Only ``mix_order="post"`` buckets: with "pre" mixing the simulator keeps
    the monolithic step (descent must follow the full-tree mix there, so
    there is nothing to pipeline behind).
    """
    if mix_order != "post":
        raise ValueError("bucketed execution requires mix_order='post'")
    if hyper.get("kind") != "sgd":
        raise ValueError(
            f"bucketed execution supports the SGD family only, got {hyper!r}"
        )
    beta = float(hyper.get("momentum", 0.0))
    wd = float(hyper.get("weight_decay", 0.0) or 0.0)
    nesterov = bool(hyper.get("nesterov", False))
    if kernel_split is not None and (wd or nesterov):
        raise ValueError("the fused kernel path supports plain momentum-SGD only")

    @profile.scope("gossip")
    def _mix(mat, fault, stage=program):
        if faulty:
            return stage.apply_masked(
                mat, fault["alive"], link_up=fault.get("link")
            )
        return stage.apply_stacked(mat)

    @profile.scope("optimizer")
    def _update(theta, mom, grad, lr, fault):
        """Elementwise SGD on one bucket matrix; returns (theta*, mom')."""
        t32 = theta.astype(jnp.float32)
        g32 = grad.astype(jnp.float32)
        if wd:
            g32 = g32 + wd * t32
        if beta == 0.0:
            step_v, new_m = g32, mom
        else:
            new_m = beta * mom + g32
            step_v = g32 + beta * new_m if nesterov else new_m
        t_new = t32 - jnp.asarray(lr, jnp.float32) * step_v
        if faulty:
            # stragglers/dead skip their local update entirely
            u = fault["update"].astype(jnp.float32)[:, None]
            t_new = jnp.where(u > 0, t_new, t32)
            if beta != 0.0:
                new_m = jnp.where(u > 0, new_m, mom)
        return t_new.astype(theta.dtype), new_m

    def _kernel_round(theta, mom, grad, lr, fault):
        from repro.kernels.gossip_update import fused_bucket_update

        first, rest = kernel_split
        with profile.scope("fused_update"):
            t_new, m_new = fused_bucket_update(
                first, theta, grad, mom,
                lr=lr, beta=beta, fault=fault, mix_order="post",
            )
        for stage in rest:
            t_new = _mix(t_new, fault, stage)
        return t_new, m_new

    def bucket_step(theta_b, mom_b, grad_b, lr, tok, fault=None):
        if kernel_split is not None:
            mixed, m_new = _kernel_round(theta_b, mom_b, grad_b, lr, fault)
        else:
            theta_star, m_new = _update(theta_b, mom_b, grad_b, lr, fault)
            mixed = _mix(theta_star, fault)
        tok_out = tok.astype(jnp.float32) + _bucket_partial_sq(mixed)
        return mixed, m_new, tok_out

    if has_momentum:
        if faulty:
            return bucket_step
        return lambda t, m, g, lr, tok: bucket_step(t, m, g, lr, tok)

    # momentum-free signature: no state matrix in or out
    def bucket_step_nomom(theta_b, grad_b, lr, tok, fault=None):
        zeros = jnp.zeros(theta_b.shape, jnp.float32)
        mixed, _, tok_out = bucket_step(theta_b, zeros, grad_b, lr, tok, fault)
        return mixed, tok_out

    if faulty:
        return bucket_step_nomom
    return lambda t, g, lr, tok: bucket_step_nomom(t, g, lr, tok)
