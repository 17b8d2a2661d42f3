"""Mixing-program IR: compile any communication graph into a gossip program.

A ``GossipProgram`` is a small list of primitive communication ops that
realizes one mixing step  θ ← W θ  for an n-node gossip graph:

  * ``PPermute(perm, weight[, offset])`` — every node receives one weighted
    neighbor buffer along a permutation (a single collective-permute on the
    wire).  ``offset`` marks the circulant special case (perm is the shift
    ``i ← i+d``), which the stacked interpreter realizes as one ``jnp.roll``.
  * ``AllReduce()``                      — uniform average over all nodes
    (ring all-reduce; the complete-graph fast path).
  * ``GatherRow(w)``                     — dense fallback: gather all
    replicas, contract with this node's row of W.  Exact for *any* W; costs
    an all-gather (kept for the paper-faithful dense baseline and irregular
    graphs with no sparse decomposition).

Program semantics (all interpreters agree to float32 accumulation):

    out = self_weight ⊙ x + Σ_op op(x)

with ``self_weight`` a scalar or per-node vector (irregular graphs weight
their own replica differently per node).

Three interpreters share the single compiled program:

  * ``apply_dense``   — dense mixing-matrix einsum over the stacked replica
                        axis.  The paper-faithful oracle.
  * ``apply_stacked`` — rolls/gathers over the stacked axis (vmap engine;
                        under jit on a sharded axis XLA lowers each roll to
                        collective-permutes).
  * ``apply_shard``   — explicit collectives inside ``shard_map`` (SPMD
                        production engine): one ``jax.lax.ppermute`` per
                        ``PPermute``, ``pmean`` for ``AllReduce``,
                        all-gather + row contraction for ``GatherRow``.

``compile_graph`` picks the cheapest faithful realization:
circulant graph → one PPermute per offset; complete graph → AllReduce;
any other ``EdgeGraph`` (matchings, the star, arbitrary irregular graphs) →
an **edge-colored permute program**: the edge set is partitioned into
≤ Δ+1 matchings (Vizing's theorem, constructive Misra–Gries coloring with
a greedy fast path), each matching becomes one per-node-weighted PPermute,
and the diagonal of W rides in ``self_weight``.  The decomposition is
verified against W exactly at compile time; only if it cannot reproduce W
does the compiler fall back to the ``GatherRow`` dense all-gather.  A star
at n = 1008 therefore moves O(Δ) buffers per step instead of the O(n·P)
all-gather.

Multi-step fusion: ``GossipProgram.fuse`` composes H consecutive programs
(e.g. a full one-peer exponential cycle) into one ``FusedProgram`` whose
interpreters run all H rounds inside a single jitted executable — H
dispatches become one, and engines cache it under one key.

Programs are frozen/hashable: both engines key their compiled-executable
caches on the program, so time-varying topologies rotate through a bounded
executable set — one XLA compile per distinct program at its first use and
zero recompiles thereafter (``Topology.distinct_programs`` enumerates the
set up front).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

__all__ = [
    "PPermute",
    "AllReduce",
    "GatherRow",
    "GossipProgram",
    "FusedProgram",
    "compile_graph",
    "degraded_matrix",
    "dense_program",
    "edge_coloring",
    "hub_balanced_rounds",
    "identity_program",
    "maybe_hub_balanced",
    "permutation_for_offset",
    "program_comm_bytes",
    "program_max_node_bytes",
]


# ---------------------------------------------------------------------------
# Primitive ops
# ---------------------------------------------------------------------------

def permutation_for_offset(n: int, d: int) -> tuple[tuple[int, int], ...]:
    """ppermute pairs so that node i receives from node (i + d) % n."""
    return tuple(((i + d) % n, i) for i in range(n))


@dataclasses.dataclass(frozen=True)
class PPermute:
    """Receive one weighted buffer along a permutation.

    perm: (src, dst) pairs; a dst absent from the list receives zeros.
    weight: scalar, or per-dst-node tuple of length n (applied at receiver).
    offset: when the perm is the circulant shift ``dst ← dst + offset``,
      the stacked interpreter uses one ``jnp.roll`` instead of a gather.
    """

    perm: tuple[tuple[int, int], ...]
    weight: Union[float, tuple[float, ...]]
    offset: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class AllReduce:
    """Uniform average over all nodes (contributes J/n to W)."""


@dataclasses.dataclass(frozen=True)
class GatherRow:
    """Dense fallback: all-gather replicas, contract with this node's W row.

    w: the full n×n mixing matrix (including the diagonal) as nested tuples.
    """

    w: tuple[tuple[float, ...], ...]


Op = Union[PPermute, AllReduce, GatherRow]


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------

def _weight_column(weight, n: int) -> np.ndarray:
    if isinstance(weight, tuple):
        return np.asarray(weight, dtype=np.float64)
    return np.full(n, float(weight), dtype=np.float64)


def degraded_matrix(w, alive, link_up=None) -> np.ndarray:
    """The fault-degraded mixing matrix W' (the dense oracle, float64).

    Every off-diagonal entry whose edge is down — either endpoint not in
    ``alive``, or the link itself masked by ``link_up`` — is zeroed and its
    mass moved onto the *receiver's* diagonal, so W' stays row-stochastic
    for any W, symmetric when W and the masks are symmetric (and therefore
    doubly stochastic when W is).  A node that loses every edge — dead, or
    isolated by link failures — self-averages: its row becomes identity and
    its parameters are untouched by the mixing step.

    This single rule is the semantic shared by ``GossipProgram.degrade``
    (the pre-enumerated permanent-crash program transform), the runtime
    masked interpreters (``apply_masked`` / ``apply_shard_masked``), and
    the in-kernel renormalization of the fused Pallas apply: all three
    realize exactly this matrix for the same masks.

    Two consequences the elastic-membership subsystem relies on:

    *Composition.*  Degrading only zeroes off-diagonal entries and moves
    their mass to the receiver diagonal, so degrading by mask A and then
    runtime-masking by mask B realizes exactly ``degraded_matrix(W, A∩B)``
    — a k-node concurrent crash composes runtime masks over the existing
    single-node-out programs and needs NO multi-node-out enumeration.

    *Float masks.*  The formula is linear in ``alive``: a value b > 1 at
    node d scales every edge weight touching d by b (the excess subtracted
    from the receiver's diagonal).  A symmetric float mask keeps W'
    symmetric and row sums at 1, so W' stays doubly stochastic and the
    global mean is preserved — the mean-preserving preemption drain
    (``faults.Preemption``) up-weights a departing node exactly this way.
    Nonnegativity bounds the boost: node d's diagonal needs
    ``w_dd >= (b-1) * sum_j w_dj``.

    *Ghost ranks.*  A rank masked dead from step 0 (``faults.SparePool``'s
    spare, alive = 0 throughout) degrades to the exact identity row AND
    column: it is an inert fixed point of the mixing and the alive block
    stays doubly stochastic.  Over-provisioning a mesh with such ghosts is
    therefore free in the mixing math, and *activating* one — flipping its
    mask to 1 at an elastic join — is just a different runtime realization
    of the same W: no re-formation, no new programs.
    """
    w = np.asarray(w, dtype=np.float64)
    n = w.shape[0]
    alive = np.asarray(alive, dtype=np.float64).reshape(n)
    em = np.outer(alive, alive)
    if link_up is not None:
        em = em * np.asarray(link_up, dtype=np.float64)
    off = w * em
    np.fill_diagonal(off, 0.0)
    return off + np.diag(1.0 - off.sum(axis=1))


def _flat_axis_index(axis_names):
    """Node index along (possibly multiple) manual mesh axes."""
    if isinstance(axis_names, str):
        return jax.lax.axis_index(axis_names)
    idx = jnp.zeros((), jnp.int32)
    for a in axis_names:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


@dataclasses.dataclass(frozen=True)
class GossipProgram:
    """A compiled mixing schedule: out = self_weight ⊙ x + Σ_op op(x)."""

    name: str
    n: int
    ops: tuple[Op, ...]
    self_weight: Union[float, tuple[float, ...]] = 0.0

    # -- views ---------------------------------------------------------------
    @property
    def cache_key(self):
        """Cheap hashable identity for per-executable step caches.

        Computed once per program: dict lookups must not re-hash the op
        tuple every training step (a GatherRow at n=1008 holds ~1M floats).
        The sha256 digest of the canonical repr makes collisions across
        distinct programs practically impossible.
        """
        key = self.__dict__.get("_cache_key")
        if key is None:
            import hashlib

            digest = hashlib.sha256(
                repr((self.n, self.ops, self.self_weight)).encode()
            ).hexdigest()[:32]
            key = (self.name, self.n, digest)
            object.__setattr__(self, "_cache_key", key)
        return key

    @property
    def is_identity(self) -> bool:
        return not self.ops

    @property
    def num_collectives(self) -> int:
        return len(self.ops)

    def matrix(self) -> np.ndarray:
        """The dense (n, n) mixing matrix W this program realizes (float64)."""
        return _program_matrix(self)

    def describe(self) -> str:
        kinds = [type(op).__name__ for op in self.ops]
        return f"{self.name}(n={self.n}, ops=[{', '.join(kinds)}])"

    def permute_tables(self):
        """Dense per-node tables for an all-PPermute program, or ``None``.

        Returns ``(srcs, weights)`` with ``srcs`` an (n, deg) int32 array —
        ``srcs[i, k]`` is the node whose buffer node i receives in permute
        round k (itself when i idles that round) — and ``weights`` an
        (n, deg+1) float32 array ``[self, w_1 .. w_deg]`` whose masked
        entries are 0.  This is the layout the fused Pallas kernel consumes:
        each node's weight row is one (deg+1,) SMEM vector.
        """
        if not self.ops or not all(isinstance(op, PPermute) for op in self.ops):
            return None
        n, deg = self.n, len(self.ops)
        srcs = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, deg))
        weights = np.zeros((n, deg + 1), dtype=np.float32)
        weights[:, 0] = _weight_column(self.self_weight, n)
        for k, op in enumerate(self.ops):
            wv = _weight_column(op.weight, n)
            for s, d in op.perm:
                srcs[d, k] = s
                weights[d, k + 1] = wv[d]
        return srcs, weights

    def degrade(self, alive) -> "GossipProgram":
        """The program for the surviving membership ``alive`` ((n,) bools).

        Removes every permute pair with a dead endpoint and renormalizes by
        moving the dropped weight onto the receiver's self weight, so the
        result realizes exactly ``degraded_matrix(self.matrix(), alive)``:
        still row-stochastic, symmetric when the base is, dead/isolated
        nodes self-averaging.  Programs with non-permute ops (AllReduce /
        GatherRow) fall back to one GatherRow of the degraded dense matrix.

        This is the *permanent-crash* path: each alive-set yields one new
        (cached, hashable) program, pre-enumerated by
        ``Topology.distinct_programs`` so crashes never recompile mid-run.
        Transient faults instead keep the base program and feed runtime
        masks to ``apply_masked`` — same matrix, zero new executables.
        """
        alive_t = tuple(bool(a) for a in np.asarray(alive).reshape(-1))
        if len(alive_t) != self.n:
            raise ValueError(f"alive mask has {len(alive_t)} entries, n={self.n}")
        if all(alive_t):
            return self
        return _degrade_cached(self, alive_t)

    # -- runtime-masked interpreters (transient faults; no new executables) --
    def _masked_tables(self, alive, link_up):
        """(srcs const, per-node effective weight rows) under runtime masks.

        ``alive`` is an (n,) runtime array, ``link_up`` an optional (n, n)
        runtime array; the returned weights are traced values, so one
        jitted executable serves every fault realization.  ``alive`` may
        be a *float* mask (see ``degraded_matrix``): values in (0, 1)
        down-weight a node's edges, values > 1 up-weight them (preemption
        drain) — the w0 compensation keeps every row sum at 1 either way.
        """
        tables = self.permute_tables()
        if tables is None:
            return None
        srcs_np, weights_np = tables
        srcs = jnp.asarray(srcs_np)
        w = jnp.asarray(weights_np)
        af = jnp.asarray(alive, jnp.float32).reshape(self.n)
        m = af[srcs] * af[:, None]
        if link_up is not None:
            lm = jnp.asarray(link_up, jnp.float32)
            m = m * lm[jnp.arange(self.n)[:, None], srcs]
        wn = w[:, 1:] * m
        w0 = w[:, 0] + jnp.sum(w[:, 1:] * (1.0 - m), axis=1)
        return srcs_np, jnp.concatenate([w0[:, None], wn], axis=1)

    def _masked_matrix(self, alive, link_up):
        """Runtime degraded matrix (traced) — the dense fallback/oracle."""
        w0 = jnp.asarray(self.matrix(), jnp.float32)
        af = jnp.asarray(alive, jnp.float32).reshape(self.n)
        em = af[:, None] * af[None, :]
        if link_up is not None:
            em = em * jnp.asarray(link_up, jnp.float32)
        off = w0 * em * (1.0 - jnp.eye(self.n, dtype=jnp.float32))
        return off + jnp.diag(1.0 - jnp.sum(off, axis=1))

    def apply_masked(
        self, tree: PyTree, alive, *, link_up=None, engine: str = "stacked"
    ) -> PyTree:
        """One fault-degraded mixing step with *runtime* masks.

        Equivalent to ``self.degrade(alive).apply(...)`` (plus link
        masking) but with the masks as traced inputs: a new fault
        realization changes only array values, never the executable.
        ``engine="dense"`` multiplies by the runtime degraded matrix (the
        oracle); ``engine="stacked"`` uses the masked permute tables when
        the program is all-PPermute and the dense matrix otherwise.
        """
        if engine not in ("dense", "stacked"):
            raise ValueError(f"unknown engine {engine!r}")
        masked = self._masked_tables(alive, link_up)
        if engine == "dense" or masked is None:
            wm = self._masked_matrix(alive, link_up)

            def _mix(x):
                return jnp.einsum(
                    "ij,j...->i...", wm, x.astype(jnp.float32)
                ).astype(x.dtype)

            return jax.tree.map(_mix, tree)
        srcs_np, weights = masked
        n = self.n

        def _col(v, ndim):
            return v.reshape((n,) + (1,) * (ndim - 1))

        def _mix(x):
            xf = x.astype(jnp.float32)
            acc = _col(weights[:, 0], x.ndim) * xf
            for k in range(srcs_np.shape[1]):
                gathered = jnp.take(xf, jnp.asarray(srcs_np[:, k]), axis=0)
                acc = acc + _col(weights[:, k + 1], x.ndim) * gathered
            return acc.astype(x.dtype)

        return jax.tree.map(_mix, tree)

    def apply_shard_masked(self, local: PyTree, axis_names, alive, *, link_up=None):
        """``apply_masked`` on per-node values inside ``shard_map``.

        Dropped edges still traverse the wire (the permute schedule is
        compiled); their weight is zeroed and renormalized onto self at the
        receiver — the transient-fault trade: no recompile, dead-edge bytes
        still move.  Permanent crashes use ``degrade`` to actually remove
        the sends.  Non-permute programs fall back to all-gather + a
        runtime row of the degraded matrix.
        """
        n = self.n
        idx = _flat_axis_index(axis_names)
        masked = self._masked_tables(alive, link_up)
        if masked is None:
            wm = self._masked_matrix(alive, link_up)

            def _mix(x):
                xf = x.astype(jnp.float32)
                row = jax.lax.dynamic_slice_in_dim(wm, idx, 1, 0)[0]
                g = jax.lax.all_gather(xf, axis_names, axis=0, tiled=False)
                return jnp.einsum("g...,g->...", g, row).astype(x.dtype)

            return jax.tree.map(_mix, local)
        _, weights = masked
        wrow = weights[idx]

        def _mix(x):
            xf = x.astype(jnp.float32)
            acc = wrow[0] * xf
            for k, op in enumerate(self.ops):
                y = jax.lax.ppermute(xf, axis_names, list(op.perm))
                acc = acc + wrow[k + 1] * y
            return acc.astype(x.dtype)

        return jax.tree.map(_mix, local)

    @staticmethod
    def fuse(programs: Sequence["GossipProgram"], name: Optional[str] = None):
        """Compose H consecutive mixing steps into one program.

        The result applies ``programs[0]`` first, then ``programs[1]``, …
        (``matrix() == W_H ··· W_1``), and its interpreters run all rounds
        inside one jitted executable — H dispatches become one.  Nested
        fused programs flatten; a single program passes through unchanged.
        """
        stages: list[GossipProgram] = []
        for p in programs:
            if isinstance(p, FusedProgram):
                stages.extend(p.stages)
            else:
                stages.append(p)
        if not stages:
            raise ValueError("fuse needs at least one program")
        if len({p.n for p in stages}) > 1:
            raise ValueError("cannot fuse programs over different node counts")
        if len(stages) == 1:
            return stages[0]
        return FusedProgram(
            name=name or f"fuse[{'+'.join(p.name for p in stages)}]",
            n=stages[0].n,
            ops=tuple(op for p in stages for op in p.ops),
            self_weight=0.0,
            stages=tuple(stages),
        )

    # -- interpreters --------------------------------------------------------
    def apply(
        self,
        tree: PyTree,
        *,
        engine: str = "stacked",
        axis_names=None,
    ) -> PyTree:
        """Run one mixing step.

        engine:
          "dense"   — dense-matrix einsum over leading axis 0 (oracle).
          "stacked" — rolls/gathers over leading axis 0 (vmap engine).
          "shard"   — collectives on per-node values inside shard_map;
                      requires ``axis_names``.
        """
        if engine == "dense":
            return self.apply_dense(tree)
        if engine == "stacked":
            return self.apply_stacked(tree)
        if engine == "shard":
            if axis_names is None:
                raise ValueError("engine='shard' requires axis_names")
            return self.apply_shard(tree, axis_names)
        raise ValueError(f"unknown engine {engine!r}")

    def apply_dense(self, stacked: PyTree) -> PyTree:
        """θ ← W θ via the dense matrix (leading axis 0 = node axis)."""
        if self.is_identity and self.self_weight == 1.0:
            return stacked
        w = jnp.asarray(self.matrix(), jnp.float32)

        def _mix(x):
            return jnp.einsum("ij,j...->i...", w, x.astype(jnp.float32)).astype(
                x.dtype
            )

        return jax.tree.map(_mix, stacked)

    def apply_stacked(self, stacked: PyTree) -> PyTree:
        """Mixing over the stacked node axis via rolls / gathers."""
        if self.is_identity and self.self_weight == 1.0:
            return stacked
        n = self.n
        sw = jnp.asarray(_weight_column(self.self_weight, n), jnp.float32)

        def _col(v, ndim):
            return v.reshape((n,) + (1,) * (ndim - 1))

        def _mix(x):
            xf = x.astype(jnp.float32)
            acc = _col(sw, x.ndim) * xf
            for op in self.ops:
                if isinstance(op, PPermute):
                    wv = jnp.asarray(_weight_column(op.weight, n), jnp.float32)
                    if op.offset is not None:
                        # node i receives from (i + d) % n: roll by -d
                        acc = acc + _col(wv, x.ndim) * jnp.roll(
                            xf, -op.offset, axis=0
                        )
                    else:
                        src = np.full(n, 0, dtype=np.int32)
                        mask = np.zeros(n, dtype=np.float32)
                        for s, d in op.perm:
                            src[d] = s
                            mask[d] = 1.0
                        gathered = jnp.take(xf, jnp.asarray(src), axis=0)
                        acc = acc + _col(wv * jnp.asarray(mask), x.ndim) * gathered
                elif isinstance(op, AllReduce):
                    acc = acc + jnp.mean(xf, axis=0, keepdims=True)
                else:  # GatherRow
                    wm = jnp.asarray(op.w, jnp.float32)
                    acc = acc + jnp.einsum("ij,j...->i...", wm, xf)
            return acc.astype(x.dtype)

        return jax.tree.map(_mix, stacked)

    def apply_shard(self, local: PyTree, axis_names) -> PyTree:
        """Mixing on per-node values inside shard_map (one collective/op)."""
        if self.is_identity and self.self_weight == 1.0:
            return local
        n = self.n
        per_node_sw = isinstance(self.self_weight, tuple)
        per_node = per_node_sw or any(
            isinstance(op, PPermute) and isinstance(op.weight, tuple)
            for op in self.ops
        )
        idx = _flat_axis_index(axis_names) if per_node else None

        def _scalar_here(weight):
            if isinstance(weight, tuple):
                return jnp.asarray(weight, jnp.float32)[idx]
            return jnp.float32(weight)

        def _mix(x):
            xf = x.astype(jnp.float32)
            acc = _scalar_here(self.self_weight) * xf
            for op in self.ops:
                if isinstance(op, PPermute):
                    y = jax.lax.ppermute(xf, axis_names, list(op.perm))
                    acc = acc + _scalar_here(op.weight) * y
                elif isinstance(op, AllReduce):
                    acc = acc + jax.lax.pmean(xf, axis_names)
                else:  # GatherRow
                    wm = jnp.asarray(op.w, jnp.float32)
                    row = jax.lax.dynamic_slice_in_dim(
                        wm, _flat_axis_index(axis_names), 1, 0
                    )[0]
                    g = jax.lax.all_gather(xf, axis_names, axis=0, tiled=False)
                    acc = acc + jnp.einsum("g...,g->...", g, row)
            return acc.astype(x.dtype)

        return jax.tree.map(_mix, local)


@lru_cache(maxsize=512)
def _degrade_cached(program: GossipProgram, alive: tuple) -> GossipProgram:
    n = program.n
    dead = [i for i, a in enumerate(alive) if not a]
    name = f"{program.name}!dead[{','.join(map(str, dead))}]"
    if not all(isinstance(op, PPermute) for op in program.ops):
        # AllReduce / GatherRow programs: one dense row of the degraded W.
        return GossipProgram(
            name=name,
            n=n,
            ops=(GatherRow(_matrix_to_tuple(
                degraded_matrix(program.matrix(), alive)
            )),),
            self_weight=0.0,
        )
    self_w = _weight_column(program.self_weight, n).copy()
    ops = []
    for op in program.ops:
        wv = _weight_column(op.weight, n)
        perm, weight = [], np.zeros(n)
        for s, d in op.perm:
            if alive[s] and alive[d]:
                perm.append((s, d))
                weight[d] = wv[d]
            elif alive[d]:
                self_w[d] += wv[d]  # receiver renormalizes the lost edge
        if perm:
            ops.append(
                PPermute(tuple(perm), tuple(float(v) for v in weight))
            )
    for i in dead:
        self_w[i] = 1.0  # dead nodes self-average: params frozen
    return GossipProgram(
        name=name,
        n=n,
        ops=tuple(ops),
        self_weight=tuple(float(v) for v in self_w),
    )


@lru_cache(maxsize=512)
def _program_matrix(program: GossipProgram) -> np.ndarray:
    n = program.n
    w = np.diag(_weight_column(program.self_weight, n))
    for op in program.ops:
        if isinstance(op, PPermute):
            wv = _weight_column(op.weight, n)
            for s, d in op.perm:
                w[d, s] += wv[d]
        elif isinstance(op, AllReduce):
            w += np.ones((n, n)) / n
        else:  # GatherRow
            w += np.asarray(op.w, dtype=np.float64)
    return w


@dataclasses.dataclass(frozen=True)
class FusedProgram(GossipProgram):
    """H mixing rounds compiled into one executable (``GossipProgram.fuse``).

    Semantics are *sequential*: ``out = W_H ··· W_1 x`` where stage i
    realizes W_i.  ``ops`` holds the concatenated stage ops so collective
    counts and the comm-cost model sum naturally; the interpreters ignore
    it and fold over ``stages`` instead (one jit of an apply method runs
    every round in a single dispatch — that is the fusion win for
    time-varying one-peer schedules).
    """

    stages: tuple[GossipProgram, ...] = ()

    @property
    def cache_key(self):
        key = self.__dict__.get("_cache_key")
        if key is None:
            key = ("fused",) + tuple(p.cache_key for p in self.stages)
            object.__setattr__(self, "_cache_key", key)
        return key

    @property
    def is_identity(self) -> bool:
        return all(p.is_identity and p.self_weight == 1.0 for p in self.stages)

    def matrix(self) -> np.ndarray:
        w = np.eye(self.n)
        for p in self.stages:
            w = p.matrix() @ w
        return w

    def describe(self) -> str:
        inner = ", ".join(p.describe() for p in self.stages)
        return f"{self.name}(n={self.n}, stages=[{inner}])"

    def permute_tables(self):
        """Fused programs mix sequentially; the single-round kernel tables
        do not apply (each stage has its own — use ``stages[i]``)."""
        return None

    def degrade(self, alive) -> "GossipProgram":
        """Stage-wise degrade: each round renormalizes independently (NOT a
        mask of the product matrix — faults apply to every wire round)."""
        alive_t = tuple(bool(a) for a in np.asarray(alive).reshape(-1))
        if all(alive_t):
            return self
        return GossipProgram.fuse(
            [p.degrade(alive_t) for p in self.stages],
            name=f"{self.name}!dead[{','.join(str(i) for i, a in enumerate(alive_t) if not a)}]",
        )

    def apply_masked(self, tree, alive, *, link_up=None, engine="stacked"):
        for p in self.stages:
            tree = p.apply_masked(tree, alive, link_up=link_up, engine=engine)
        return tree

    def apply_shard_masked(self, local, axis_names, alive, *, link_up=None):
        for p in self.stages:
            local = p.apply_shard_masked(local, axis_names, alive, link_up=link_up)
        return local

    def apply_dense(self, stacked: PyTree) -> PyTree:
        """One einsum with the *product* matrix — the fused dense oracle."""
        if self.is_identity:
            return stacked
        w = jnp.asarray(self.matrix(), jnp.float32)

        def _mix(x):
            return jnp.einsum("ij,j...->i...", w, x.astype(jnp.float32)).astype(
                x.dtype
            )

        return jax.tree.map(_mix, stacked)

    def apply_stacked(self, stacked: PyTree) -> PyTree:
        for p in self.stages:
            stacked = p.apply_stacked(stacked)
        return stacked

    def apply_shard(self, local: PyTree, axis_names) -> PyTree:
        for p in self.stages:
            local = p.apply_shard(local, axis_names)
        return local


# ---------------------------------------------------------------------------
# Hub-balanced round scheduling
# ---------------------------------------------------------------------------

def hub_balanced_rounds(
    program: GossipProgram, rounds: int, name: Optional[str] = None
) -> GossipProgram:
    """Distribute a program's permute rounds across ``rounds`` fused steps.

    A static edge-colored program applies all C matchings every step, so a
    hot vertex (the star hub, degree Δ) sends Δ·P bytes per step even
    though the mean is ~2P.  This scheduler round-robins the C matchings
    over ``rounds`` stage programs — stage h applies matchings
    ``ops[h::rounds]`` and soaks the unapplied neighbor mass into its self
    weight, so every stage is row-stochastic (symmetric/doubly stochastic
    when the base is) and each matching runs exactly once per cycle.  The
    hub's *per-step peak* send volume drops from Δ·P to ⌈Δ/rounds⌉·P.

    The cycle's product matrix is not W^rounds — it is a time-varying
    schedule over the same edge set (each edge averaged once per cycle at
    its base weight), trading per-cycle mixing strength for a ``rounds``×
    lower peak link load.  Mean preservation and consensus contraction are
    kept (pinned by tests); use via ``mix_rounds`` + ``hub_balance`` on the
    engines.
    """
    rounds = int(rounds)
    if rounds <= 1:
        return program
    if not all(isinstance(op, PPermute) for op in program.ops):
        raise ValueError(
            f"hub_balanced_rounds needs an all-PPermute program, got "
            f"{program.describe()}"
        )
    if len(program.ops) <= 1:
        return program
    n = program.n
    base_self = _weight_column(program.self_weight, n)
    cols = [_weight_column(op.weight, n) for op in program.ops]
    # receiver-side mass per op: only perm-participating dsts carry weight
    masks = []
    for op in program.ops:
        m = np.zeros(n)
        for _, d in op.perm:
            m[d] = 1.0
        masks.append(m)
    stages = []
    for h in range(rounds):
        picked = list(range(h, len(program.ops), rounds))
        sw = base_self.copy()
        for k, (wv, m) in enumerate(zip(cols, masks)):
            if k not in picked:
                sw += wv * m  # unapplied matchings self-average this step
        stages.append(
            GossipProgram(
                name=f"{program.name}@round{h}",
                n=n,
                ops=tuple(program.ops[k] for k in picked),
                self_weight=tuple(float(v) for v in sw),
            )
        )
    return GossipProgram.fuse(
        stages, name=name or f"hub_balanced[{program.name}/H{rounds}]"
    )


def maybe_hub_balanced(progs: Sequence[GossipProgram], rounds: int):
    """The shared eligibility rule for hub-balancing a fused gossip round.

    Reschedules ONLY when the ``rounds`` fused steps are one *static*
    multi-matching permute program repeated — time-varying families keep
    their own rotation, single-matching and non-permute programs have
    nothing to rotate.  Both the Topology and the SPMD trainer route
    through this helper so the engines always hub-balance the same
    programs (their shared-schedule invariant).  Returns the rescheduled
    program, or ``None`` when plain fusion should apply.
    """
    if (
        rounds > 1
        and len({p.cache_key for p in progs}) == 1
        and progs[0].permute_tables() is not None
        and len(progs[0].ops) > 1
    ):
        return hub_balanced_rounds(progs[0], rounds)
    return None


# ---------------------------------------------------------------------------
# Edge coloring: decompose an arbitrary edge set into <= Δ+1 matchings
# ---------------------------------------------------------------------------

def _greedy_coloring(n: int, edges, ncolors: int):
    """Smallest-free-color greedy pass.  O(E·Δ); may need up to 2Δ-1 colors,
    but is exact (Δ or Δ+1) on stars, matchings, paths and most sparse
    graphs — the hot compile path.  Returns None when it exceeds ncolors."""
    used = [set() for _ in range(n)]
    color: dict[tuple[int, int], int] = {}
    for i, j in edges:
        taken = used[i] | used[j]
        c = next((c for c in range(ncolors) if c not in taken), None)
        if c is None:
            return None
        color[(i, j)] = c
        used[i].add(c)
        used[j].add(c)
    return color


def _misra_gries_coloring(n: int, edges, ncolors: int):
    """Misra & Gries (1992) constructive Vizing coloring: always <= Δ+1
    colors on a simple graph.  O(E·Δ²) worst case — only invoked when the
    greedy pass overflows, which small irregular graphs occasionally do."""
    adj = [dict() for _ in range(n)]   # adj[u][v] = color of edge (u, v)
    # color -> multiplicity at each node: a plain set would corrupt during
    # path inversion / fan rotation, where a color transiently sits on two
    # edges of one node and a set-discard would lose the surviving copy
    used = [dict() for _ in range(n)]

    def _add(u, c):
        used[u][c] = used[u].get(c, 0) + 1

    def _rm(u, c):
        k = used[u][c] - 1
        if k:
            used[u][c] = k
        else:
            del used[u][c]

    def free(u):
        return next(c for c in range(ncolors) if c not in used[u])

    def set_color(u, v, c):
        adj[u][v] = c
        adj[v][u] = c
        _add(u, c)
        _add(v, c)

    def unset(u, v):
        c = adj[u].pop(v)
        adj[v].pop(u)
        _rm(u, c)
        _rm(v, c)

    def invert_cd_path(u, c, d):
        """Flip colors along the maximal c/d-alternating path through u."""
        prev, cur, want = None, u, d
        while True:
            nxt = next(
                (w for w, cc in adj[cur].items() if cc == want and w != prev),
                None,
            )
            if nxt is None:
                return
            unset(cur, nxt)
            set_color(cur, nxt, c if want == d else d)
            prev, cur = cur, nxt
            want = c if want == d else d

    for u, v in edges:
        # maximal fan of u: F[0] = v; color(u, F[i]) is free on F[i-1]
        fan, in_fan = [v], {v}
        grown = True
        while grown:
            grown = False
            for w, c in adj[u].items():
                if w not in in_fan and c not in used[fan[-1]]:
                    fan.append(w)
                    in_fan.add(w)
                    grown = True
                    break
        c, d = free(u), free(fan[-1])
        invert_cd_path(u, c, d)
        # the inversion may shrink the usable fan: take the shortest prefix
        # that is still a fan and whose tip has d free, then rotate it
        w_idx = None
        for i, w in enumerate(fan):
            if i > 0 and adj[u][fan[i]] in used[fan[i - 1]]:
                break
            if d not in used[w]:
                w_idx = i
                break
        if w_idx is None:  # pragma: no cover - MG invariant guarantees a w
            return None
        # rotate fan[0..w_idx]: (u, F[i]) takes the color of (u, F[i+1]);
        # unset every involved edge first so multiplicities stay exact
        old = [adj[u].get(fan[i]) for i in range(w_idx + 1)]
        for i in range(w_idx + 1):
            if fan[i] in adj[u]:
                unset(u, fan[i])
        for i in range(w_idx):
            set_color(u, fan[i], old[i + 1])
        set_color(u, fan[w_idx], d)

    return {(i, j): adj[i][j] for i, j in edges}


def edge_coloring(
    n: int, edges: Sequence[tuple[int, int]]
) -> list[list[tuple[int, int]]]:
    """Partition an undirected edge set into <= Δ+1 matchings.

    Greedy first (covers stars/matchings/sparse graphs with Δ or Δ+1 colors
    in O(E·Δ)); when greedy overflows the Δ+1 palette, the Misra–Gries
    constructive Vizing pass guarantees Δ+1.  Every returned color class is
    a matching; together they cover each edge exactly once, so a mixing
    matrix W decomposes exactly into one per-node-weighted PPermute per
    class plus its diagonal.
    """
    edges = [tuple(sorted(e)) for e in edges]
    if not edges:
        return []
    deg = [0] * n
    for i, j in edges:
        deg[i] += 1
        deg[j] += 1
    ncolors = max(deg) + 1
    color = _greedy_coloring(n, edges, ncolors)
    if color is None:
        color = _misra_gries_coloring(n, edges, ncolors)
    if color is None:  # pragma: no cover - MG always succeeds on simple graphs
        color = _greedy_coloring(n, edges, 2 * max(deg))
    classes: dict[int, list[tuple[int, int]]] = {}
    for e, c in color.items():
        classes.setdefault(c, []).append(e)
    return [sorted(classes[c]) for c in sorted(classes)]


# ---------------------------------------------------------------------------
# Compilation
# ---------------------------------------------------------------------------

def identity_program(n: int, name: str = "identity") -> GossipProgram:
    return GossipProgram(name=name, n=n, ops=(), self_weight=1.0)


def _matrix_to_tuple(w: np.ndarray) -> tuple[tuple[float, ...], ...]:
    return tuple(tuple(float(v) for v in row) for row in np.asarray(w))


@lru_cache(maxsize=512)
def dense_program(graph) -> GossipProgram:
    """The paper-faithful dense realization: one GatherRow of the full W.

    Costs an all-gather of the parameter tree — kept as the faithful
    baseline (``mixing="dense"``); ``compile_graph`` is the optimized path.
    Cached: callers look this up every training step, and building the
    n×n tuple (plus the cache_key digest) is O(n²) host work.
    """
    w = graph.mixing_matrix()
    return GossipProgram(
        name=f"dense:{graph.name}",
        n=graph.n,
        ops=(GatherRow(_matrix_to_tuple(w)),),
        self_weight=0.0,
    )


def compile_graph(graph_or_sequence):
    """Compile a graph (or a sequence of graphs) into GossipProgram(s).

    A single ``CommGraph`` yields one program; a sequence (time-varying
    topology: one graph per step/phase) yields a tuple of programs, one per
    element — the rotation schedule the engines iterate through.
    """
    if isinstance(graph_or_sequence, (list, tuple)):
        return tuple(_compile_one(g) for g in graph_or_sequence)
    return _compile_one(graph_or_sequence)


@lru_cache(maxsize=512)
def _compile_one(graph) -> GossipProgram:
    # Local import: graphs.py ↔ schedule.py would otherwise cycle.
    from repro.core.graphs import CirculantGraph, EdgeGraph

    n = graph.n
    if graph.degree == 0 or n <= 1:
        return identity_program(n, name=graph.name)

    if isinstance(graph, CirculantGraph):
        if graph.name == "complete" and graph.degree == n - 1:
            # Uniform complete graph: W = J/n == one ring all-reduce.
            return GossipProgram(
                name=graph.name, n=n, ops=(AllReduce(),), self_weight=0.0
            )
        ops = tuple(
            PPermute(permutation_for_offset(n, d), wd, offset=d)
            for d, wd in graph.weighted_offsets()
        )
        return GossipProgram(
            name=graph.name, n=n, ops=ops, self_weight=graph.self_weight
        )

    if isinstance(graph, EdgeGraph):
        w = graph.mixing_matrix()
        # Edge-colored sparse decomposition: <= Δ+1 per-node-weighted
        # permute rounds (matchings are the 1-color special case).  Every
        # off-diagonal W entry lands in exactly one matching, the diagonal
        # rides in self_weight — exact for any symmetric weight scheme.
        ops = []
        for matching in edge_coloring(n, graph.edges):
            perm = []
            weight = np.zeros(n)
            for i, j in matching:
                perm += [(i, j), (j, i)]
                weight[j] = w[j, i]
                weight[i] = w[i, j]
            ops.append(
                PPermute(
                    tuple(sorted(perm, key=lambda p: p[1])),
                    tuple(float(v) for v in weight),
                )
            )
        program = GossipProgram(
            name=graph.name,
            n=n,
            ops=tuple(ops),
            self_weight=tuple(float(v) for v in np.diag(w)),
        )
        if np.allclose(program.matrix(), w, rtol=0.0, atol=1e-12):
            return program
        # Exactness check failed (cannot happen for a proper coloring of a
        # simple graph; kept as the safety net): dense fallback.
        return GossipProgram(  # pragma: no cover
            name=graph.name,
            n=n,
            ops=(GatherRow(_matrix_to_tuple(w)),),
            self_weight=0.0,
        )

    raise TypeError(f"cannot compile {type(graph).__name__} into a GossipProgram")


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------

def _live_pairs(op: PPermute, n: int, alive=None, link_up=None):
    """The (src, dst) pairs that actually move bytes under this permute.

    A pair moves nothing when its receiver weight is zero (a degraded
    program keeps renormalized zero entries out of ``perm``, but masked /
    hand-built programs may carry them) or when a fault mask kills either
    endpoint or the link — dead edges must not be billed (at high fault
    rates they dominate a naive ``len(perm)`` count).
    """
    wv = _weight_column(op.weight, n)
    pairs = []
    for s, d in op.perm:
        if wv[d] == 0.0:
            continue
        if alive is not None and not (alive[s] and alive[d]):
            continue
        if link_up is not None and not link_up[s][d]:
            continue
        pairs.append((s, d))
    return pairs


def program_comm_bytes(
    program: GossipProgram, param_bytes: int, *, alive=None, link_up=None
) -> int:
    """Mean bytes each node sends per mixing step under this program.

    A partial permute (an edge-colored matching round) only moves buffers
    on its participating source→dest links, so it costs ``P · pairs/n``
    per node on average — an edge-colored star totals ~2P per node versus
    the (n-1)·P ring all-gather of ``GatherRow``.  ``alive`` / ``link_up``
    bill a fault realization by its surviving edges only (the ``GatherRow``
    all-gather still moves every replica regardless of masks).
    """
    total = 0.0
    n = program.n
    alive_l = None if alive is None else [bool(a) for a in np.asarray(alive)]
    link_l = None if link_up is None else np.asarray(link_up).tolist()
    for op in program.ops:
        if isinstance(op, PPermute):
            total += param_bytes * (len(_live_pairs(op, n, alive_l, link_l)) / n)
        elif isinstance(op, AllReduce):
            total += 2 * param_bytes * (n - 1) / n
        else:  # GatherRow: ring all-gather — each node forwards P to n-1 peers
            total += param_bytes * (n - 1)
    return int(total)


def program_max_node_bytes(
    program: GossipProgram, param_bytes: int, *, alive=None, link_up=None
) -> int:
    """Bytes the busiest node sends per mixing step (the latency-critical
    figure: a star hub participates in every matching round, so its send
    volume is Δ·P even though the mean is ~2P — ``hub_balanced_rounds``
    exists to cap exactly this number)."""
    n = program.n
    sends = np.zeros(n)
    alive_l = None if alive is None else [bool(a) for a in np.asarray(alive)]
    link_l = None if link_up is None else np.asarray(link_up).tolist()
    for op in program.ops:
        if isinstance(op, PPermute):
            for s, _ in _live_pairs(op, n, alive_l, link_l):
                sends[s] += param_bytes
        elif isinstance(op, AllReduce):
            sends += 2 * param_bytes * (n - 1) / n
        else:  # GatherRow
            sends += param_bytes * (n - 1)
    return int(sends.max()) if n else 0
