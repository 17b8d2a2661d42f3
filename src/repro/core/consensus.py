"""Consensus distance + the closed-loop Ada controller (arXiv:2102.04828).

Consensus Control for Decentralized Deep Learning shows the right control
signal for adapting decentralized training is the *consensus distance*

    Ξ_t = sqrt( 1/n · Σ_i ‖x_i - x̄‖² ),    x̄ = 1/n Σ_i x_i,

the RMS disagreement between replicas and their average.  This module
computes it on-device and uses it to close Ada's scheduling loop.  Each
probe reduces the whole parameter tree to one scalar per node (mirroring
``dbench.param_l2_norms``), but computing x̄ itself costs one pmean of the
parameter tree — O(P) on the wire per probe, about one one-peer gossip
step — so probes are *not* free: ``probe_every`` sets the cadence, and the
comm accounting in ``benchmarks/ada.py`` bills them.

On-device realizations (both engines):

  * ``consensus_sq_stacked`` / ``consensus_distance_stacked`` — for trees
    whose leaves carry a leading (n, ...) node axis (the simulator state and
    the SPMD trainer's gossip-stacked global state).  One mean over the node
    axis per leaf, then a per-node squared-distance reduction.
  * ``consensus_sq_shard`` / ``consensus_distance_shard`` — for per-node
    values inside ``shard_map``: ``pmean`` produces x̄, a local reduction
    produces ‖x_i - x̄‖², and a second ``pmean`` averages it over nodes.

``ConsensusController`` replaces Ada's open-loop time law
``k(epoch) = k0 - int(γ·epoch)`` (and the hard-coded k<2 one-peer handoff)
with a measured trigger: every time the probed ratio ``Ξ_t / Ξ_0`` falls to
the ``target``, the schedule steps down one rung of the pre-enumerated
ladder ``k0, k0-1, …, 2[, one_peer]``.  The paper's Observation 5 (high
connectivity helps early, sparse graphs are free later) becomes a
measurement: the graph sparsifies exactly when the replicas agree tightly
enough to afford it.

The bounded-executable-set invariant is preserved by construction: the
controller only ever *selects among* the ladder's rungs, and every rung's
mixing programs are enumerable up front (``Topology.distinct_programs``
pins each rung in turn), so closed-loop graph adaptation still costs zero
mid-run recompiles.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ada import AdaSchedule
from repro.core.graphs import (
    CommGraph, RingLattice, one_peer_exponential, one_peer_period,
)
from repro.telemetry import profile

PyTree = Any

__all__ = [
    "consensus_sq_stacked",
    "consensus_distance_stacked",
    "consensus_distance_jit",
    "consensus_distance_masked",
    "consensus_distance_masked_jit",
    "consensus_sq_shard",
    "consensus_distance_shard",
    "ConsensusController",
]


# ---------------------------------------------------------------------------
# On-device consensus distance (jit-able)
# ---------------------------------------------------------------------------

def consensus_sq_stacked(stacked: PyTree) -> jax.Array:
    """Per-node squared consensus distance ‖x_i - x̄‖² — returns (n,) float32.

    ``stacked``: a pytree whose leaves carry a leading node axis (n, ...) —
    the simulator state and the SPMD trainer's gossip-stacked global state.
    Accumulates in float32 across every leaf (the full parameter vector).
    """
    leaves = jax.tree.leaves(stacked)
    if not leaves:
        raise ValueError("consensus distance of an empty pytree")
    total = None
    for x in leaves:
        xf = x.astype(jnp.float32)
        d = xf - xf.mean(axis=0, keepdims=True)
        sq = jnp.sum(jnp.square(d), axis=tuple(range(1, d.ndim)))
        total = sq if total is None else total + sq
    return total


@profile.scope("probe")
def consensus_distance_stacked(stacked: PyTree) -> jax.Array:
    """Ξ = sqrt(1/n Σ_i ‖x_i - x̄‖²) over the leading node axis (scalar)."""
    return jnp.sqrt(jnp.mean(consensus_sq_stacked(stacked)))


# The probe both engines call every `probe_every` steps: one shared jitted
# entry point (jax caches traces per shape), so neither engine carries its
# own lazy-init state.
consensus_distance_jit = jax.jit(consensus_distance_stacked)


@profile.scope("probe")
def consensus_distance_masked(stacked: PyTree, alive) -> jax.Array:
    """Ξ over the *alive* nodes only: sqrt(1/|A| Σ_{i∈A} ‖x_i - x̄_A‖²).

    Under faults a dead node's frozen replica is not part of the training
    population; including it would hold Ξ artificially high and freeze the
    controller's ladder.  ``alive`` is a runtime (n,) mask, so one
    executable serves every realization (shape-keyed jit like the unmasked
    probe).  With every node alive this equals ``consensus_distance_stacked``.
    """
    leaves = jax.tree.leaves(stacked)
    if not leaves:
        raise ValueError("consensus distance of an empty pytree")
    af = jnp.asarray(alive, jnp.float32)
    count = jnp.maximum(jnp.sum(af), 1.0)
    total = None
    for x in leaves:
        xf = x.astype(jnp.float32)
        acol = af.reshape((af.shape[0],) + (1,) * (xf.ndim - 1))
        mean = jnp.sum(xf * acol, axis=0, keepdims=True) / count
        d = (xf - mean) * acol
        sq = jnp.sum(jnp.square(d), axis=tuple(range(1, d.ndim)))
        total = sq if total is None else total + sq
    return jnp.sqrt(jnp.sum(total) / count)


consensus_distance_masked_jit = jax.jit(consensus_distance_masked)


def consensus_sq_shard(local: PyTree, axis_names) -> jax.Array:
    """This node's ‖x_i - x̄‖² inside ``shard_map`` (one pmean; scalar)."""
    leaves = jax.tree.leaves(local)
    if not leaves:
        raise ValueError("consensus distance of an empty pytree")
    total = jnp.zeros((), jnp.float32)
    for x in leaves:
        xf = x.astype(jnp.float32)
        mean = jax.lax.pmean(xf, axis_names)
        total = total + jnp.sum(jnp.square(xf - mean))
    return total


@profile.scope("probe")
def consensus_distance_shard(local: PyTree, axis_names) -> jax.Array:
    """Ξ inside ``shard_map``: the same scalar on every node (two pmeans)."""
    return jnp.sqrt(
        jax.lax.pmean(consensus_sq_shard(local, axis_names), axis_names)
    )


# ---------------------------------------------------------------------------
# The closed-loop controller
# ---------------------------------------------------------------------------

Rung = Union[int, str]  # a coordination number, or the terminal "one_peer"


@dataclasses.dataclass(eq=False)
class ConsensusController:
    """Consensus-distance-triggered Ada scheduling (closed loop).

    Wraps an ``AdaSchedule`` and replaces its time law with a measured
    trigger.  The controller walks a fixed ladder of rungs

        k0, k0-1, …, floor[, "one_peer"]

    (``floor`` = the schedule's integer ``k_floor``, 2 in the paper;
    ``"one_peer"`` appended when ``k_floor == "one_peer"``; graph-identical
    k's — RingLattice uses k//2 hops, so odd k equals k-1 — collapse to one
    rung so every transition actually sparsifies).  Each probe calls
    ``observe(Ξ_t, step)``:

      * Ξ_0 is the *phase reference*: the peak consensus distance observed
        on the current rung (replicas start identical, so zero probes are
        skipped; early probes rise while momentum spins up and the peak
        tracks them — 2102.04828 likewise re-anchors its reference per
        phase);
      * whenever Ξ_t ≤ target · Ξ_0 the schedule steps down exactly one
        rung and the reference re-arms on the sparser graph (sparsifying
        raises Ξ back up — the loop self-regulates), and the one-peer
        handoff happens when — and only when — the measured ratio crosses
        the target on the last lattice rung, not at the open-loop ``k < 2``
        constant.

    By default the rung walk is monotone (never re-densifies).  Passing
    ``spike`` (a ratio > 1) makes the ladder NON-monotone: a measured Ξ_t
    at or above ``spike`` × the phase's running peak — a crash, a deadline
    storm, a join landing — walks the ladder back UP one rung to a denser
    graph (logged as a ``"redensify"`` event and a transition), because a
    disagreement spike is exactly when the run needs MORE connectivity,
    not the sparser graph the stale monotone walk would keep.  The spike
    reference survives ``rearm`` (a membership event clears the trigger
    reference Ξ_0 *before* the spiked probe arrives — the spike must still
    compare against the pre-fault level); after a re-densify the phase
    re-seeds at the spiked level, so a single event moves at most one rung
    and the loop cannot thrash.  Either way the walk is bounded by the
    ladder, so the executable set an engine needs is exactly the ladder's
    programs — ``Topology.distinct_programs`` enumerates them by pinning
    each rung in turn (``pinned``), and engines cache one executable per
    program as for open-loop Ada: re-densification only ever *re-selects*
    an already-enumerated denser rung.

    Mutable by design (training-run state); ``reset()`` re-arms it for a
    fresh run, ``rung_at(step)`` replays the realized schedule afterwards
    (the comm-volume accounting in ``benchmarks/ada.py`` uses this).
    """

    schedule: AdaSchedule
    target: float = 0.5      # trigger ratio Ξ_t / Ξ_0 (2102.04828's fraction)
    probe_every: int = 1     # probe cadence in raw training steps
    spike: Optional[float] = None  # Ξ_t / peak ratio that re-densifies (>1)

    # -- run state (mutated by observe) -------------------------------------
    xi0: Optional[float] = None
    rung: int = 0
    transitions: list = dataclasses.field(default_factory=list)  # [(step, rung)]
    trace: list = dataclasses.field(default_factory=list)  # [(step, xi, rung)]
    events: list = dataclasses.field(default_factory=list)  # [(step, reason)]

    def __post_init__(self):
        if not (0.0 < self.target < 1.0):
            raise ValueError(f"target must be in (0, 1), got {self.target}")
        if self.spike is not None and not float(self.spike) > 1.0:
            raise ValueError(
                f"spike is a re-densify ratio and must be > 1, got {self.spike}"
            )
        self.probe_every = max(int(self.probe_every), 1)
        # the re-densify reference: the current phase's peak Ξ, persisted
        # through rearm() (unlike xi0) so a membership event cannot hide
        # the very spike it causes from the spike trigger
        self._spike_ref: Optional[float] = None
        # run-telemetry recorder (engines bind theirs): transitions and
        # rearm/redensify reasons route through it so both engines share
        # one event stream with identical coalescing semantics
        self._recorder = None
        n = self.schedule.n_nodes
        floor = (
            2
            if self.schedule.k_floor == "one_peer"
            else max(int(self.schedule.k_floor), 2)
        )
        start = int(np.clip(self.schedule.k0, floor, max(n - 1, floor)))
        # Dedup graph-identical rungs: RingLattice uses k//2 hops per side,
        # so k and k-1 compile to the SAME graph for odd k.  Keeping both
        # would waste a full trigger crossing (and a duplicate executable)
        # on a transition that changes nothing — keep one rung per distinct
        # graph, labeled by the sparser k (honoring the floor).
        ladder: list[Rung] = []
        prev_sig = None
        for k in range(start, floor - 1, -1):
            g = RingLattice(n, k)
            sig = (g.offsets, g.mult)
            if ladder and sig == prev_sig:
                ladder[-1] = k
            else:
                ladder.append(k)
            prev_sig = sig
        if self.schedule.k_floor == "one_peer":
            ladder.append("one_peer")
        self._ladder: tuple[Rung, ...] = tuple(ladder)

    # -- the ladder ----------------------------------------------------------
    @property
    def ladder(self) -> tuple[Rung, ...]:
        """The pre-enumerated rungs the controller may select among."""
        return self._ladder

    @property
    def current(self) -> Rung:
        return self._ladder[self.rung]

    @property
    def one_peer_active(self) -> bool:
        return self.current == "one_peer"

    @property
    def handoff_step(self) -> Optional[int]:
        """Step at which the one-peer handoff fired (None before it does)."""
        for step, rung in self.transitions:
            if self._ladder[rung] == "one_peer":
                return step
        return None

    # -- probing -------------------------------------------------------------
    def should_probe(self, step: int) -> bool:
        return step % self.probe_every == 0

    def observe(self, xi: float, step: int) -> bool:
        """Feed one measured Ξ_t; returns True iff the schedule stepped down.

        Ξ_0 is the running peak of the current phase: the first
        strictly-positive finite observation (after init or after a
        transition) seeds it, later larger observations raise it.  A
        transition fires iff ``xi <= target * Ξ_0`` with a sparser rung
        available; firing re-arms the reference for the new phase.  At most
        one rung step per observation.

        With ``spike`` set the walk is non-monotone: before anything else,
        ``xi >= spike * peak`` (the phase peak persisted through ``rearm``)
        with a denser rung available walks the ladder UP one rung, logs a
        ``"redensify"`` event, and re-seeds the phase at the spiked level
        — so the same event cannot fire twice, and once Ξ recovers below
        ``target`` × the spiked reference the normal trigger re-sparsifies
        (the loop heals the spike, then resumes the walk).
        """
        xi = float(xi)
        if (
            self.spike is not None
            and self.rung > 0
            and math.isfinite(xi)
            and self._spike_ref is not None
            and xi >= float(self.spike) * self._spike_ref
        ):
            self.rung -= 1
            self.transitions.append((int(step), self.rung))
            self._emit_transition(step)
            self._log_event(step, "redensify")
            # re-seed the phase on the denser rung at the spiked level:
            # both references restart, so this spike is consumed
            self.xi0 = None
            self._spike_ref = None
            self.trace.append((int(step), xi, self.rung))
            return False
        if xi > 0.0 and math.isfinite(xi):
            self._spike_ref = (
                xi if self._spike_ref is None else max(self._spike_ref, xi)
            )
        if self.xi0 is None:
            if xi > 0.0 and math.isfinite(xi):
                self.xi0 = xi
            self.trace.append((int(step), xi, self.rung))
            return False
        if math.isfinite(xi):
            self.xi0 = max(self.xi0, xi)
        fired = (
            math.isfinite(xi)
            and xi <= self.target * self.xi0
            and self.rung < len(self._ladder) - 1
        )
        if fired:
            self.rung += 1
            self.transitions.append((int(step), self.rung))
            self._emit_transition(step)
            self.xi0 = None  # re-arm the phase reference on the new rung
            self._spike_ref = None  # sparser graphs run hotter: new baseline
        self.trace.append((int(step), xi, self.rung))
        return fired

    def rearm(self, step: int, reason: str = "fault") -> None:
        """Re-arm the per-phase peak Ξ_0 on a membership event.

        A crash or rejoin spikes the measured consensus distance (a dead
        node's replica freezes; a rejoining node re-enters off-average).
        Without re-arming, the stale pre-fault Ξ_0 makes the post-fault
        ratio Ξ_t/Ξ_0 look tighter than it is and ratchets the ladder down
        exactly when the run needs MORE connectivity.  Re-arming keeps the
        rung and restarts the phase reference: the next probes re-seed and
        peak-track Ξ_0 on the degraded membership, so the trigger compares
        like with like.  Recorded in ``events`` for replay/diagnostics.

        Simultaneous membership events in ONE step — a k-node concurrent
        crash, a departure landing on a join — coalesce into a single
        re-arm and a single log entry: re-arming is idempotent within a
        step (Ξ_0 is already cleared), and k duplicate entries would make
        the event log overstate distinct membership phases k-fold.
        Distinct same-step reasons merge into one ``"a+b"`` entry.

        The spike reference deliberately SURVIVES re-arming: the membership
        event fires before the spiked probe it causes, so clearing it here
        would blind the ``spike`` re-densify trigger to exactly the spikes
        it exists for.
        """
        self.xi0 = None
        self._log_event(step, reason)

    def bind_recorder(self, recorder) -> None:
        """Attach the run's :class:`repro.telemetry.MetricsRecorder`: every
        transition/rearm/redensify log entry is mirrored as a telemetry
        event.  Both engines bind at construction (and the simulator
        re-binds after an elastic ``_admit`` rebuilds the controller), so
        the event stream — coalescing included — is engine-independent."""
        self._recorder = recorder

    def _emit_transition(self, step: int) -> None:
        if self._recorder is not None:
            self._recorder.event(
                "transition", int(step),
                data={"rung": int(self.rung), "k": str(self.current)},
            )

    def _log_event(self, step: int, reason: str) -> None:
        """Append to ``events``, coalescing same-step reasons into "a+b".

        The merge itself is the shared implementation in
        ``repro.telemetry.coalesce_into``; when the coalesced entry
        changes, the merged reason is re-emitted as a ``controller``
        telemetry event (consumers keep the last emission per step)."""
        from repro.telemetry import coalesce_into

        merged = coalesce_into(self.events, int(step), str(reason))
        if merged is not None and self._recorder is not None:
            self._recorder.event(
                "controller", int(step), data={"reason": merged}
            )

    # -- resume / adoption ----------------------------------------------------
    def state_dict(self) -> dict:
        """JSON-serializable run state (for crash-consistent resume)."""
        return {
            "xi0": self.xi0,
            "spike_ref": self._spike_ref,
            "rung": int(self.rung),
            "transitions": [[int(s), int(r)] for s, r in self.transitions],
            "trace": [[int(s), float(x), int(r)] for s, x, r in self.trace],
            "events": [[int(s), str(r)] for s, r in self.events],
        }

    def load_state_dict(self, d: dict) -> None:
        """Restore ``state_dict`` output — resumed runs continue the same
        phase reference, rung walk, and logs as the uninterrupted run."""
        self.xi0 = None if d.get("xi0") is None else float(d["xi0"])
        self._spike_ref = (
            None if d.get("spike_ref") is None else float(d["spike_ref"])
        )
        self.rung = min(int(d["rung"]), len(self._ladder) - 1)
        self.transitions[:] = [(int(s), int(r)) for s, r in d["transitions"]]
        self.trace[:] = [
            (int(s), float(x), int(r)) for s, x, r in d["trace"]
        ]
        self.events[:] = [(int(s), str(r)) for s, r in d["events"]]

    def adopt(self, other: "ConsensusController") -> None:
        """Continue another controller's run state on THIS ladder.

        Used at an elastic join: the topology re-derives its graph family
        at the new n, which rebuilds the controller with a new ladder; the
        fresh instance adopts the old run state (rung clamped to the new
        ladder, history carried over) so the schedule position and logs
        survive the membership change.  The caller's next
        ``track_membership`` re-arms the phase reference for the grown
        population.
        """
        self.load_state_dict(other.state_dict())

    def reset(self) -> None:
        """Re-arm for a fresh run (clears Ξ_0, rung, and the trace)."""
        self.xi0 = None
        self._spike_ref = None
        self.rung = 0
        self.transitions.clear()
        self.trace.clear()
        self.events.clear()

    # -- schedule interface (what Topology delegates to) ----------------------
    def graph_at(self, epoch: int = 0, step: int = 0) -> CommGraph:
        """The graph the *current* rung selects (epoch is ignored: the
        measured signal, not wall-clock epochs, drives the schedule)."""
        cur = self.current
        if cur == "one_peer":
            return one_peer_exponential(self.schedule.n_nodes, step)
        return RingLattice(self.schedule.n_nodes, int(cur))

    def period_steps(self) -> int:
        """Steps before the current rung's graph repeats (1 = static)."""
        if self.one_peer_active:
            return one_peer_period(self.schedule.n_nodes)
        return 1

    @contextlib.contextmanager
    def pinned(self, rung: int):
        """Temporarily force a rung — used to enumerate the bounded program
        set (``Topology.distinct_programs``) and to replay a recorded run
        for comm accounting, without disturbing the live run state."""
        if not 0 <= rung < len(self._ladder):
            raise ValueError(f"rung {rung} outside ladder of {len(self._ladder)}")
        old = self.rung
        self.rung = rung
        try:
            yield self
        finally:
            self.rung = old

    def rung_at(self, step: int) -> int:
        """The rung in force at ``step``, replayed from the transition log
        (a transition observed at step s governs step s onward)."""
        rung = 0
        for s, r in self.transitions:
            if s <= step:
                rung = r
            else:
                break
        return rung

    def describe(self) -> str:
        ks = ",".join(str(r) for r in self._ladder)
        sp = "" if self.spike is None else f", spike={self.spike}"
        return (
            f"ConsensusController(target={self.target}, "
            f"probe_every={self.probe_every}{sp}, ladder=[{ks}])"
        )
