"""Paper-faithful multi-node simulator (the DBench engine).

Simulates an n-node (de)centralized data-parallel run on any number of real
devices by carrying a leading *node axis* on every state leaf and vmapping
the per-node computation.  Mixing interprets the same compiled
``GossipProgram`` as the SPMD engine — with the dense-matrix interpreter
(the literal equation of the paper, §2.2) by default, so this engine is the
correctness oracle for the production engine.

One simulator step:
  1. per-node forward/backward on that node's batch shard   (vmap)
  2. centralized  : all-reduce gradients, identical update everywhere
     decentralized: local optimizer update, then θ ← W θ  (mix_order="post")
  3. optional DBench probe: per-node, per-leaf L2 norms *before* mixing

Time-varying topologies (one-peer exponential, random-matching pools, Ada
with ``k_floor="one_peer"``) are step-granular: the step function is cached
per compiled program, so a run compiles each member of a small bounded set
(``Topology.distinct_programs``) once at first use and never recompiles.

Closed-loop Ada (``Topology.controller``): before a probe step the engine
computes the consensus distance Ξ_t on-device (one jitted stacked
reduction, ``core/consensus.py``) and feeds it to the controller, which may
step the schedule down one rung.  The controller only selects among the
pre-enumerated ladder programs, so the cached-executable bound holds
unchanged.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import validate_run_config as _validate_run_config
from repro.core import dbench
from repro.core.dsgd import Topology
from repro.core.faults import (
    admit_node, adopt_neighbor_average, drain_handoff, realization_arrays,
    rejoin_neighbors, track_membership,
)
from repro.core.schedule import GossipProgram
from repro.optim.sgd import Optimizer

PyTree = Any

__all__ = ["SimState", "DecentralizedSimulator"]

_ENGINES = {"dense": "dense", "shift": "stacked", "stacked": "stacked"}


@dataclasses.dataclass
class SimState:
    params: PyTree      # leaves (n_nodes, ...)
    opt_state: PyTree   # leaves (n_nodes, ...)
    step: int = 0

    def node_params(self, i: int) -> PyTree:
        return jax.tree.map(lambda x: x[i], self.params)

    def mean_params(self) -> PyTree:
        """The final model θ = average over all θ_i (paper §2.2)."""
        return jax.tree.map(lambda x: x.mean(axis=0), self.params)


class DecentralizedSimulator:
    """vmap-based engine for centralized/decentralized DNN training."""

    def __init__(
        self,
        loss_fn: Callable[..., jax.Array],
        optimizer: Optimizer,
        topology: Topology,
        *,
        mixing: str = "dense",  # "dense" (paper equation) | "shift" (stacked)
        mix_every: int = 1,
        mix_rounds: int = 1,
        hub_balance: bool = False,
        collect_norms: bool = False,
        has_rng: bool = False,
        shard_nodes: bool = False,
        bucket_mb: Optional[float] = None,
        debug_no_retrace: bool = False,
        telemetry=None,
    ):
        """Args:
          loss_fn: per-node ``loss_fn(params, batch)`` (or with rng as third
            arg when ``has_rng``) returning a scalar.
          optimizer: per-node optimizer (state carried per node).
          topology: which SGD implementation to simulate.  A topology with
            a ``fault_model`` runs the fault-aware step: stragglers/dead
            nodes skip their local update, transient drops degrade the
            mixing matrix via *runtime* masks (one executable per program,
            exactly as many as the fault-free run), permanent crashes
            select the pre-enumerated degraded program, and recovered
            nodes rejoin by adopting their neighbors' average.
          mixing: which ``GossipProgram`` interpreter executes W θ — "dense"
            (paper-faithful matrix product) or "shift" (stacked roll/gather).
          mix_rounds: gossip rounds fused into each mixing step — H
            consecutive schedule steps (e.g. a full one-peer cycle) run as
            ONE cached executable instead of H dispatches.
          hub_balance: with ``mix_rounds > 1`` on a static multi-matching
            program, rotate its edge-colored matchings across the H rounds
            (``hub_balanced_rounds``) to cap hot-vertex peak send volume.
          shard_nodes: virtual-node sharding — partition the leading node
            axis over the host's devices (a 1-D "nodes" mesh using the
            largest device count dividing n), so n = 256–1024 dynamics runs
            fit a small CPU box: each device simulates an n/d block of
            virtual nodes.  A no-op (identical numerics) on one device.
          bucket_mb: overlap-scheduled gossip — partition the flattened
            parameter vector into ~bucket_mb-MiB buckets
            (``core/buckets.BucketLayout``) and run each mixing step as
            one *per-bucket* update+gossip dispatch chain instead of a
            monolithic tail: bucket i's permutes carry no data dependency
            on bucket i+1's compute, so the dispatches pipeline, and each
            bucket's Ξ² partial sum is folded into its pass (closed-loop
            probes on fault-free runs stop paying the standalone probe
            executable).  SGD-family optimizers and ``mix_order="post"``
            only; numerically equivalent to the monolithic path (tested
            ≤ 1e-6 vs the dense oracle).
        """
        if mixing not in _ENGINES:
            raise ValueError(
                f"mixing must be one of {sorted(_ENGINES)}, got {mixing!r}"
            )
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.topology = topology
        self.n = topology.n_nodes
        self.mixing = mixing
        self.mix_every = max(int(mix_every), 1)
        self.mix_rounds = max(int(mix_rounds), 1)
        self.hub_balance = bool(hub_balance)
        self.collect_norms = collect_norms
        self.has_rng = has_rng
        self.fault_model = topology.fault_model
        self._last_membership = None
        # unified run telemetry (repro.telemetry): counters/gauges/spans/
        # events for sink-attached runs, and the observational wall-clock
        # deadline trace for deadline runs — the seeded model drives the
        # masks (determinism + engine equivalence), the recorder just logs
        # measured per-round durations against the deadline.  The default
        # recorder has no sinks and costs nothing on the hot path.
        from repro.telemetry import MetricsRecorder

        self.telemetry = (
            telemetry if telemetry is not None else MetricsRecorder()
        )
        self.telemetry.configure(
            deadline_ms=getattr(self.fault_model, "deadline_ms", None)
        )
        if topology.controller is not None:
            topology.controller.bind_recorder(self.telemetry)
        self._pn_bytes: Optional[int] = None
        self._last_program = None
        self._step_cache: dict[Any, Callable] = {}
        # debug mode (repro.analysis.recompile): invoking a WARM cached
        # executable must never trace/compile — the zero-mid-run-recompile
        # invariant enforced live instead of post-hoc cache counting
        self.debug_no_retrace = bool(debug_no_retrace)
        self._was_warm = False
        self.shard_nodes = bool(shard_nodes)
        self._sharding = (
            self._node_sharding(self.n) if self.shard_nodes else None
        )
        self.bucket_mb = bucket_mb
        if bucket_mb is not None:
            from repro.core.buckets import bucket_eligible_optimizer

            if not bucket_eligible_optimizer(optimizer):
                raise ValueError(
                    "bucket_mb requires an SGD-family optimizer (elementwise "
                    f"update; got {optimizer.name}) — AdamW's global step "
                    "counter and LARS's per-layer norms do not bucket"
                )
            if topology.centralized:
                raise ValueError("bucket_mb needs a decentralized topology")
            if topology.mix_order != "post":
                raise ValueError(
                    "bucket_mb requires mix_order='post' (pre-mixing must "
                    "see the full tree before the update — nothing to "
                    "pipeline behind)"
                )
        self._bucket_layout = None
        # Ξ² fold: per-node partial sums accumulated across the last bucketed
        # mixing step's dispatches; valid for a probe at _folded_for_step
        self._folded_sq = None
        self._folded_for_step = -1
        # grads stashed by the bucketed path for the grad-norm gauge at
        # metrics-due steps (cleared after each emission)
        self._pending_grads = None

    # -- telemetry views -------------------------------------------------------
    # round_ms / deadline_overruns were per-engine lists before the shared
    # recorder existed; they stay as thin views for backward compatibility.
    @property
    def round_ms(self) -> list:
        return self.telemetry.round_ms

    @property
    def deadline_overruns(self) -> int:
        return self.telemetry.deadline_overruns

    @property
    def _deadline_ms(self):
        return self.telemetry.deadline_ms

    def _per_node_bytes(self, params: PyTree) -> int:
        """Per-node parameter bytes P for comm billing (stacked leaves
        carry the node axis first)."""
        if self._pn_bytes is None:
            self._pn_bytes = sum(
                int(np.prod(x.shape[1:])) * x.dtype.itemsize
                for x in jax.tree.leaves(params)
            )
        return self._pn_bytes

    def _bill_comm(self, program, params: PyTree, step: int, fr) -> None:
        """Bill one mixing-program application at dispatch time (bytes on
        the wire + permute count), matching the offline replay accounting
        in ``benchmarks/ada.py::_total_comm``."""
        if program is None or not self.telemetry.active:
            return
        alive = link = None
        if fr is not None:
            alive = np.asarray(fr.alive, np.float64)
            link = fr.link_up
        self.telemetry.comm(
            program, self._per_node_bytes(params), step=step,
            alive=alive, link_up=link,
        )

    @staticmethod
    def _node_sharding(n: int):
        """NamedSharding partitioning the leading node axis over the largest
        device count that divides n (1 device => effectively replicated)."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        devs = jax.devices()
        nd = max(d for d in range(1, len(devs) + 1) if n % d == 0)
        mesh = Mesh(np.array(devs[:nd]), ("nodes",))
        return NamedSharding(mesh, PartitionSpec("nodes"))

    def _place(self, tree: PyTree) -> PyTree:
        return (
            tree if self._sharding is None
            else jax.device_put(tree, self._sharding)
        )

    # -- state ----------------------------------------------------------------
    def init(self, params: PyTree) -> SimState:
        """Broadcast one replica to all nodes (paper: identical replicas)."""
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (self.n,) + x.shape), params
        )
        opt0 = self.optimizer.init(params)
        opt = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (self.n,) + x.shape), opt0
        )
        return SimState(
            params=self._place(stacked), opt_state=self._place(opt), step=0
        )

    # -- one training step ------------------------------------------------------
    def _build_step(self, program: Optional[GossipProgram], faulty: bool = False):
        """program: compiled mixing schedule; None => pure local update.

        ``faulty`` builds the fault-aware signature: an extra runtime mask
        pytree (``realization_arrays``) gates per-node updates and degrades
        the mixing weights — mask *values* change per realization, the
        executable never does.
        """
        engine = _ENGINES[self.mixing]

        def _grads(params, batch, rng):
            if self.has_rng:
                rngs = jax.random.split(rng, self.n)
                return jax.vmap(jax.value_and_grad(self.loss_fn))(
                    params, batch, rngs
                )
            return jax.vmap(jax.value_and_grad(self.loss_fn))(params, batch)

        def _norms(params):
            return (
                jax.vmap(dbench.param_l2_norms)(params)
                if self.collect_norms
                else jnp.zeros((self.n, 0), jnp.float32)
            )

        def step(params, opt_state, batch, lr, rng):
            loss, grads = _grads(params, batch, rng)
            norms = _norms(params)

            if self.topology.centralized:
                # C_complete: average gradients globally; replicas stay identical.
                grads = jax.tree.map(
                    lambda g: jnp.broadcast_to(
                        g.mean(axis=0, keepdims=True), g.shape
                    ),
                    grads,
                )
                new_params, new_opt = jax.vmap(
                    self.optimizer.update, in_axes=(0, 0, 0, None)
                )(grads, opt_state, params, lr)
                return new_params, new_opt, loss, norms

            if program is not None and self.topology.mix_order == "pre":
                params = program.apply(params, engine=engine)
            new_params, new_opt = jax.vmap(
                self.optimizer.update, in_axes=(0, 0, 0, None)
            )(grads, opt_state, params, lr)
            if program is not None and self.topology.mix_order == "post":
                new_params = program.apply(new_params, engine=engine)
            return new_params, new_opt, loss, norms

        def fault_step(params, opt_state, batch, lr, rng, fault):
            loss, grads = _grads(params, batch, rng)
            norms = _norms(params)

            def _mix(tree):
                return program.apply_masked(
                    tree, fault["alive"], link_up=fault["link"], engine=engine
                )

            if program is not None and self.topology.mix_order == "pre":
                params = _mix(params)
            new_params, new_opt = jax.vmap(
                self.optimizer.update, in_axes=(0, 0, 0, None)
            )(grads, opt_state, params, lr)
            # stragglers and dead nodes skip their local update entirely
            u = fault["update"]

            def _gate(new, old):
                ucol = u.reshape((self.n,) + (1,) * (new.ndim - 1))
                return jnp.where(ucol > 0, new, old)

            new_params = jax.tree.map(_gate, new_params, params)
            new_opt = jax.tree.map(_gate, new_opt, opt_state)
            if program is not None and self.topology.mix_order == "post":
                new_params = _mix(new_params)
            return new_params, new_opt, loss, norms

        fn = fault_step if faulty else step
        if self._sharding is None:
            return jax.jit(fn)
        # virtual-node sharding: keep every node-axis output partitioned so
        # the state never silently collapses to replicated between steps
        s = self._sharding
        return jax.jit(fn, out_shardings=(s, s, s, s))

    def _resolve_program(self, step: int, epoch: int, program_alive=None):
        """This gossip round's fused program (degraded for a permanent-crash
        membership) — shared by the monolithic and bucketed paths."""
        program = self.topology.fused_program_at(
            step=step, epoch=epoch, rounds=self.mix_rounds,
            hub_balance=self.hub_balance,
        )
        if program is not None and program_alive is not None:
            program = program.degrade(program_alive)
        return program

    def _step_for(self, step: int, epoch: int, mix: bool = True,
                  program_alive=None):
        """The jitted executable for one iteration, cached per program.

        ``program_alive`` (permanent-crash membership) selects the
        pre-enumerated degraded program; a non-None value also selects the
        fault-aware step signature.
        """
        faulty = self.fault_model is not None
        # programless keys carry n: an elastic join changes the node-axis
        # shape the closures trace with, so sizes must not share executables
        if self.topology.centralized:
            key = ("__centralized__", self.n)
            program = None
            faulty = False
        elif not mix:
            key = ("__local__", self.n)
            program = None
        else:
            program = self._resolve_program(step, epoch, program_alive)
            key = (
                program.cache_key if program is not None
                else ("__local__", self.n)
            )
        if faulty:
            key = (key, "faulty")
        self._last_program = program  # comm billing reuses this resolution
        self._was_warm = key in self._step_cache
        if key not in self._step_cache:
            self._step_cache[key] = self._build_step(program, faulty=faulty)
        return self._step_cache[key]

    def _retrace_guard(self, warm: bool, label: str):
        """``debug_no_retrace`` guard around a cached-executable call: a
        warm executable invoked again must not fire a trace/compile event
        (``repro.analysis.recompile``).  Guards ONLY the call itself —
        eager membership-event work (admit/adopt/drain) legitimately runs
        outside jit and must not trip the sanitizer."""
        if not (self.debug_no_retrace and warm):
            import contextlib

            return contextlib.nullcontext()
        from repro.analysis.recompile import assert_no_retrace

        return assert_no_retrace(label)

    # -- bucketed, overlap-scheduled path -----------------------------------
    def _grads_fn(self):
        """Jitted (loss, grads, norms) — the compute the bucketed mixing
        dispatches pipeline behind."""
        key = ("__grads__", self.n)
        if key not in self._step_cache:

            def gn(params, batch, rng):
                if self.has_rng:
                    rngs = jax.random.split(rng, self.n)
                    loss, grads = jax.vmap(jax.value_and_grad(self.loss_fn))(
                        params, batch, rngs
                    )
                else:
                    loss, grads = jax.vmap(jax.value_and_grad(self.loss_fn))(
                        params, batch
                    )
                norms = (
                    jax.vmap(dbench.param_l2_norms)(params)
                    if self.collect_norms
                    else jnp.zeros((self.n, 0), jnp.float32)
                )
                return loss, grads, norms

            if self._sharding is None:
                self._step_cache[key] = jax.jit(gn)
            else:
                s = self._sharding
                self._step_cache[key] = jax.jit(gn, out_shardings=(s, s, s))
        return self._step_cache[key]

    def _bucket_fn(self, program, width: int, has_m: bool, faulty: bool):
        """One bucket width's jitted update+mix dispatch, cached per
        (program, width): all full buckets share one executable, the tail
        adds at most a second — fault masks are runtime operands, so
        executables scale with distinct programs, never buckets × faults."""
        key = ("__bucket__", program.cache_key, width, has_m, faulty)
        self._was_warm = key in self._step_cache
        if key not in self._step_cache:
            from repro.core.buckets import build_bucket_step

            fn = build_bucket_step(
                program,
                hyper=self.optimizer.hyper,
                has_momentum=has_m,
                faulty=faulty,
            )
            if self._sharding is None:
                self._step_cache[key] = jax.jit(fn)
            else:
                s = self._sharding
                outs = (s, s, s) if has_m else (s, s)
                self._step_cache[key] = jax.jit(fn, out_shardings=outs)
        return self._step_cache[key]

    def _bucketed_step(self, state, batch, lr, rng, program, fault):
        """One iteration as B independent per-bucket dispatches.

        The grads dispatch runs first; then each bucket's update+mix+Ξ²
        launches as its own executable over that bucket's slices.  The
        (n,) Ξ² accumulator token is the ONLY cross-bucket dependency —
        it pins a consistent execution order (collective-bearing
        executables deadlock if devices start them in different orders)
        while the (n, w) payloads stay independent, so the runtime
        pipelines bucket i's permutes behind bucket i+1's update (the
        monolithic step is one tail barrier instead).  On a fault-free
        step the final token is cached for the next Ξ_t probe.  The
        dispatch window is bounded (``MAX_INFLIGHT_BUCKETS``): before
        launching a new bucket the host blocks on the token of the one
        leaving the window, so fine bucket sizes cannot queue hundreds
        of collective-bearing launches at once.
        """
        from repro.core.buckets import MAX_INFLIGHT_BUCKETS, BucketLayout

        if self._bucket_layout is None:
            # per-node leaf sizes only — elastic joins change n, not the
            # layout, and jit re-traces per node-axis shape on its own
            self._bucket_layout = BucketLayout.for_stacked(
                state.params, self.bucket_mb
            )
        layout = self._bucket_layout
        loss, grads, norms = self._grads_fn()(state.params, batch, rng)
        # the bucketed path is the one place grads materialize outside the
        # fused step executable — stash them for the grad-norm gauge (host
        # work deferred to the post-step metrics emission, after the loss
        # sync, so the bucket dispatch chain is not delayed)
        self._pending_grads = (
            grads if self.telemetry.due(state.step) else None
        )
        has_m = state.opt_state != ()
        t_mats = layout.split_stacked(state.params)
        g_mats = layout.split_stacked(grads)
        m_mats = layout.split_stacked(state.opt_state) if has_m else None
        lr32 = jnp.float32(lr)
        n = jax.tree.leaves(state.params)[0].shape[0]
        tok = self._place(jnp.zeros((n,), jnp.float32))
        out_t, out_m = [], []
        window: deque = deque()
        for b, w in enumerate(layout.widths):
            if len(window) >= MAX_INFLIGHT_BUCKETS:
                jax.block_until_ready(window.popleft())
            fn = self._bucket_fn(program, w, has_m, fault is not None)
            args = (
                (t_mats[b], m_mats[b], g_mats[b], lr32, tok)
                if has_m
                else (t_mats[b], g_mats[b], lr32, tok)
            )
            if fault is not None:
                args = args + (fault,)
            with self._retrace_guard(self._was_warm, f"bucket {b}"):
                res = fn(*args)
            if has_m:
                t2, m2, tok = res
                out_m.append(m2)
            else:
                t2, tok = res
            out_t.append(t2)
            window.append(tok)
        new_params = self._place(layout.merge_stacked(out_t, state.params))
        new_opt = (
            self._place(layout.merge_stacked(out_m, state.opt_state))
            if has_m
            else state.opt_state
        )
        if fault is None:
            self._folded_sq = tok
            self._folded_for_step = state.step + 1
        return new_params, new_opt, loss, norms

    def train_step(
        self,
        state: SimState,
        batch: PyTree,
        lr: float,
        *,
        epoch: int = 0,
        rng: Optional[jax.Array] = None,
    ) -> tuple[SimState, jax.Array, jax.Array]:
        """Run one iteration.

        Args:
          batch: leaves with leading (n_nodes, per_node_batch, ...) dims.
        Returns:
          (new_state, per_node_loss (n,), per_node_norms (n, n_leaves)).
        """
        tel = self.telemetry
        t_start = tel.round_start()
        fr = None
        if self.fault_model is not None:
            fr = self.fault_model.at(state.step)
            if fr.joins:
                # elastic growth: resize the family, then admit the newcomers
                if tel.active:
                    tel.event("join", state.step,
                              data={"nodes": sorted(int(j) for j in fr.joins)})
                state = self._admit(state, fr, epoch)
            for node in fr.rejoin:
                # elastic re-entry: adopt the alive neighbors' average
                nbrs = rejoin_neighbors(
                    self.topology, fr, node, step=state.step, epoch=epoch,
                    mix_every=self.mix_every,
                )
                if tel.active:
                    tel.event("rejoin", state.step, data={"node": int(node)})
                state = SimState(
                    adopt_neighbor_average(state.params, node, nbrs),
                    adopt_neighbor_average(state.opt_state, node, nbrs),
                    state.step,
                )
            for node in fr.depart:
                # clean preemption departure: exact mean-preserving handoff
                # to the neighborhood before the node's row goes dead
                nbrs = rejoin_neighbors(
                    self.topology, fr, node, step=state.step, epoch=epoch,
                    mix_every=self.mix_every,
                )
                if tel.active:
                    tel.event("depart", state.step, data={"node": int(node)})
                state = SimState(
                    drain_handoff(state.params, node, nbrs, fr.alive),
                    drain_handoff(state.opt_state, node, nbrs, fr.alive),
                    state.step,
                )
        ctl = self.topology.controller
        if self.fault_model is not None:
            prev_membership = self._last_membership
            self._last_membership = track_membership(
                self._last_membership, fr, ctl, state.step
            )
            if (
                tel.active
                and prev_membership is not None
                and self._last_membership != prev_membership
            ):
                tel.event(
                    "membership", state.step,
                    data={"alive": [bool(b) for b in self._last_membership]},
                )
        if ctl is not None and ctl.should_probe(state.step):
            if fr is not None:
                from repro.core.consensus import consensus_distance_masked_jit

                # membership mask, NOT the raw alive mask: a float drain
                # boost must not weight the draining node in the probe
                xi = consensus_distance_masked_jit(
                    state.params,
                    jnp.asarray(np.asarray(fr.alive) != 0, jnp.float32),
                )
            elif self._folded_for_step == state.step:
                # folded probe: the last bucketed mixing step already
                # accumulated each bucket's Ξ² partial sum in its own
                # dispatch — only the final √mean runs, on the host
                from repro.core.buckets import xi_from_folded_sq

                xi = xi_from_folded_sq(self._folded_sq)
            else:
                from repro.core.consensus import consensus_distance_jit

                xi = consensus_distance_jit(state.params)
            if tel.active:
                tel.gauge("xi", float(xi), step=state.step)
            ctl.observe(float(xi), state.step)
        mix = (state.step + 1) % self.mix_every == 0
        # index time-varying schedules by gossip round (see SPMDTrainer):
        # raw-step indexing under mix_every=H would alias period-p families
        # to a single phase whenever p divides H.
        sel = fr.selection_mask() if fr is not None else None
        palive = sel if sel is not None and not sel.all() else None
        if rng is None:
            rng = jax.random.PRNGKey(0)
        if (
            self.bucket_mb is not None
            and mix
            and not self.topology.centralized
        ):
            program = self._resolve_program(
                state.step // self.mix_every, epoch, palive
            )
            if program is not None:
                self._bill_comm(program, state.params, state.step, fr)
                fault = realization_arrays(fr) if fr is not None else None
                p, o, loss, norms = self._bucketed_step(
                    state, batch, lr, rng, program, fault
                )
                self._finish_round(
                    loss, norms, t_start, step=state.step, mix=True, lr=lr
                )
                return SimState(p, o, state.step + 1), loss, norms
        fn = self._step_for(
            state.step // self.mix_every, epoch, mix=mix, program_alive=palive
        )
        if mix and not self.topology.centralized:
            self._bill_comm(self._last_program, state.params, state.step, fr)
        args = (state.params, state.opt_state, batch, jnp.float32(lr), rng)
        if fr is not None and not self.topology.centralized:
            args = args + (realization_arrays(fr),)
        with self._retrace_guard(self._was_warm, f"sim step {state.step}"):
            p, o, loss, norms = fn(*args)
        self._finish_round(loss, norms, t_start, step=state.step, mix=mix, lr=lr)
        return SimState(p, o, state.step + 1), loss, norms

    def _finish_round(self, loss, norms, t_start, *, step: int, mix: bool,
                      lr: float) -> None:
        """Shared post-step telemetry (the former per-engine
        ``_record_round``): closes the ``round`` span — blocking on the
        loss so the measured duration covers the whole dispatched round,
        with deadline-overrun attribution in the recorder — and emits the
        loss/lr/variance/grad-norm sample at the metrics cadence.  Purely
        observational; the averaging masks stay seeded."""
        tel = self.telemetry
        if t_start is not None:
            jax.block_until_ready(loss)
            tel.round_end(t_start, step=step, mix=mix)
        if tel.due(step):
            tel.step_metrics(
                step, loss=loss, lr=lr,
                norms=norms if self.collect_norms else None,
                grads=self._pending_grads,
            )
            self._pending_grads = None

    # -- elastic growth ----------------------------------------------------------
    def _admit(self, state: SimState, fr, epoch: int) -> SimState:
        """Grow membership to ``len(fr.program_alive)``: re-derive the
        topology family at the new n (``Topology.resized``; the fresh
        controller adopts the old run state) and append one state row per
        joining node seeded with its neighborhood average."""
        m = len(fr.program_alive)
        old_ctl = self.topology.controller
        topo = self.topology.resized(m)
        if topo.controller is not None and old_ctl is not None:
            topo.controller.adopt(old_ctl)
        if topo.controller is not None:
            # the rebuilt controller keeps routing events into the run's
            # recorder (same stream across the membership change)
            topo.controller.bind_recorder(self.telemetry)
        self.topology = topo
        self.n = m
        if self.shard_nodes:
            self._sharding = self._node_sharding(m)
        params, opt = state.params, state.opt_state
        rows = len(fr.program_alive) - len(fr.joins)
        for node in sorted(fr.joins):
            # same-step multi-joins admit in index order; a later joiner is
            # not yet a row, so drop it from an earlier joiner's average
            nbrs = [
                i for i in rejoin_neighbors(
                    topo, fr, node, step=state.step, epoch=epoch,
                    mix_every=self.mix_every,
                )
                if i < rows
            ]
            params = admit_node(params, nbrs)
            opt = admit_node(opt, nbrs)
            rows += 1
        return SimState(self._place(params), self._place(opt), state.step)

    # -- crash-consistent resume -------------------------------------------------
    def snapshot_extra(self) -> dict:
        """Engine run state a crash-consistent checkpoint must carry beyond
        (params, opt_state): the membership tracking (else the first
        post-resume membership change skips its controller re-arm) and the
        controller's phase/rung/log state.  JSON-serializable.

        ``run_config`` records the load-bearing launch configuration
        (topology name, bucket layout) so a mismatched ``--resume`` fails
        fast at restore.  ``n`` stays OUTSIDE run_config: elastic joins
        legitimately grow it mid-run, and restore resizes to match."""
        d: dict = {
            "run_config": {
                "topology": self.topology.name,
                "bucket_mb": (
                    None if self.bucket_mb is None else float(self.bucket_mb)
                ),
            },
            "n": int(self.n),
            "last_membership": (
                None if self._last_membership is None
                else [bool(b) for b in self._last_membership]
            ),
        }
        ctl = self.topology.controller
        if ctl is not None:
            d["controller"] = ctl.state_dict()
        d["telemetry"] = self.telemetry.state_dict()
        return d

    def restore_extra(self, d: dict) -> None:
        """Inverse of ``snapshot_extra`` on a freshly-built engine.

        Validates the checkpoint's recorded ``run_config`` (topology and
        bucket layout; NOT n — elastic resumes resize) fail-fast first."""
        _validate_run_config(
            d.get("run_config") or {}, topology=self.topology.name,
            bucket_mb=self.bucket_mb,
        )
        n = int(d.get("n", self.n))
        if n != self.n:
            # elastic resume: the run had already grown past the initial n
            self.topology = self.topology.resized(n)
            self.n = n
            if self.shard_nodes:
                self._sharding = self._node_sharding(n)
        lm = d.get("last_membership")
        self._last_membership = (
            None if lm is None else tuple(bool(b) for b in lm)
        )
        ctl = self.topology.controller
        if ctl is not None and d.get("controller") is not None:
            ctl.load_state_dict(d["controller"])
        if d.get("telemetry") is not None:
            # resumed counters/span totals continue instead of restarting
            self.telemetry.load_state_dict(d["telemetry"])

    # -- full run helper ---------------------------------------------------------
    def run(
        self,
        params0: PyTree,
        batches: Iterator[PyTree],
        *,
        n_steps: int,
        lr_schedule: Callable[[float], float],
        steps_per_epoch: int = 1,
        record_every: int = 1,
        recorder: Optional[dbench.DBenchRecorder] = None,
        eval_fn: Optional[Callable[[PyTree], float]] = None,
        eval_every: int = 0,
        rng: Optional[jax.Array] = None,
    ) -> tuple[SimState, dict]:
        state = self.init(params0)
        rng = jax.random.PRNGKey(17) if rng is None else rng
        history = {"step": [], "loss": [], "eval_step": [], "eval": []}
        for t in range(n_steps):
            epoch = t // steps_per_epoch
            rng, sub = jax.random.split(rng)
            batch = next(batches)
            state, loss, norms = self.train_step(
                state, batch, lr_schedule(t), epoch=epoch, rng=sub
            )
            if t % record_every == 0:
                history["step"].append(t)
                history["loss"].append(float(jnp.mean(loss)))
                if recorder is not None:
                    recorder.record(t, np.asarray(loss), np.asarray(norms))
            if eval_fn is not None and eval_every and (t + 1) % eval_every == 0:
                history["eval_step"].append(t + 1)
                history["eval"].append(float(eval_fn(state.mean_params())))
        return state, history
