"""SPMD decentralized training engine (the production path).

The train step is a ``shard_map`` manual over the *gossip axes* only; the
``model`` axis stays a GSPMD auto axis, so tensor/expert parallelism inside
a node is driven purely by the parameter in_shardings.  Global state is the
gossip-stacked tree (leaves ``(G, ...)`` sharded over the gossip axes);
inside the body each node sees its own replica.

Mixing interprets the same compiled ``GossipProgram`` as the simulator
oracle (``core/schedule.py``): one ``jax.lax.ppermute`` per compiled
permute, the all-reduce fast path for the complete graph, and the
paper-faithful dense all-gather realization with ``mixing="dense"`` (the
program's GatherRow op).  There is no per-engine mixing dispatch — both
engines call ``GossipProgram.apply``.

Per iteration (paper §2.1 order):
  1. local forward/backward (optionally grad-accumulated over microbatches)
  2. C_complete: ``pmean`` gradients over the gossip axes (all-reduce)
     D_*:        local optimizer update, then gossip parameter averaging
  3. optional DBench probe: per-leaf L2 norms *before* mixing

Time-varying topologies (Ada, one-peer exponential, random-matching pools)
compile one executable per distinct ``GossipProgram`` — a handful per run,
enumerable up front via ``Topology.distinct_programs`` — each at its first
use, and switch cached executables at (epoch, step) boundaries thereafter:
graph adaptation costs zero recompiles beyond that bounded set and zero
host sync.

Closed-loop Ada (``--consensus-target``): before a probe step the trainer
computes the consensus distance Ξ_t over the gossip-stacked global state
(one jitted reduction, ``core/consensus.py``) and feeds it to the
topology's ``ConsensusController``; the measured ratio Ξ_t/Ξ_0 — not the
epoch law — steps the schedule down its pre-enumerated ladder, so the
bounded-executable-set invariant holds unchanged.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint.ckpt import validate_run_config as _validate_run_config
from repro.core import dbench
from repro.core.dsgd import Topology
from repro.core.schedule import (
    GossipProgram, _flat_axis_index, compile_graph, dense_program,
)
from repro.launch import sharding as shd
from repro.launch.mesh import gossip_axes_for, gossip_size
from repro.models import transformer as tfm
from repro.models.common import abstract_params, spec_tree
from repro.optim.sgd import Optimizer
from repro.telemetry import profile

PyTree = Any

__all__ = ["SPMDTrainer", "TrainState", "RunResult", "build_config", "main"]


@dataclasses.dataclass
class TrainState:
    params: PyTree
    opt_state: PyTree
    step: int = 0


class _LazyStep:
    """Defers the jit/shard_map build until concrete batch shapes arrive.
    Keeps the shapes and dtypes of its first call's arguments, so that
    ``hlo_text`` can name the executable that call built."""

    def __init__(self, build, mesh):
        self._build = build
        self._mesh = mesh
        self._fn = None
        self.abstract_args = None

    def __call__(self, params, opt_state, batch, lr, *fault):
        if self._fn is None:
            self._fn = self._build(batch)
            self.abstract_args = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    np.shape(x), x.dtype, weak_type=getattr(x, "weak_type", False)
                ),
                (params, opt_state, batch, lr) + fault,
            )
        return self._fn(params, opt_state, batch, lr, *fault)

    def lower(self, params, opt_state, batch, lr, *fault):
        return self._build(batch).lower(params, opt_state, batch, lr, *fault)

    def hlo_text(self, *args) -> str:
        """The optimized HLO text of the executable for ``args`` (arrays or
        ShapeDtypeStructs; by default those of the first call), lowered and
        compiled again: a load from the persistent compile cache where the
        step was cached.  The jit's in_shardings give the shardings."""
        with jax.set_mesh(self._mesh):
            return self.lower(*(args or self.abstract_args)).compile().as_text()


class SPMDTrainer:
    """Builds and runs the sharded train step for one (arch × mesh × topology)."""

    def __init__(
        self,
        cfg,
        mesh: jax.sharding.Mesh,
        topology: Topology,
        optimizer: Optimizer,
        *,
        loss_fn: Optional[Callable] = None,
        accum_steps: int = 1,
        collect_norms: bool = False,
        mixing: str = "ppermute",  # ppermute (compiled program) | dense
        mix_every: int = 1,
        mix_rounds: int = 1,
        hub_balance: bool = False,
        fused_apply: bool = False,
        donate: bool = True,
        bucket_mb: Optional[float] = None,
        debug_no_retrace: bool = False,
        telemetry=None,
    ):
        """mix_every: gossip once every H optimizer steps (local-SGD ×
        decentralized; beyond-paper — the limit of the paper's Obs. 5 that
        late-stage connectivity is nearly free to drop).  The non-mixing
        step compiles separately, so the H−1 local steps carry zero gossip
        collectives.

        mix_rounds: fuse H consecutive schedule steps into each gossip
        round — ONE cached executable runs all H rounds back-to-back
        (``GossipProgram.fuse``), so e.g. a full one-peer exponential cycle
        is a single dispatch instead of H.

        hub_balance: with ``mix_rounds > 1`` on a static multi-matching
        program, rotate its edge-colored matchings across the H rounds
        (``hub_balanced_rounds``) so hot vertices (the star hub) stop
        sending in every round of every step.

        fused_apply: run optimizer update + gossip averaging as one fused
        Pallas pass (``kernels/gossip_update``) whenever the step's program
        is all-PPermute (circulant, matching, edge-colored); programs with
        AllReduce/GatherRow ops and non-mixing steps keep the interpreter
        path.  Requires plain momentum-SGD (the kernel re-implements the
        update); the dense-interpreter oracle remains the correctness bar.

        bucket_mb: overlap-scheduled gossip — run each mixing step as a
        chain of per-bucket update+gossip dispatches over a
        ``core/buckets.BucketLayout`` partition of the flattened parameter
        vector instead of one monolithic tail (bucket i's permutes carry
        no data dependency on bucket i+1's compute, so the dispatches
        pipeline), folding each bucket's Ξ² partial into its pass so
        fault-free closed-loop probes skip the standalone probe
        executable.  Composes with ``fused_apply`` (the kernel runs per
        bucket), ``mix_rounds`` (every stage of the fused round runs
        inside the same per-bucket dispatch), and fault masks (runtime
        operands — executables stay one per (program, bucket width), never
        buckets × faults).  SGD family + ``mix_order="post"`` only;
        active in the stacked GSPMD realization (the shard_map realization
        keeps the monolithic step — its per-bucket schedule lives in
        ``GossipProgram.apply_shard_bucketed`` for manual-axes meshes).

        Fault injection rides on the topology (``topology.fault_model``):
        the trainer draws the same seeded realization stream as the
        simulator, gates straggling/dead nodes' local updates, degrades the
        mixing weights with runtime masks (transient faults reuse the
        fault-free executable count; permanent crashes select from the
        pre-enumerated degraded program set), rejoins recovered nodes from
        their neighbors' average, and re-arms the consensus controller on
        membership changes.
        """
        if mixing not in ("ppermute", "dense"):
            raise ValueError(f"mixing must be 'ppermute'|'dense', got {mixing!r}")
        self.cfg = cfg
        self.mesh = mesh
        self.topology = topology
        self.optimizer = optimizer
        self.accum_steps = accum_steps
        self.collect_norms = collect_norms
        self.mixing = mixing
        self.mix_every = max(int(mix_every), 1)
        self.mix_rounds = max(int(mix_rounds), 1)
        self.hub_balance = bool(hub_balance)
        self.fault_model = topology.fault_model
        if self.fault_model is not None and self.fault_model.elastic:
            raise ValueError(
                "elastic (join) fault models grow membership past the mesh's "
                "gossip size; the SPMD trainer's device mesh is fixed — "
                "over-provision the mesh with spare ranks instead "
                "(--spare-ranks / faults.SparePool: joins activate "
                "alive-masked ghost ranks with zero recompiles), or use the "
                "DecentralizedSimulator for true mid-run growth"
            )
        self._last_membership = None
        # unified run telemetry (repro.telemetry): the shared recorder
        # carries the observational wall-clock deadline trace
        # (GossipDeadline runs) — the seeded model drives the masks, the
        # recorder logs MEASURED per-round durations and overruns against
        # the same deadline; enabling timing synchronizes once per step
        # (block on the loss), which the trace documents.  Sink-attached
        # recorders additionally stream counters/gauges/events/variance.
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
        self._pending_grads = None
        self.fused_apply = bool(fused_apply)
        if self.fused_apply:
            hyper = optimizer.hyper or {}
            if (
                hyper.get("kind") != "sgd"
                or hyper.get("nesterov")
                or hyper.get("weight_decay")
            ):
                raise ValueError(
                    "fused_apply re-implements the update inside the Pallas "
                    "kernel and supports plain momentum-SGD only; got "
                    f"{optimizer.name}"
                )
            self._fused_beta = float(hyper.get("momentum", 0.0))
        self.bucket_mb = bucket_mb
        if bucket_mb is not None:
            from repro.core.buckets import bucket_eligible_optimizer

            if not bucket_eligible_optimizer(optimizer):
                raise ValueError(
                    "bucket_mb requires an SGD-family optimizer (elementwise "
                    f"update; got {optimizer.name})"
                )
            if topology.centralized:
                raise ValueError("bucket_mb needs a decentralized topology")
            if topology.mix_order != "post":
                raise ValueError(
                    "bucket_mb requires mix_order='post' (pre-mixing must see "
                    "the full tree before the update)"
                )
        self._bucket_layout = None
        self._folded_sq = None
        self._folded_for_step = -1
        self.donate = donate
        self.gossip_axes = gossip_axes_for(cfg.name, mesh)
        self.g = gossip_size(mesh, self.gossip_axes)
        if topology.n_nodes != self.g:
            raise ValueError(
                f"topology has {topology.n_nodes} nodes but mesh gossip axes "
                f"{self.gossip_axes} give {self.g}"
            )
        self.use_shard_map = self.g > 1
        tp = mesh.shape.get("model", 1)
        self.defs = tfm.model_defs(cfg, tp_size=tp)
        self.loss_fn = loss_fn or (lambda p, b: tfm.loss_fn(p, cfg, b))
        self._step_cache: dict[Any, Any] = {}
        # debug mode (repro.analysis.recompile): a warm cached executable
        # invoked again must never trace/compile
        self.debug_no_retrace = bool(debug_no_retrace)
        self._was_warm = False
        self._build_shardings()

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
        carry the gossip axis first)."""
        if self._pn_bytes is None:
            self._pn_bytes = sum(
                int(np.prod(x.shape[1:])) * jnp.dtype(x.dtype).itemsize
                for x in jax.tree.leaves(params)
            )
        return self._pn_bytes

    def _bill_comm(self, program, params: PyTree, step: int, fr) -> None:
        """Bill one mixing-program application at dispatch time (bytes on
        the wire + permute count) — the same accounting
        ``benchmarks/ada.py::_total_comm`` replays offline."""
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

    @contextlib.contextmanager
    def _call_span(self, warm: bool, step: int):
        """The span ``repro.step.dispatch``, or ``repro.step.compile`` for a
        call that builds an executable; bills to the recorder the calls its
        trace added to ``profile.traced`` (attention blocks by path, chunked
        WKV blocks)."""
        before = profile.traced()
        with profile.span("step.dispatch" if warm else "step.compile"):
            yield
        for name, total in profile.traced().items():
            if total > before[name]:
                self.telemetry.counter(name, total - before[name], step=step)

    def _retrace_guard(self, warm: bool, label: str):
        """``debug_no_retrace`` guard around a warm cached-executable call
        (see ``DecentralizedSimulator._retrace_guard``)."""
        if not (self.debug_no_retrace and warm):
            return contextlib.nullcontext()
        from repro.analysis.recompile import assert_no_retrace

        return assert_no_retrace(label)

    # -- mixing program -------------------------------------------------------
    def _one_program(self, step: int, epoch: int) -> Optional[GossipProgram]:
        graph = self.topology.graph_at(epoch, step)
        if graph is None:
            return None
        if self.mixing == "dense":
            return dense_program(graph)
        return compile_graph(graph)

    def _program_at(self, step: int, epoch: int) -> Optional[GossipProgram]:
        if self.mix_rounds <= 1:
            return self._one_program(step, epoch)
        progs = [
            self._one_program(step * self.mix_rounds + r, epoch)
            for r in range(self.mix_rounds)
        ]
        if any(p is None for p in progs):
            return None
        if self.hub_balance:
            from repro.core.schedule import maybe_hub_balanced

            balanced = maybe_hub_balanced(progs, self.mix_rounds)
            if balanced is not None:
                return balanced
        return GossipProgram.fuse(progs)

    def precompile_programs(self, n_epochs: int = 1) -> list[GossipProgram]:
        """Enumerate every distinct program a run will rotate through.

        This compiles the mixing *programs* (the IR), not the XLA
        executables — each step executable is jitted once at its first use
        and cached by program key; this method bounds and reports that set.
        """
        if self.topology.centralized:
            return []
        progs = []
        seen = set()
        ctl = self.topology.controller
        for (e, s), _ in self.topology.distinct_programs(n_epochs):
            if ctl is not None:
                # Closed-loop keys are (rung, phase): pin the rung so this
                # trainer's own transforms (dense / mix_rounds fusion) see
                # the program the step cache will be keyed on.
                with ctl.pinned(e):
                    p = self._program_at(s, 0)
            else:
                p = self._program_at(s, e)
            if p is not None and p.cache_key not in seen:
                seen.add(p.cache_key)
                progs.append(p)
        if self.fault_model is not None:
            # permanent crashes select among degraded variants of the
            # trainer's own (possibly fused/dense) programs — enumerate
            # them here so they too compile at first use, never beyond.
            from repro.core.faults import fold_degraded_programs

            progs += [
                d for _, d in fold_degraded_programs(progs, self.fault_model)
            ]
        return progs

    # -- shardings -----------------------------------------------------------
    def _build_shardings(self):
        stacked = self.g > 1
        p_abs = abstract_params(self.defs)
        p_specs = spec_tree(self.defs)
        o_abs = jax.eval_shape(self.optimizer.init, p_abs)
        o_specs = self.optimizer.state_specs(p_specs)
        if stacked:
            p_abs = shd.stack_abstract(p_abs, self.g)
            o_abs = shd.stack_abstract(o_abs, self.g)
        kw = dict(stacked=stacked, fsdp=not stacked)
        self.param_shardings = shd.param_shardings(
            p_abs, p_specs, self.mesh, self.gossip_axes, **kw
        )
        self.opt_shardings = shd.param_shardings(
            o_abs, o_specs, self.mesh, self.gossip_axes, **kw
        )
        self.abstract_state = (p_abs, o_abs)

    def batch_shardings(self, batch_like: PyTree) -> PyTree:
        return jax.tree.map(
            lambda l: shd.batch_sharding(
                self.mesh, self.gossip_axes, np.ndim(l) if not hasattr(l, "shape") else len(l.shape),
                stacked=self.g > 1,
            ),
            batch_like,
        )

    # -- state init ------------------------------------------------------------
    def init_state(self, key: jax.Array) -> TrainState:
        """Identical replicas on every node (paper §2.2)."""
        tp = self.mesh.shape.get("model", 1)

        def _init(k):
            p = tfm.init_model(self.cfg, k, tp_size=tp)
            o = self.optimizer.init(p)
            if self.g > 1:
                p, o = jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (self.g,) + x.shape), (p, o)
                )
            return p, o

        with jax.set_mesh(self.mesh):
            p, o = jax.jit(
                _init, out_shardings=(self.param_shardings, self.opt_shardings)
            )(key)
        return TrainState(p, o, 0)

    # -- per-node grads (shared by both realizations) ----------------------------
    def _grads_of(self, params, batch):
        accum = self.accum_steps
        if accum == 1:
            with profile.scope("model"):
                return jax.value_and_grad(self.loss_fn)(params, batch)
        micro = jax.tree.map(
            lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]), batch
        )

        def acc_body(carry, mb):
            with profile.scope("model"):
                l, g = jax.value_and_grad(self.loss_fn)(params, mb)
            return (
                carry[0] + l / accum,
                jax.tree.map(lambda a, b: a + b / accum, carry[1], g),
            ), None

        zero = (
            jnp.zeros((), jnp.float32),
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        )
        (loss, grads), _ = jax.lax.scan(acc_body, zero, micro)
        return loss, grads

    # -- fused kernel eligibility ---------------------------------------------
    def _fused_split(self, program: Optional[GossipProgram]):
        """(kernel_stage, interpreter_stages) when the fused Pallas apply can
        run this program, else None.

        The kernel handles one all-PPermute round (circulant offsets,
        matchings, edge-colored graphs).  A ``mix_rounds`` FusedProgram
        composes: the kernel executes update + round 1, the interpreter the
        remaining rounds — still one executable.  Not eligible: programs
        with AllReduce/GatherRow first ops, non-mixing steps, and
        ``mix_order="pre"`` multi-round fusions (there the descent must
        follow ALL rounds, which the one-round kernel cannot express).
        """
        from repro.core.schedule import FusedProgram

        if (
            not self.fused_apply
            or program is None
            or self.topology.centralized
        ):
            return None
        if isinstance(program, FusedProgram):
            if self.topology.mix_order != "post":
                return None
            first, rest = program.stages[0], program.stages[1:]
        else:
            first, rest = program, ()
        if first.permute_tables() is None:
            return None
        return first, rest

    def _use_fused(self, program: Optional[GossipProgram]) -> bool:
        return self._fused_split(program) is not None

    # -- the node-level step (shard_map realization) ------------------------------
    def _node_step(self, program: Optional[GossipProgram], faulty: bool = False):
        topo = self.topology
        opt = self.optimizer
        axes = self.gossip_axes
        fused = self._fused_split(program) if self.g > 1 else None

        def node_step(params_st, opt_st, batch_st, lr, fault=None):
            # batches carry the node axis at every G; params only when G > 1
            squeeze = self.g > 1
            params = jax.tree.map(lambda x: x[0], params_st) if squeeze else params_st
            opt_state = jax.tree.map(lambda x: x[0], opt_st) if squeeze else opt_st
            batch = jax.tree.map(lambda x: x[0], batch_st)

            loss, grads = self._grads_of(params, batch)
            norms = (
                dbench.param_l2_norms(params)
                if self.collect_norms
                else jnp.zeros((0,), jnp.float32)
            )

            @profile.scope("gossip")
            def _mix(tree, stage=program):
                if fault is None:
                    return stage.apply_shard(tree, axes)
                return stage.apply_shard_masked(
                    tree, axes, fault["alive"], link_up=fault["link"]
                )

            if topo.centralized and self.g > 1:
                grads = jax.tree.map(lambda g: jax.lax.pmean(g, axes), grads)
            if fused:
                from repro.kernels.gossip_update import fused_apply_shard

                first, rest = fused
                with profile.scope("fused_update"):
                    new_p, new_o = fused_apply_shard(
                        first, params, grads, opt_state, axes,
                        lr=lr, beta=self._fused_beta, fault=fault,
                        mix_order=topo.mix_order,
                    )
                for stage in rest:
                    new_p = _mix(new_p, stage)
            else:
                if topo.mix_order == "pre" and program is not None and self.g > 1:
                    params = _mix(params)
                with profile.scope("optimizer"):
                    new_p, new_o = opt.update(grads, opt_state, params, lr)
                if fault is not None:
                    # stragglers/dead skip their local update (this node's
                    # flag selected from the replicated mask)
                    u = fault["update"][_flat_axis_index(axes)]
                    gate = lambda nw, od: jnp.where(u > 0, nw, od)
                    new_p = jax.tree.map(gate, new_p, params)
                    new_o = jax.tree.map(gate, new_o, opt_state)
                if topo.mix_order == "post" and program is not None and self.g > 1:
                    new_p = _mix(new_p)

            if squeeze:
                new_p = jax.tree.map(lambda x: x[None], new_p)
                new_o = jax.tree.map(lambda x: x[None], new_o)
                loss = loss[None]
                norms = norms[None]
            return new_p, new_o, loss, norms

        if faulty:
            return node_step
        return lambda p, o, b, lr: node_step(p, o, b, lr)

    # -- the stacked step (GSPMD realization; old-jax fallback) -------------------
    def _stacked_step(self, program: Optional[GossipProgram], faulty: bool = False):
        """vmap over the gossip axis + the program's stacked interpreter.

        Numerically identical to the shard_map realization; on a mesh whose
        gossip axes shard the leading dim, XLA lowers the program's rolls to
        collective-permutes (and the GatherRow einsum to an all-gather).
        """
        topo = self.topology
        opt = self.optimizer
        fused = self._fused_split(program)

        def stacked_step(params, opt_state, batch, lr, fault=None):
            loss, grads = jax.vmap(self._grads_of)(params, batch)
            norms = (
                jax.vmap(dbench.param_l2_norms)(params)
                if self.collect_norms
                else jnp.zeros((self.g, 0), jnp.float32)
            )
            if topo.centralized:
                grads = jax.tree.map(
                    lambda g: jnp.broadcast_to(
                        g.mean(axis=0, keepdims=True), g.shape
                    ),
                    grads,
                )

            @profile.scope("gossip")
            def _mix(tree, stage=program):
                if fault is None:
                    return stage.apply_stacked(tree)
                return stage.apply_masked(
                    tree, fault["alive"], link_up=fault["link"]
                )

            if fused:
                from repro.kernels.gossip_update import fused_apply_stacked

                first, rest = fused
                with profile.scope("fused_update"):
                    new_p, new_o = fused_apply_stacked(
                        first, params, grads, opt_state,
                        lr=lr, beta=self._fused_beta, fault=fault,
                        mix_order=topo.mix_order,
                    )
                for stage in rest:
                    new_p = _mix(new_p, stage)
                return new_p, new_o, loss, norms
            if topo.mix_order == "pre" and program is not None:
                params = _mix(params)
            with profile.scope("optimizer"):
                new_p, new_o = jax.vmap(opt.update, in_axes=(0, 0, 0, None))(
                    grads, opt_state, params, lr
                )
            if fault is not None:
                u = fault["update"]

                def _gate(nw, od):
                    ucol = u.reshape((self.g,) + (1,) * (nw.ndim - 1))
                    return jnp.where(ucol > 0, nw, od)

                new_p = jax.tree.map(_gate, new_p, params)
                new_o = jax.tree.map(_gate, new_o, opt_state)
            if topo.mix_order == "post" and program is not None:
                new_p = _mix(new_p)
            return new_p, new_o, loss, norms

        if faulty:
            return stacked_step
        return lambda p, o, b, lr: stacked_step(p, o, b, lr)

    # -- bucketed, overlap-scheduled path (stacked realization) ---------------
    @property
    def _bucketed(self) -> bool:
        return (
            self.bucket_mb is not None
            and self.g > 1
            and not self.use_shard_map
        )

    def _bucket_grads_fn(self, batch: PyTree):
        """The jitted backward: (loss, grads, norms) — the compute the
        per-bucket mixing dispatches pipeline behind."""
        key = "__bucket_grads__"
        if key not in self._step_cache:
            gvec = NamedSharding(self.mesh, P(self.gossip_axes))

            def gn(params, batch):
                loss, grads = jax.vmap(self._grads_of)(params, batch)
                norms = (
                    jax.vmap(dbench.param_l2_norms)(params)
                    if self.collect_norms
                    else jnp.zeros((self.g, 0), jnp.float32)
                )
                return loss, grads, norms

            self._step_cache[key] = jax.jit(
                gn,
                in_shardings=(
                    self.param_shardings,
                    jax.tree.map(
                        lambda x: shd.batch_sharding(
                            self.mesh, self.gossip_axes, len(x.shape),
                            stacked=True,
                        ),
                        batch,
                    ),
                ),
                # grads mirror the parameter tree leaf-for-leaf
                out_shardings=(gvec, self.param_shardings, gvec),
            )
        return self._step_cache[key]

    def _bucket_fn(self, program, width: int, has_m: bool, faulty: bool):
        """One bucket width's jitted update+mix dispatch, cached per
        (program, width): all full buckets share one executable, the tail
        adds at most a second; fault masks ride as runtime operands."""
        key = ("__bucket__", program.cache_key, width, has_m, faulty)
        if key not in self._step_cache:
            from repro.core.buckets import build_bucket_step

            kernel_split = (
                self._fused_split(program) if self.fused_apply else None
            )
            fn = build_bucket_step(
                program,
                hyper=self.optimizer.hyper,
                has_momentum=has_m,
                faulty=faulty,
                kernel_split=kernel_split,
            )
            lead2 = NamedSharding(self.mesh, P(self.gossip_axes, None))
            gvec = NamedSharding(self.mesh, P(self.gossip_axes))
            rep = NamedSharding(self.mesh, P())
            ins = (
                [lead2, lead2, rep, gvec]
                if not has_m
                else [lead2, lead2, lead2, rep, gvec]
            )
            if faulty:
                ins.append({
                    "update": rep, "alive": rep,
                    "link": rep if self.fault_model.has_link_faults else None,
                })
            outs = (lead2, lead2, gvec) if has_m else (lead2, gvec)
            self._step_cache[key] = jax.jit(
                fn,
                in_shardings=tuple(ins),
                out_shardings=outs,
                donate_argnums=((0, 1) if has_m else (0,)) if self.donate else (),
            )
        return self._step_cache[key]

    def _bucket_split_fn(self, state, grads, has_m: bool):
        """Jitted bucket-view builder: canonical (model-sharded) trees in,
        (G, w) bucket matrices out.  One executable (not one per leaf):
        the model-axis gathers the reshapes imply stay INSIDE it, so they
        are ordered by its data dependencies — loose eager reshapes would
        each be their own collective-bearing dispatch, outside the token
        chain (see ``_bucketed_step``), and could interleave differently
        across devices and deadlock."""
        key = ("__bucket_split__", has_m)
        if key not in self._step_cache:
            layout = self._bucket_layout
            lead2 = NamedSharding(self.mesh, P(self.gossip_axes, None))

            def split3(params, opt, g):
                return (
                    layout.split_stacked(params),
                    layout.split_stacked(opt) if has_m else [],
                    layout.split_stacked(g),
                )

            nb = layout.num_buckets
            self._step_cache[key] = jax.jit(
                split3,
                in_shardings=(
                    self.param_shardings,
                    self.opt_shardings if has_m else (),
                    self.param_shardings,
                ),
                out_shardings=(
                    [lead2] * nb, [lead2] * nb if has_m else [], [lead2] * nb
                ),
            )
        return self._step_cache[key]

    def _bucket_merge_fn(self, state, has_m: bool):
        """Jitted inverse: bucket matrices back into canonically-sharded
        trees.  Consumes the Ξ² token, so it is ordered after the last
        bucket dispatch; passes it through for the probe fold."""
        key = ("__bucket_merge__", has_m)
        if key not in self._step_cache:
            layout = self._bucket_layout
            lead2 = NamedSharding(self.mesh, P(self.gossip_axes, None))
            gvec = NamedSharding(self.mesh, P(self.gossip_axes))
            p_tmpl = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params
            )
            o_tmpl = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                state.opt_state,
            )
            nb = layout.num_buckets

            def merge3(ts, ms, tok):
                p = layout.merge_stacked(ts, p_tmpl)
                o = layout.merge_stacked(ms, o_tmpl) if has_m else ()
                return p, o, tok

            self._step_cache[key] = jax.jit(
                merge3,
                in_shardings=(
                    [lead2] * nb, [lead2] * nb if has_m else [], gvec
                ),
                out_shardings=(
                    self.param_shardings,
                    self.opt_shardings if has_m else (),
                    gvec,
                ),
            )
        return self._step_cache[key]

    def _bucketed_step(self, state, batch, lr, program, fault):
        """One iteration as a pipelined chain of per-bucket dispatches.

        The backward dispatch runs first; a jitted split carves the
        canonical trees into (G, w) bucket matrices; then each bucket's
        update + all its gossip rounds + its Ξ² partial launches as its
        own executable; a jitted merge re-places the canonical trees.
        The (G,) Ξ² accumulator token is the only cross-bucket operand:
        it pins a consistent execution order across devices (independent
        collective-bearing executables can otherwise start in different
        per-device orders and deadlock at the permute rendezvous), while
        the (G, w) payloads stay independent, so the runtime overlaps
        bucket i's collective-permutes with bucket i+1's compute instead
        of serializing communication behind one monolithic tail.  The
        dispatch window is bounded (``MAX_INFLIGHT_BUCKETS``): before
        launching a new bucket the host blocks on the token of the one
        leaving the window, so fine bucket sizes cannot queue hundreds
        of collective-bearing launches at once.
        """
        from repro.core.buckets import MAX_INFLIGHT_BUCKETS, BucketLayout

        if self._bucket_layout is None:
            self._bucket_layout = BucketLayout.for_stacked(
                state.params, self.bucket_mb
            )
        layout = self._bucket_layout
        with jax.set_mesh(self.mesh):
            loss, grads, norms = self._bucket_grads_fn(batch)(
                state.params, batch
            )
            # the bucketed path is the one place grads materialize outside
            # the fused step executable — stash them for the grad-norm
            # gauge (host work deferred to the post-step metrics emission)
            self._pending_grads = (
                grads if self.telemetry.due(state.step) else None
            )
            has_m = state.opt_state != ()
            t_mats, m_mats, g_mats = self._bucket_split_fn(state, grads, has_m)(
                state.params, state.opt_state, grads
            )
            lr32 = jnp.float32(lr)
            gvec = NamedSharding(self.mesh, P(self.gossip_axes))
            tok = jax.device_put(jnp.zeros((self.g,), jnp.float32), gvec)
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
                res = fn(*args)
                if has_m:
                    t2, m2, tok = res
                    out_m.append(m2)
                else:
                    t2, tok = res
                out_t.append(t2)
                window.append(tok)
            new_params, new_opt, tok = self._bucket_merge_fn(state, has_m)(
                out_t, out_m, tok
            )
            if not has_m:
                new_opt = state.opt_state
        if fault is None:
            self._folded_sq = tok
            self._folded_for_step = state.step + 1
        return new_params, new_opt, loss, norms

    # -- jitted step per program ----------------------------------------------
    def step_fn(self, epoch: int = 0, batch_abstract: Optional[PyTree] = None,
                *, step: int = 0, mix: bool = True, program_alive=None):
        """``program_alive``: permanent-crash membership — selects the
        pre-enumerated degraded program.  A topology with a fault model
        compiles the fault-aware signature (one extra runtime-mask arg):
        transient realizations change mask values only, so the cached-
        executable count matches the fault-free run."""
        program = self._program_at(step, epoch) if mix else None
        if not mix and self.topology.centralized:
            raise ValueError("mix_every > 1 is a decentralized-only feature")
        if program is not None and program_alive is not None:
            program = program.degrade(program_alive)
        faulty = (
            self.fault_model is not None
            and self.g > 1
            and not self.topology.centralized
        )
        key = None if program is None else program.cache_key
        if faulty:
            key = (key, "faulty")
        self._last_program = program  # comm billing reuses this resolution
        self._was_warm = key in self._step_cache
        if key in self._step_cache:
            return self._step_cache[key]

        gspec = P(self.gossip_axes) if self.g > 1 else P()
        lead = lambda nd: P(self.gossip_axes, *([None] * nd))
        in_specs = (
            jax.tree.map(lambda l: lead(len(l.shape) - 1), self.abstract_state[0]),
            jax.tree.map(lambda l: lead(len(l.shape) - 1), self.abstract_state[1]),
        )

        def jit_step(step_fn, batch_tree):
            ins = (
                self.param_shardings,
                self.opt_shardings,
                self.batch_shardings(batch_tree),
                NamedSharding(self.mesh, P()),
            )
            if faulty:  # the runtime-mask pytree is replicated
                rep = NamedSharding(self.mesh, P())
                ins = ins + (
                    {"update": rep, "alive": rep,
                     "link": rep if self.fault_model.has_link_faults else None},
                )
            return jax.jit(
                step_fn,
                in_shardings=ins,
                out_shardings=(
                    self.param_shardings,
                    self.opt_shardings,
                    NamedSharding(self.mesh, gspec),
                    NamedSharding(self.mesh, gspec),
                ),
                donate_argnums=(0, 1) if self.donate else (),
            )

        if self.g == 1:
            node_step = self._node_step(program)

            def build(batch_tree):
                return jit_step(node_step, batch_tree)

        elif self.use_shard_map:
            node_step = self._node_step(program, faulty=faulty)

            def build(batch_tree):
                batch_specs = jax.tree.map(
                    lambda x: lead(len(x.shape) - 1), batch_tree
                )
                arg_specs = (in_specs[0], in_specs[1], batch_specs, P())
                if faulty:
                    arg_specs = arg_specs + (P(),)
                mapped = jax.shard_map(
                    node_step,
                    mesh=self.mesh,
                    in_specs=arg_specs,
                    out_specs=(in_specs[0], in_specs[1], gspec, gspec),
                    # size-1 axes are manual too: a Pallas TPU kernel
                    # (--fused-apply) cannot sit in an auto-partitioned region
                    axis_names=set(self.gossip_axes)
                    | {a for a, n in self.mesh.shape.items() if n == 1},
                    check_vma=False,
                )
                return jit_step(mapped, batch_tree)

        else:
            stacked_step = self._stacked_step(program, faulty=faulty)

            def build(batch_tree):
                return jit_step(stacked_step, batch_tree)

        fn = _LazyStep(build, self.mesh)
        self._step_cache[key] = fn
        return fn

    def step_hlo_texts(self) -> dict:
        """``{executable key: optimized HLO text}`` of each step executable
        this trainer has run, compiled again from its first call's shapes.
        Each instruction's ``metadata={op_name=...}`` carries the scopes of
        ``repro.telemetry.profile``, which a profiler trace's events lack.
        Nothing runs on the step's path for this."""
        return {
            repr(key): fn.hlo_text() for key, fn in self._step_cache.items()
            if isinstance(fn, _LazyStep) and fn.abstract_args is not None
        }

    # -- public API ------------------------------------------------------------------
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

    def _realize_faults(self, state: TrainState, epoch: int):
        """This step's fault realization, applied to the state: recovered
        nodes rejoin from their neighbours' average, preempted nodes hand
        off and leave, and membership changes re-arm the controller.
        Returns (realization, state)."""
        from repro.core.faults import (
            adopt_neighbor_average, drain_handoff, rejoin_neighbors,
            track_membership,
        )

        tel = self.telemetry
        fr = self.fault_model.at(state.step)
        for node in fr.rejoin:
            nbrs = rejoin_neighbors(
                self.topology, fr, node, step=state.step, epoch=epoch,
                mix_every=self.mix_every,
            )
            if tel.active:
                tel.event("rejoin", state.step, data={"node": int(node)})
            with jax.set_mesh(self.mesh):
                state = TrainState(
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
            with jax.set_mesh(self.mesh):
                state = TrainState(
                    drain_handoff(state.params, node, nbrs, fr.alive),
                    drain_handoff(state.opt_state, node, nbrs, fr.alive),
                    state.step,
                )
        prev_membership = self._last_membership
        self._last_membership = track_membership(
            self._last_membership, fr, self.topology.controller, state.step
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
        return fr, state

    def _probe(self, state: TrainState, fr) -> None:
        """Consensus probe: Ξ_t of the current state, fed to the controller
        (a host sync on Ξ_t)."""
        ctl = self.topology.controller
        with jax.set_mesh(self.mesh):
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
                # accumulated each bucket's Ξ² partial in its own
                # dispatch — only the final √mean runs, on the host
                from repro.core.buckets import xi_from_folded_sq

                xi = xi_from_folded_sq(self._folded_sq)
            else:
                from repro.core.consensus import consensus_distance_jit

                xi = consensus_distance_jit(state.params)
        if self.telemetry.active:
            self.telemetry.gauge("xi", float(xi), step=state.step)
        ctl.observe(float(xi), state.step)

    def _bucketed_warm(self, program, has_m: bool, faulty: bool) -> bool:
        """True when every executable of a bucketed step is already built."""
        layout = self._bucket_layout
        return layout is not None and all(
            ("__bucket__", program.cache_key, w, has_m, faulty) in self._step_cache
            for w in set(layout.widths)
        )

    def train_step(self, state: TrainState, batch: PyTree, lr: float, *, epoch: int = 0):
        """One training step, inside the host span ``repro.train_step``
        (``step_num`` = the step) and its phases ``repro.step.faults``,
        ``repro.step.probe`` and ``repro.step.compile`` or
        ``repro.step.dispatch`` (``repro.telemetry.profile``)."""
        with profile.step_span(state.step):
            t_start = self.telemetry.round_start()
            ctl = self.topology.controller
            fr = None
            if self.fault_model is not None and self.g > 1:
                with profile.span("step.faults"):
                    fr, state = self._realize_faults(state, epoch)
            if ctl is not None and self.g > 1 and ctl.should_probe(state.step):
                with profile.span("step.probe"):
                    self._probe(state, fr)
            mix = (state.step + 1) % self.mix_every == 0
            # Time-varying schedules advance per *gossip round*, not per raw
            # step: with mix_every=H only every H-th step mixes, and indexing
            # by raw step would alias a period-p family to the single phase
            # H-1 mod p whenever p | H (e.g. one-peer n=16 with H=4 would
            # gossip hop 8 forever, splitting the network into isolated pairs).
            # the *selection* mask: for composed concurrent crashes it stays
            # all-ones (base program + runtime masks), so the degraded-program
            # branch — and any extra executable — is never taken
            sel = fr.selection_mask() if fr is not None else None
            palive = sel if sel is not None and not sel.all() else None
            if self._bucketed and mix and not self.topology.centralized:
                program = self._program_at(state.step // self.mix_every, epoch)
                if program is not None and palive is not None:
                    program = program.degrade(palive)
                if program is not None:
                    from repro.core.faults import realization_arrays

                    self._bill_comm(program, state.params, state.step, fr)
                    fault = realization_arrays(fr) if fr is not None else None
                    warm = self._bucketed_warm(
                        program, state.opt_state != (), fault is not None
                    )
                    with self._call_span(warm, state.step):
                        p, o, loss, norms = self._bucketed_step(
                            state, batch, lr, program, fault
                        )
                    self._finish_round(
                        loss, norms, t_start, step=state.step, mix=True, lr=lr
                    )
                    return TrainState(p, o, state.step + 1), loss, norms
            fn = self.step_fn(
                epoch, step=state.step // self.mix_every,
                mix=mix or self.topology.centralized,
                program_alive=palive,
            )
            if mix and self.g > 1 and not self.topology.centralized:
                self._bill_comm(self._last_program, state.params, state.step, fr)
            args = (state.params, state.opt_state, batch, jnp.float32(lr))
            if fr is not None:
                from repro.core.faults import realization_arrays

                args = args + (realization_arrays(fr),)
            # a warm _LazyStep that has not built yet still traces legitimately
            warm = self._was_warm and (
                not isinstance(fn, _LazyStep) or fn._fn is not None
            )
            with jax.set_mesh(self.mesh), self._retrace_guard(
                warm, f"spmd step {state.step}"
            ), self._call_span(warm, state.step):
                p, o, loss, norms = fn(*args)
            self._finish_round(
                loss, norms, t_start, step=state.step, mix=mix, lr=lr
            )
            return TrainState(p, o, state.step + 1), loss, norms

    # -- crash-consistent resume -------------------------------------------------
    def snapshot_extra(self) -> dict:
        """Engine run state a crash-consistent checkpoint must carry beyond
        (params, opt_state): membership tracking (else the first
        post-resume membership change skips its controller re-arm) and the
        consensus controller's phase/rung/log state.  Fault realizations
        themselves are pure fn(seed, step) and need no persisting —
        replaying from the checkpoint step regenerates them bit-exactly.

        ``run_config`` records the load-bearing launch configuration
        (topology name, gossip size, bucket layout) so a mismatched
        ``--resume`` fails fast at restore with a clear error instead of
        surfacing as a shape/tree mismatch mid-run."""
        d: dict = {
            "run_config": {
                "topology": self.topology.name,
                "n": int(self.g),
                "bucket_mb": (
                    None if self.bucket_mb is None else float(self.bucket_mb)
                ),
            },
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
        """Inverse of ``snapshot_extra`` on a freshly-built trainer.

        Validates the checkpoint's recorded ``run_config`` against this
        trainer's configuration first (fail-fast resume)."""
        rc = d.get("run_config") or {}
        _validate_run_config(
            rc, topology=self.topology.name, n=int(self.g),
            bucket_mb=self.bucket_mb, n_label="mesh gossip size",
        )
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

    def lower_step(self, shape, *, epoch: int = 0, step: int = 0):
        """Abstract lowering for the dry-run: ShapeDtypeStructs only."""
        from repro.configs.base import input_specs

        batch = input_specs(self.cfg, shape, n_nodes=self.g)
        fn = self.step_fn(epoch, step=step)
        p_abs, o_abs = self.abstract_state
        lr = jax.ShapeDtypeStruct((), jnp.float32)
        # a fault-model trainer's step takes the runtime-mask pytree too
        fault_abs = ()
        if self.fault_model is not None and self.g > 1:
            fault_abs = ({
                "update": jax.ShapeDtypeStruct((self.g,), jnp.float32),
                "alive": jax.ShapeDtypeStruct((self.g,), jnp.float32),
                "link": (
                    jax.ShapeDtypeStruct((self.g, self.g), jnp.float32)
                    if self.fault_model.has_link_faults
                    else None
                ),
            },)
        with jax.set_mesh(self.mesh):
            return fn.lower(p_abs, o_abs, batch, lr, *fault_abs)


# ---------------------------------------------------------------------------
# CLI launcher:  PYTHONPATH=src python -m repro.launch.train --arch granite-8b
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunResult:
    """What one ``main`` run leaves behind: the trainer, its final state,
    the per-node loss of every step, each step's wall time (ended by
    ``block_until_ready``; the first includes tracing and compilation), and
    the learning rate the steps ran at (``--lr`` times its scaling)."""

    trainer: SPMDTrainer
    state: TrainState
    losses: list
    step_seconds: list
    lr: float


def use_repo_compile_cache() -> None:
    """Keep JAX's persistent compilation cache in ``<repo>/.jax_cache``,
    unless ``JAX_COMPILATION_CACHE_DIR`` already chose a directory (JAX
    reads that variable itself).  The path is fixed because it is part of
    the cache key: a directory that moves never hits."""
    import os
    from pathlib import Path

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        repo = Path(__file__).resolve().parents[3]
        jax.config.update("jax_compilation_cache_dir", str(repo / ".jax_cache"))


def build_config(arch: str, *, reduced: bool = False,
                 layers: Optional[int] = None):
    """The CLI's model config: ``arch`` at its published size, cut to the
    CPU-scale reduced config only when ``reduced`` is given, and to
    ``layers`` layers (depth only) when that is given."""
    from repro.configs import get_config

    cfg = get_config(arch + ("-reduced" if reduced else ""))
    cfg = dataclasses.replace(cfg, name=arch)  # keep gossip placement
    if layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    return cfg


def _start_profile(profile_dir: str) -> None:
    """Start a profiler trace with the chip benchmark's options: host
    annotations (the ``repro.*`` spans) without every Python call, and no
    HLO protos (the step's text goes beside the trace instead)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    jax.profiler.start_trace(profile_dir, profiler_options=options)


def _stop_profile(profile_dir: str, trainer: SPMDTrainer) -> None:
    """Stop the trace, then write each step executable's optimized HLO text
    to ``<profile_dir>/step_hlo/<i>.txt``."""
    from pathlib import Path

    jax.profiler.stop_trace()
    out = Path(profile_dir) / "step_hlo"
    out.mkdir(parents=True, exist_ok=True)
    for i, text in enumerate(trainer.step_hlo_texts().values()):
        (out / f"{i}.txt").write_text(text)
    print(f"profile: {profile_dir} (step HLO under {out})")


def main(argv: Optional[list] = None) -> RunResult:
    import argparse

    ap = argparse.ArgumentParser(description="decentralized training launcher")
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="cut widths and depth to the CPU-scale reduced "
                         "config (ArchConfig.reduced)")
    ap.add_argument("--layers", type=int, default=None,
                    help="depth-only cut: replace n_layers, keep the "
                         "published widths")
    ap.add_argument("--topology", default="d_ada")
    ap.add_argument("--mixing", default="ppermute", choices=["ppermute", "dense"])
    ap.add_argument("--mix-every", type=int, default=1)
    ap.add_argument("--mix-rounds", type=int, default=1,
                    help="fuse H consecutive schedule steps per gossip round "
                         "into one executable (GossipProgram.fuse)")
    ap.add_argument("--hub-balance", action="store_true",
                    help="with --mix-rounds H > 1 on a static multi-matching "
                         "program, rotate the edge-colored matchings across "
                         "the H rounds so hot vertices (star hub) stop "
                         "sending in every round")
    ap.add_argument("--fused-apply", action="store_true",
                    help="run optimizer+gossip as one fused Pallas pass for "
                         "all-PPermute programs (plain momentum-SGD only)")
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="overlap-scheduled gossip: partition the flattened "
                         "parameter vector into ~this-many-MiB buckets and "
                         "pipeline per-bucket update+permute dispatches "
                         "instead of one monolithic mixing tail (folds the "
                         "consensus probe into the gossip pass; SGD family "
                         "+ post-mixing only)")
    ap.add_argument("--fault-model", default="none",
                    choices=["none", "crash", "concurrent", "preempt",
                             "join", "deadline", "dropout", "link",
                             "straggler"],
                    help="seeded fault injection: permanent single-node "
                         "crash, k-node concurrent crashes, planned "
                         "preemption drain, pre-declared joins ('join' "
                         "needs --spare-ranks on this fixed-mesh trainer), "
                         "per-round gossip deadlines with backoff "
                         "readmission, transient node dropout, Bernoulli "
                         "link failure, or stragglers that skip the local "
                         "update but still mix (core/faults.py)")
    ap.add_argument("--fault-rate", type=float, default=0.1,
                    help="per-step fault probability (crash/concurrent/"
                         "preempt: geometric onset)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="fault realization seed (step-deterministic; both "
                         "engines draw identical realizations)")
    ap.add_argument("--fault-down-steps", type=int, default=None,
                    help="crash/concurrent: steps until a victim rejoins by "
                         "adopting its neighbors' average (elastic "
                         "membership; default: never)")
    ap.add_argument("--fault-k", type=int, default=2,
                    help="concurrent only: number of victims with "
                         "overlapping down windows")
    ap.add_argument("--fault-drain-steps", type=int, default=5,
                    help="preempt only: announced drain window before the "
                         "clean mean-preserving departure")
    ap.add_argument("--fault-enumerate", action="store_true",
                    help="concurrent only: pre-enumerate the realized "
                         "multi-node degraded programs (bounded fast path) "
                         "instead of the composed runtime-mask default")
    ap.add_argument("--fault-join-steps", default="",
                    help="join only: comma-separated steps at which new "
                         "members arrive (with --spare-ranks each join "
                         "activates one spare rank)")
    ap.add_argument("--spare-ranks", type=int, default=0,
                    help="over-provision the gossip mesh with this many "
                         "ghost ranks riding from step 0 as alive-masked "
                         "zero-weight participants: joins/rejoins activate "
                         "a spare with ZERO extra executables "
                         "(faults.SparePool; composes with any "
                         "--fault-model)")
    ap.add_argument("--gossip-deadline-ms", type=float, default=30.0,
                    help="deadline only: per-round gossip deadline; nodes "
                         "whose (seeded) round latency misses it are masked "
                         "out of that round's averaging and fall back to "
                         "their local step")
    ap.add_argument("--deadline-backoff", type=float, default=2.0,
                    help="deadline only: exponential readmission backoff "
                         "base — each consecutive miss benches the node "
                         "for 1, b, b², ... rounds")
    ap.add_argument("--k-floor", default="2",
                    help="Ada decay floor: an int, or 'one_peer' for the "
                         "time-varying one-peer exponential family")
    ap.add_argument("--gamma-k", type=float, default=None,
                    help="Ada decay rate per epoch (default: the paper's "
                         "0.02; 1.0 is its ResNet50 @ 1008 GPUs setting)")
    ap.add_argument("--consensus-target", type=float, default=None,
                    help="close the Ada loop: step the schedule down a rung "
                         "whenever measured consensus distance falls to this "
                         "fraction of its initial value (d_ada only)")
    ap.add_argument("--consensus-every", type=int, default=1,
                    help="consensus-distance probe cadence in steps")
    ap.add_argument("--consensus-spike", type=float, default=None,
                    help="non-monotone ladder: walk the closed-loop "
                         "schedule back UP to a denser rung whenever a "
                         "probed Ξ_t spikes past this multiple of the "
                         "phase's running peak (crash, deadline storm, "
                         "join; ~3.0 is a good start; needs "
                         "--consensus-target)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--steps-per-epoch", type=int, default=10)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--per-node-batch", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--lr-scaling", default="sqrt", choices=["none", "linear", "sqrt"])
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adamw", "lars"])
    ap.add_argument("--mesh", default="2,2", help="data,model (CPU uses host devices)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir "
                         "(crash-consistent: restores params, optimizer, "
                         "controller phase/rung/logs, and membership "
                         "tracking; fault realizations are pure fn(seed, "
                         "step), so the continued run is bit-identical to "
                         "an uninterrupted one)")
    ap.add_argument("--telemetry", default="",
                    help="stream structured run telemetry (JSONL) to this "
                         "path: per-step spans, comm-bytes counters, "
                         "loss/xi/grad-norm gauges, streamed DBench "
                         "variance, and controller/membership/checkpoint "
                         "events; inspect with "
                         "python -m repro.telemetry summarize PATH "
                         "(with --resume the file is appended, and "
                         "counters continue from the checkpoint)")
    ap.add_argument("--metrics-every", type=int, default=10,
                    help="gauge/variance emission cadence in steps "
                         "(with --telemetry; spans and counters are "
                         "per-step)")
    ap.add_argument("--profile-dir", default="",
                    help="write a JAX profiler trace of --profile-steps to "
                         "this directory (TensorBoard/xprof), with the "
                         "optimized HLO text of each step executable under "
                         "step_hlo/, whose op_name metadata names the "
                         "device scopes the trace's events lack")
    ap.add_argument("--profile-steps", default="1:3",
                    help="A:B, the steps A up to (not including) B that "
                         "--profile-dir traces; the default skips step 0, "
                         "which compiles")
    args = ap.parse_args(argv)
    profile_steps = None
    if args.profile_dir:
        try:
            profile_steps = tuple(int(x) for x in args.profile_steps.split(":"))
        except ValueError:
            profile_steps = ()
        if len(profile_steps) != 2 or not 0 <= profile_steps[0] < profile_steps[1]:
            raise SystemExit(
                f"--profile-steps must be A:B with 0 <= A < B, got {args.profile_steps!r}"
            )

    from repro.core.dsgd import make_topology
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.optim.schedules import lr_scale
    from repro.optim.sgd import get_optimizer

    shape = tuple(int(x) for x in args.mesh.split(","))
    need, found = shape[0] * shape[1], len(jax.devices())
    if found < need:
        backend = jax.default_backend()
        hint = (
            f" — set XLA_FLAGS=--xla_force_host_platform_device_count={need}"
            if backend == "cpu" else ""
        )
        raise SystemExit(
            f"mesh {shape} needs {need} devices but only {found} {backend} "
            f"device(s) present{hint}"
        )
    mesh = make_mesh(shape, ("data", "model"))
    cfg = build_config(args.arch, reduced=args.reduced, layers=args.layers)
    g = shape[0]
    if args.k_floor == "one_peer":
        k_floor = "one_peer"
    else:
        try:
            k_floor = int(args.k_floor)
        except ValueError:
            raise SystemExit(
                f"--k-floor must be an integer or 'one_peer', got {args.k_floor!r}"
            )
    from repro.core.faults import make_fault_model

    join_steps = (
        tuple(int(x) for x in args.fault_join_steps.split(",") if x.strip())
        or None
    )
    fault_model = make_fault_model(
        args.fault_model, g, rate=args.fault_rate, seed=args.fault_seed,
        down_steps=args.fault_down_steps, k=args.fault_k,
        drain_steps=args.fault_drain_steps, join_steps=join_steps,
        enumerate_programs=args.fault_enumerate,
        spare_ranks=args.spare_ranks,
        deadline_ms=args.gossip_deadline_ms,
        deadline_backoff=args.deadline_backoff,
    )
    topo = make_topology(
        args.topology, g, k_floor=k_floor, gamma_k=args.gamma_k,
        consensus_target=args.consensus_target,
        consensus_spike=args.consensus_spike,
        consensus_probe_every=args.consensus_every,
        fault_model=fault_model,
    )
    recorder = None
    if args.telemetry:
        from repro.telemetry import JsonlSink, MetricsRecorder

        recorder = MetricsRecorder(
            sinks=[JsonlSink(args.telemetry, append=args.resume)],
            metrics_every=args.metrics_every, record_spans=True,
        )
    trainer = SPMDTrainer(
        cfg, mesh, topo, get_optimizer(args.optimizer), collect_norms=True,
        mixing=args.mixing, mix_every=args.mix_every,
        mix_rounds=args.mix_rounds, hub_balance=args.hub_balance,
        fused_apply=args.fused_apply,
        bucket_mb=args.bucket_mb, telemetry=recorder,
    )
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(abstract_params(trainer.defs))
    )
    print(f"{args.arch}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, {n_params:,} params per node")
    if recorder is not None:
        run = {
            "engine": "spmd",
            "config": {k: v for k, v in sorted(vars(args).items())},
            "topology": topo.describe(),
            "mesh": {str(k): int(v) for k, v in dict(mesh.shape).items()},
            "seed": 0,
            "resumed": bool(args.resume),
        }
        try:  # provenance only — absent git must not block a run
            import subprocess

            rev = subprocess.run(
                ["git", "rev-parse", "HEAD"], capture_output=True,
                text=True, timeout=5,
            )
            if rev.returncode == 0:
                run["git"] = rev.stdout.strip()
        except Exception:
            pass
        recorder.manifest(run)
    # report the apply path the step will ACTUALLY take: fused_apply falls
    # back to the interpreter for non-PPermute programs (complete, dense)
    apply_mode = "interpreter"
    if args.fused_apply and trainer._use_fused(trainer._program_at(0, 0)):
        apply_mode = "fused-pallas"
    elif args.fused_apply:
        apply_mode = "interpreter (program not fused-eligible)"
    if trainer._bucketed:
        apply_mode += f" | bucketed {args.bucket_mb}MiB"
    print(topo.describe(), "| mesh", dict(mesh.shape), "| mixing", args.mixing,
          "| engine", "shard_map" if trainer.use_shard_map else "stacked",
          "| rounds", args.mix_rounds, "| apply", apply_mode)
    n_progs = len(trainer.precompile_programs(args.steps // args.steps_per_epoch + 1))
    print(f"{n_progs} distinct mixing program(s) over the run")
    state = trainer.init_state(jax.random.PRNGKey(0))
    start_step = 0
    if args.resume:
        if not args.ckpt_dir:
            raise SystemExit("--resume requires --ckpt-dir")
        from repro.checkpoint import load_checkpoint, load_checkpoint_extra

        restored, start_step = load_checkpoint(
            args.ckpt_dir, {"p": state.params, "o": state.opt_state}
        )
        trainer.restore_extra(load_checkpoint_extra(args.ckpt_dir, start_step) or {})
        state = TrainState(restored["p"], restored["o"], start_step)
        trainer.telemetry.event(
            "checkpoint_restore", int(start_step), data={"dir": args.ckpt_dir}
        )
        print(f"resumed from {args.ckpt_dir} at step {start_step}")
    src = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq, seed=0)
    scale = lr_scale(
        args.lr_scaling, global_batch=g * args.per_node_batch,
        base_batch=max(g * args.per_node_batch, 1), graph_degree=topo.degree_at(0),
    )
    losses, step_seconds = [], []
    tracing = False
    for t in range(start_step, args.steps):
        if profile_steps and t == profile_steps[0]:
            _start_profile(args.profile_dir)
            tracing = True
        batch = {k: jnp.asarray(v) for k, v in src.stacked(g, t, args.per_node_batch).items()}
        epoch = t // args.steps_per_epoch
        t0 = time.perf_counter()
        state, loss, norms = trainer.train_step(state, batch, args.lr * scale, epoch=epoch)
        jax.block_until_ready((state.params, loss))
        step_seconds.append(time.perf_counter() - t0)
        losses.append(np.asarray(loss).reshape(-1))
        if t % 5 == 0 or t == args.steps - 1:
            print(f"step {t:4d} k={topo.degree_at(epoch, t)} loss={float(loss.mean()):.4f} "
                  f"spread={float(loss.max() - loss.min()):.4f}")
        if args.ckpt_dir and args.ckpt_every and (t + 1) % args.ckpt_every == 0:
            from repro.checkpoint import save_checkpoint

            save_checkpoint(
                args.ckpt_dir, t + 1,
                {"p": state.params, "o": state.opt_state},
                extra=trainer.snapshot_extra(),
            )
            trainer.telemetry.event(
                "checkpoint_save", t + 1, data={"dir": args.ckpt_dir}
            )
        if tracing and t + 1 in (profile_steps[1], args.steps):
            _stop_profile(args.profile_dir, trainer)
            tracing = False
    print(f"{len(step_seconds)} steps in {sum(step_seconds):.1f}s")
    paths = trainer.telemetry.totals
    print("attention blocks traced into the step, by path: " + ", ".join(
        f"{name.removeprefix('attention.path.')} {paths.get(name, 0)}"
        for name in profile.COUNTERS if name.startswith("attention.path.")
    ))
    print(f"WKV blocks traced into the step, chunked: {paths.get('wkv.chunked', 0)}")
    if trainer.round_ms:
        ms = np.asarray(trainer.round_ms)
        line = (f"round trace: median {np.median(ms):.1f}ms "
                f"p95 {np.percentile(ms, 95):.1f}ms")
        if trainer._deadline_ms is not None:
            line += (f" | measured overruns "
                     f"{trainer.deadline_overruns}/{len(ms)} "
                     f"(deadline {trainer._deadline_ms}ms; masks stay seeded)")
        print(line)
    if topo.controller is not None:
        ctl = topo.controller
        rungs = " -> ".join(str(ctl.ladder[r]) for _, r in [(0, 0)] + ctl.transitions)
        print(
            f"consensus controller: xi0={ctl.xi0} rungs {rungs} "
            f"handoff_step={ctl.handoff_step}"
        )
    if args.telemetry:
        trainer.telemetry.close()
        print(f"telemetry: {args.telemetry} "
              f"(python -m repro.telemetry summarize {args.telemetry})")
    return RunResult(trainer, state, losses, step_seconds, args.lr * scale)


if __name__ == "__main__":
    use_repo_compile_cache()
    main()
