"""``python -m repro.analysis`` — the static-analysis pipeline CLI.

Runs the five passes over every program the benchmarked topology matrix
can emit (ring / star / one-peer-exp / random-matching × fault-free,
transient, permanent-crash, preemption, deadline, join and spare-rank
realizations):

  --invariants   mixing-program IR verifier (stochasticity, bijective
                 permute tables, ghost-rank identity, fusion round
                 conservation, bucket-layout coverage)
  --collectives  HLO collective-deadlock linter (signature consistency
                 across co-executable realizations, all-gather ban,
                 dispatch-window AST lint of the engine sources)
  --recompile    zero-mid-run-recompile sanitizer (live engine run under
                 ``assert_no_retrace`` after warm-up + executable-set
                 pre-enumeration)
  --budget       Pallas kernel SMEM/VMEM budget checker
  --telemetry    telemetry-schema pass: a 2-node smoke run streams every
                 record kind through the schema validator, the rendered
                 summary is checked, and the telemetry-on executable set
                 must equal the telemetry-off one (the recorder is
                 provably free)

``--all`` (the CI entry point) runs everything.  Exit status 1 when any
pass reports findings.
"""
from __future__ import annotations

import argparse
import os
import sys


def _setup_env() -> None:
    """Host-device + platform env, BEFORE jax is imported anywhere."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


N = 8
TOPOS = ("d_ring", "d_star", "d_one_peer_exp", "d_random_matching")


def _fault_variants():
    """(label, builder) for every fault realization family at n=N.

    Builders (not instances) so each subject constructs its own seeded
    model — ``verify_topology`` mutates nothing, but crash models fold
    into ``distinct_programs`` and must not leak between topologies.
    """
    from repro.core.faults import make_fault_model as mk

    return [
        ("fault-free", lambda: None),
        ("dropout", lambda: mk("dropout", N, rate=0.3, seed=3)),
        ("link", lambda: mk("link", N, rate=0.3, seed=4)),
        ("crash", lambda: mk("crash", N, rate=0.5, seed=1, down_steps=6)),
        ("concurrent", lambda: mk("concurrent", N, rate=0.7, seed=1, k=2)),
        ("preempt", lambda: mk("preempt", N, rate=0.6, seed=2, drain_steps=3)),
        ("deadline", lambda: mk("deadline", N, rate=0.4, seed=5)),
        ("join", lambda: mk("join", N, join_steps=(4,))),
        ("spares", lambda: mk("dropout", N, rate=0.3, seed=6, spare_ranks=2)),
    ]


def run_invariants():
    from repro.analysis.invariants import verify_bucket_layout, verify_topology
    from repro.analysis.report import run_pass
    from repro.core.buckets import BucketLayout
    from repro.core.dsgd import make_topology

    subjects = []
    for topo_name in TOPOS:
        for fault_label, build in _fault_variants():
            def thunk(topo_name=topo_name, build=build):
                topo = make_topology(topo_name, N, fault_model=build())
                verify_topology(topo, n_epochs=2, fault_steps=24)

            subjects.append((f"{topo_name} × {fault_label}", thunk))
    # representative bucket layouts: multi-leaf, leaf-straddling, exact-fit,
    # single-bucket and empty-tree edges
    for label, sizes, elems in [
        ("layout multi-leaf", (3072, 1024, 7), 512),
        ("layout straddle", (1000, 24, 1000), 256),
        ("layout exact", (512, 512), 512),
        ("layout single", (5,), 1 << 20),
        ("layout empty", (), 512),
    ]:
        subjects.append(
            (label, lambda s=sizes, e=elems: verify_bucket_layout(
                BucketLayout(s, e), sizes=s))
        )
    return run_pass("invariants", subjects)


def run_collectives():
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.analysis.collectives import (
        assert_signatures_consistent,
        collective_signature,
        lint_engine_sources,
        lint_no_forbidden,
    )
    from repro.analysis.report import run_pass
    from repro.core.dsgd import make_topology
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((N,), ("gossip",))
    x = np.arange(N * 4, dtype=np.float32).reshape(N, 4)
    alive = np.ones((N,), np.float32)

    subjects = []
    seen = set()
    for topo_name in TOPOS:
        topo = make_topology(topo_name, N)
        for _, prog in topo.distinct_programs(2):
            if prog.cache_key in seen:
                continue
            seen.add(prog.cache_key)

            def thunk(prog=prog):
                jb = jax.jit(jax.shard_map(
                    lambda v: prog.apply_shard(v, "gossip"),
                    mesh=mesh, check_vma=False, in_specs=P("gossip"), out_specs=P("gossip"),
                ))
                jm = jax.jit(jax.shard_map(
                    lambda v, a: prog.apply_shard_masked(v, "gossip", a),
                    mesh=mesh, check_vma=False, in_specs=(P("gossip"), P()),
                    out_specs=P("gossip"),
                ))
                if prog.permute_tables() is not None:
                    # colorable programs: masking must not change the
                    # permute schedule, and neither realization may
                    # all-gather on the hot path
                    assert_signatures_consistent({
                        "apply_shard": collective_signature(jb, x),
                        "apply_shard_masked": collective_signature(jm, x, alive),
                    })
                    lint_no_forbidden(jb, x)
                    lint_no_forbidden(jm, x, alive)
                else:
                    # dense/fused fallback: just compile both realizations
                    collective_signature(jb, x)
                    collective_signature(jm, x, alive)

            subjects.append((f"{topo_name}:{prog.name}", thunk))

    report = run_pass("collectives", subjects)
    # AST lint over the engines' dispatch modules
    report.checked += 1
    report.findings.extend(lint_engine_sources())
    return report


def run_recompile():
    import jax
    import jax.numpy as jnp

    from repro.analysis.recompile import (
        assert_executables_preenumerated,
        assert_no_retrace,
    )
    from repro.analysis.report import run_pass
    from repro.core.dsgd import make_topology
    from repro.core.faults import make_fault_model
    from repro.core.simulator import DecentralizedSimulator
    from repro.optim.sgd import sgd

    def _quad_loss(p, b):
        return jnp.mean((b - p["w"]) ** 2)

    def drive(topo_name, fault_model, warm_steps, guard_steps=8):
        topo = make_topology(topo_name, N, fault_model=fault_model)
        sim = DecentralizedSimulator(_quad_loss, sgd(momentum=0.9), topo)
        state = sim.init({"w": jnp.zeros(4)})

        def step(state, t):
            b = jax.random.normal(jax.random.PRNGKey(t), (N, 2, 4))
            state, *_ = sim.train_step(state, b, 0.05)
            return state

        for t in range(warm_steps):
            state = step(state, t)
        with assert_no_retrace(f"{topo_name} steps {warm_steps}..+{guard_steps}"):
            for t in range(warm_steps, warm_steps + guard_steps):
                state = step(state, t)
        assert_executables_preenumerated(sim, n_epochs=2)

    # deterministic fault horizons: crash onset/rejoin derive from the seed,
    # so warm-up provably covers every (program, faulty) combination and the
    # guarded window can demand 0 traces / 0 compiles
    crash = make_fault_model("crash", N, rate=0.5, seed=1, down_steps=4)
    crash_warm = (crash.rejoin_step or 0) + 2 * N
    subjects = [
        ("d_ring fault-free", lambda: drive("d_ring", None, 4)),
        ("d_one_peer_exp fault-free",
         lambda: drive("d_one_peer_exp", None, 8)),
        ("d_ring crash+rejoin", lambda: drive(
            "d_ring",
            make_fault_model("crash", N, rate=0.5, seed=1, down_steps=4),
            crash_warm,
        )),
    ]
    return run_pass("recompile", subjects)


def run_budget():
    from repro.analysis.budget import check_kernel_budget, verify_program_budget
    from repro.analysis.report import run_pass
    from repro.core.dsgd import make_topology

    subjects = []
    seen = set()
    for topo_name in TOPOS:
        topo = make_topology(topo_name, N)
        for _, prog in topo.distinct_programs(2):
            if prog.cache_key in seen:
                continue
            seen.add(prog.cache_key)
            for mode, kw in [("compiled", {}),
                             ("interpret", {"block": 1 << 20, "interpret": True})]:
                subjects.append((
                    f"{topo_name}:{prog.name} [{mode}]",
                    lambda p=prog, kw=kw: verify_program_budget(p, **kw),
                ))
    # the raw dispatch-signature check at the documented defaults
    subjects.append(
        ("defaults deg≤8", lambda: [
            check_kernel_budget(d, 1024) for d in range(9)])
    )
    return run_pass("budget", subjects)


def run_telemetry():
    import jax
    import jax.numpy as jnp

    from repro.analysis.report import run_pass
    from repro.core.dsgd import make_topology
    from repro.core.simulator import DecentralizedSimulator
    from repro.optim.sgd import sgd
    from repro.telemetry import MemorySink, MetricsRecorder
    from repro.telemetry.schema import SchemaError, validate_record
    from repro.telemetry.summarize import render_summary, summarize

    def _quad_loss(p, b):
        return jnp.mean((b - p["w"]) ** 2)

    def _drive(telemetry=None, n=2, steps=6):
        topo = make_topology("d_ring", n)
        sim = DecentralizedSimulator(
            _quad_loss, sgd(momentum=0.9), topo, telemetry=telemetry,
            collect_norms=True,
        )
        state = sim.init({"w": jnp.zeros(4)})
        for t in range(steps):
            b = jax.random.normal(jax.random.PRNGKey(t), (n, 2, 4))
            state, *_ = sim.train_step(state, b, 0.05)
        return sim

    def smoke():
        sink = MemorySink()
        rec = MetricsRecorder(
            sinks=[sink], metrics_every=1, record_spans=True
        )
        rec.manifest({"engine": "simulator", "n": 2})
        _drive(telemetry=rec)
        for r in sink.records:
            validate_record(r)
        kinds = {r["kind"] for r in sink.records}
        missing = {"manifest", "counter", "gauge", "span", "variance"} - kinds
        assert not missing, f"smoke run missing record kinds: {missing}"
        out = render_summary(summarize([dict(r) for r in sink.records]))
        assert "comm MiB" in out and "per-layer variance" in out

    def parity():
        off = _drive()
        on = _drive(telemetry=MetricsRecorder(
            sinks=[MemorySink()], metrics_every=1, record_spans=True
        ))
        k_off = sorted(map(str, off._step_cache))
        k_on = sorted(map(str, on._step_cache))
        assert k_on == k_off, (
            f"telemetry changed the executable set: "
            f"{len(k_off)} -> {len(k_on)}"
        )

    def rejects():
        for bad in (
            {"kind": "nope"},
            {"kind": "counter", "step": 0, "name": "x", "inc": 1},
            {"kind": "gauge", "step": 0, "name": "xi", "value": 1.0,
             "extra": 2},
        ):
            try:
                validate_record(bad)
            except SchemaError:
                continue
            raise AssertionError(f"schema accepted malformed record {bad!r}")

    subjects = [
        ("2-node smoke run + stream validation", smoke),
        ("executable-set parity on/off", parity),
        ("malformed records rejected", rejects),
    ]
    return run_pass("telemetry-schema", subjects)


PASSES = {
    "invariants": run_invariants,
    "collectives": run_collectives,
    "recompile": run_recompile,
    "budget": run_budget,
    "telemetry": run_telemetry,
}


def main(argv=None) -> int:
    _setup_env()
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static-analysis passes over the gossip stack",
    )
    ap.add_argument("--all", action="store_true", help="run every pass")
    for name in PASSES:
        ap.add_argument(f"--{name}", action="store_true")
    args = ap.parse_args(argv)

    selected = [n for n in PASSES if getattr(args, n)]
    if args.all or not selected:
        selected = list(PASSES)

    failed = False
    for name in selected:
        report = PASSES[name]()
        print(report.summary())
        for f in report.findings:
            print(f"  {f}")
        failed = failed or not report.ok
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
