"""The static-analysis pipeline (src/repro/analysis/).

Clean-path coverage plus the seeded mutation suite: for every pass, a
deliberately corrupted artifact (non-stochastic row, colliding permute
pair, corrupted bucket-layout caches, an extra retrace, a forbidden
all-gather, an unbounded dispatch loop, an over-budget kernel signature)
must be CAUGHT — a verifier nobody has seen fail is itself unverified.
Also pins the f8 dtype-width regression in the HLO wire accounting and
the structured CollectiveReport (PR 10 satellites).
"""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.analysis.budget import (
    SMEM_BUDGET_BYTES, VMEM_BUDGET_BYTES, check_kernel_budget,
    kernel_cell_cost, verify_program_budget,
)
from repro.analysis.collectives import (
    assert_signatures_consistent, collective_signature, lint_dispatch_loops,
    lint_engine_sources, lint_no_forbidden,
)
from repro.analysis.invariants import (
    verify_bench_payload, verify_bucket_layout, verify_degraded,
    verify_program, verify_topology,
)
from repro.analysis.recompile import (
    assert_executables_preenumerated, assert_no_retrace, used_program_keys,
    watch_retrace,
)
from repro.analysis.report import (
    BudgetViolation, CollectiveViolation, InvariantViolation, PassReport,
    RetraceError, run_pass,
)
from repro.core.buckets import BucketLayout
from repro.core.dsgd import make_topology
from repro.core.faults import make_fault_model
from repro.core.graphs import Ring, Star, from_adjacency
from repro.core.schedule import GossipProgram, compile_graph, dense_program
from repro.core.simulator import DecentralizedSimulator
from repro.launch.hlo_analysis import (
    _dtype_width, assert_no_all_gather, collective_counts,
)
from repro.optim.sgd import sgd


def _quad_loss(p, b):
    return jnp.mean((b - p["w"]) ** 2)


def _random_connected_graph(n, seed):
    rng = np.random.default_rng(seed)
    edges = set()
    perm = rng.permutation(n)
    for a, b in zip(perm[:-1], perm[1:]):
        edges.add((min(a, b), max(a, b)))
    for _ in range(int(rng.integers(0, n))):
        i, j = rng.integers(0, n, size=2)
        if i != j:
            edges.add((min(i, j), max(i, j)))
    return from_adjacency(sorted((int(i), int(j)) for i, j in edges))


# ---------------------------------------------------------------------------
# Pass 1 — program verifier: clean path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo_name", ["d_ring", "d_star", "d_one_peer_exp"])
def test_verifier_accepts_registered_families(topo_name):
    topo = make_topology(topo_name, 8)
    assert verify_topology(topo, n_epochs=2) >= 1


def test_verifier_accepts_degraded_and_elastic_realizations():
    fm = make_fault_model("dropout", 8, rate=0.3, seed=3, spare_ranks=2)
    topo = make_topology("d_ring", 8, fault_model=fm)
    verify_topology(topo, n_epochs=1, fault_steps=12)


# ---------------------------------------------------------------------------
# Pass 1 — mutation suite
# ---------------------------------------------------------------------------

def test_mutation_non_stochastic_row_is_caught():
    prog = compile_graph(Ring(8))
    bad = dataclasses.replace(prog, self_weight=0.9)  # rows now sum to > 1
    with pytest.raises(InvariantViolation, match="row .* sums"):
        verify_program(bad)


def test_mutation_colliding_permute_pair_is_caught():
    prog = compile_graph(Ring(8))
    op = prog.ops[0]
    perm = list(op.perm)
    s0, _ = perm[0]
    _, d1 = perm[1]
    perm[0] = (s0, d1)  # two sends now land on one receiver
    bad_op = dataclasses.replace(op, perm=tuple(perm), offset=None)
    bad = dataclasses.replace(prog, ops=(bad_op,) + prog.ops[1:])
    with pytest.raises(InvariantViolation, match="duplicate destination"):
        verify_program(bad)


def test_mutation_swapped_pair_breaks_offset_contract():
    prog = compile_graph(Ring(8))
    op = prog.ops[0]
    assert op.offset is not None  # ring compiles to circulant shifts
    perm = list(op.perm)
    (s0, d0), (s1, d1) = perm[0], perm[1]
    perm[0], perm[1] = (s0, d1), (s1, d0)  # still a bijection, wrong shift
    bad_op = dataclasses.replace(op, perm=tuple(perm))
    bad = dataclasses.replace(prog, ops=(bad_op,) + prog.ops[1:])
    with pytest.raises(InvariantViolation, match="offset"):
        verify_program(bad)


def test_mutation_overlapping_bucket_segments_are_caught():
    layout = BucketLayout((1000, 24, 1000), 256)
    verify_bucket_layout(layout, sizes=(1000, 24, 1000))  # clean first
    segs = [list(b) for b in layout.segments]
    li, start, stop = segs[1][0]
    segs[1][0] = (li, max(0, start - 16), stop)  # overlaps bucket 0's tail
    object.__setattr__(layout, "_segments", tuple(tuple(b) for b in segs))
    with pytest.raises(InvariantViolation):
        verify_bucket_layout(layout, sizes=(1000, 24, 1000))


def test_mutation_non_monotonic_bounds_are_caught():
    layout = BucketLayout((512, 512), 256)
    bounds = list(layout.bounds)
    bounds[1], bounds[2] = bounds[2], bounds[1]
    object.__setattr__(layout, "_bounds", bounds)
    with pytest.raises(InvariantViolation, match="increasing"):
        verify_bucket_layout(layout)


# ---------------------------------------------------------------------------
# Pass 1 — property tests: degraded realizations on random connected graphs
# ---------------------------------------------------------------------------

@given(st.integers(min_value=0, max_value=10_000),
       st.integers(min_value=4, max_value=10))
@settings(max_examples=25, deadline=None)
def test_degraded_matrix_always_verifies(seed, n):
    """Any boolean alive × symmetric link realization of a random connected
    graph's program passes the verifier — ``degraded_matrix`` is closed
    over the invariants (row-stochastic, dead-rank identity, symmetry)."""
    prog = compile_graph(_random_connected_graph(n, seed))
    rng = np.random.default_rng(seed + 1)
    alive = rng.random(n) > 0.35
    link = rng.random((n, n)) > 0.2
    link = np.asarray(link & link.T) | np.eye(n, dtype=bool)
    verify_program(prog)
    verify_degraded(prog, alive)
    verify_degraded(prog, alive, link)


@given(st.integers(min_value=0, max_value=10_000))
@settings(max_examples=10, deadline=None)
def test_drain_boost_realizations_verify(seed):
    """Float (drain-boost) masks stay row-stochastic and verify too."""
    n = 8
    prog = compile_graph(_random_connected_graph(n, seed))
    boost = np.ones(n)
    boost[int(np.random.default_rng(seed).integers(n))] = 1.5
    verify_degraded(prog, boost)


# ---------------------------------------------------------------------------
# Pass 2 — collective linter
# ---------------------------------------------------------------------------

_PERMUTE_HLO = """\
ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64] parameter(0)
  ROOT %cp = f32[64] collective-permute(%p0), channel_id=1, source_target_pairs={{0,1},{1,0}}
}
"""

_PERMUTE_HLO_SWAPPED = """\
ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64] parameter(0)
  ROOT %cp = f32[64] collective-permute(%p0), channel_id=1, source_target_pairs={{0,1},{1,2}}
}
"""

_ALLGATHER_HLO = """\
ENTRY %main (p0: f32[64]) -> f32[512] {
  %p0 = f32[64] parameter(0)
  ROOT %ag.leak = f32[512] all-gather(%p0), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
}
"""


def test_collective_signature_reads_rendezvous_identity():
    sig = collective_signature(_PERMUTE_HLO)
    assert len(sig) == 1
    kind, attrs = sig[0]
    assert kind == "collective-permute" and "{0,1}" in attrs
    # channel ids are per-module noise, not rendezvous identity
    assert "channel_id" not in attrs


def test_mutation_diverging_signatures_are_caught():
    assert_signatures_consistent({
        "a": collective_signature(_PERMUTE_HLO),
        "b": collective_signature(_PERMUTE_HLO),
    })
    with pytest.raises(CollectiveViolation, match="diverge"):
        assert_signatures_consistent({
            "masked": collective_signature(_PERMUTE_HLO),
            "unmasked": collective_signature(_PERMUTE_HLO_SWAPPED),
        })


def test_mutation_all_gather_leak_is_caught_with_op_name():
    lint_no_forbidden(_PERMUTE_HLO)  # clean path
    with pytest.raises(CollectiveViolation, match="ag.leak"):
        lint_no_forbidden(_ALLGATHER_HLO)
    # the refactored assert keeps raising AND names the op (satellite)
    with pytest.raises(AssertionError, match="ag.leak"):
        assert_no_all_gather(_ALLGATHER_HLO)


_UNBOUNDED_LOOP_SRC = """\
def dispatch(layout, fn, parts):
    out = []
    for b, w in enumerate(layout.widths):
        out.append(fn(parts[b], w))
    return out
"""

_BOUNDED_LOOP_SRC = """\
import collections, jax

def dispatch(layout, fn, parts):
    out, window = [], collections.deque()
    for b, w in enumerate(layout.widths):
        if len(window) >= MAX_INFLIGHT_BUCKETS:
            jax.block_until_ready(window.popleft())
        r = fn(parts[b], w)
        window.append(r)
        out.append(r)
    return out
"""

_HOST_SEGMENT_SRC = """\
def slice_up(layout, leaves):
    out = []
    for segs in layout.segments:
        out.append([leaves[li][a:b] for li, a, b in segs])
    return out
"""


def test_mutation_unbounded_dispatch_loop_is_caught():
    findings = lint_dispatch_loops(_UNBOUNDED_LOOP_SRC, "fake.py")
    assert len(findings) == 1 and "MAX_INFLIGHT_BUCKETS" in findings[0].message
    assert lint_dispatch_loops(_BOUNDED_LOOP_SRC, "fake.py") == []
    # host-side slicing loops launch nothing and must stay unflagged
    assert lint_dispatch_loops(_HOST_SEGMENT_SRC, "fake.py") == []


def test_engine_dispatch_sources_are_bounded():
    assert lint_engine_sources() == []


# ---------------------------------------------------------------------------
# Pass 3 — recompile sanitizer
# ---------------------------------------------------------------------------

def test_assert_no_retrace_catches_shape_driven_recompile():
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(3))  # warm
    with assert_no_retrace("warm shape"):
        f(jnp.ones(3))
    with pytest.raises(RetraceError, match="mid-run recompile"):
        with assert_no_retrace("mutated shape"):
            f(jnp.ones(4))  # the seeded corruption: a new avals signature


def test_watch_retrace_counts_and_allowances():
    g = jax.jit(lambda x: x - 1)
    with watch_retrace() as stats:
        g(jnp.ones(5))
    assert stats.traces >= 1 and stats.compiles >= 1 and not stats.clean
    with assert_no_retrace("declared warmup", allow_traces=4, allow_compiles=4):
        jax.jit(lambda x: x + 3)(jnp.ones(6))


def test_preenumeration_rejects_stray_and_vacuous_caches():
    topo = make_topology("d_ring", 4)
    allowed_key = topo.distinct_programs()[0][1].cache_key
    fake = types.SimpleNamespace(
        _step_cache={(allowed_key, "faulty"): None, ("__grads__", 4): None},
        topology=topo,
    )
    assert assert_executables_preenumerated(fake) == {allowed_key}
    fake._step_cache[("rogue", 4, "deadbeef")] = None
    with pytest.raises(RetraceError, match="beyond the pre-enumerated"):
        assert_executables_preenumerated(fake)
    empty = types.SimpleNamespace(_step_cache={}, topology=topo)
    with pytest.raises(RetraceError, match="vacuous"):
        assert_executables_preenumerated(empty)
    assert assert_executables_preenumerated(empty, require_used=False) == set()


def test_used_program_keys_unwraps_engine_taxonomy():
    key = ("d_ring", 8, "abc")
    cache = {
        key: 1,                                     # bare program
        (key, "faulty"): 1,                         # fault signature
        ("__bucket__", key, 128, True, False): 1,   # bucketed executable
        ("__local__", 8): 1,                        # internal closure
        (("__local__", 8), "faulty"): 1,
        None: 1,                                    # programless trainer key
    }
    assert used_program_keys(cache) == {key}


def test_simulator_debug_mode_runs_clean():
    topo = make_topology("d_ring", 4)
    sim = DecentralizedSimulator(
        _quad_loss, sgd(momentum=0.9), topo, debug_no_retrace=True
    )
    state = sim.init({"w": jnp.zeros(3)})
    for t in range(4):  # warm + guarded steady state: must not raise
        b = jax.random.normal(jax.random.PRNGKey(t), (4, 2, 3))
        state, *_ = sim.train_step(state, b, 0.05)
    assert_executables_preenumerated(sim)


# ---------------------------------------------------------------------------
# Pass 4 — kernel budget checker
# ---------------------------------------------------------------------------

def test_budget_accepts_documented_defaults():
    cost = check_kernel_budget(3, 1024)
    assert cost["smem_bytes"] == 8 + 2 * 4 * 4
    assert cost["vmem_tiles"] == 3 + 3 + 2 and cost["aligned"]
    assert kernel_cell_cost(3, 1024, has_momentum=False)["vmem_tiles"] == 6


def test_mutation_oversized_smem_row_is_caught():
    deg = (SMEM_BUDGET_BYTES // 8) + 8
    with pytest.raises(BudgetViolation, match="SMEM"):
        check_kernel_budget(deg, 1024)


def test_mutation_oversized_vmem_tile_is_caught_compiled_only():
    block = VMEM_BUDGET_BYTES  # tiles * 4 * block far over budget
    with pytest.raises(BudgetViolation, match="VMEM"):
        check_kernel_budget(2, block)
    # the interpreter's host-loop grid is exempt (2^20 default block)
    assert check_kernel_budget(2, 1 << 20, interpret=True)["aligned"]


def test_budget_guard_is_wired_into_kernel_dispatch():
    from repro.kernels.gossip_update import fused_apply_stacked

    prog = compile_graph(Star(8))
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    trees = tuple({"w": jax.random.normal(kk, (8, 32))} for kk in k)
    with pytest.raises(BudgetViolation, match="non-positive"):
        fused_apply_stacked(prog, *trees, lr=0.1, beta=0.9, block=-4)


def test_program_budget_covers_tables_and_skips_dense():
    ring = compile_graph(Ring(8))
    assert verify_program_budget(ring)["smem_bytes"] <= SMEM_BUDGET_BYTES
    assert verify_program_budget(dense_program(Star(8))) is None


# ---------------------------------------------------------------------------
# Satellite — f8 dtype widths + structured CollectiveReport
# ---------------------------------------------------------------------------

def test_f8_dtype_widths_and_fallback():
    for dt in ("f8e4m3", "f8e4m3fn", "f8e5m2", "f8e4m3fnuz", "f8e5m2fnuz"):
        assert _dtype_width(dt) == 1
    assert _dtype_width("f8e8m0fnu") == 1   # unknown f8 variant: bit fallback
    assert _dtype_width("s4") == 1          # sub-byte rounds up
    assert _dtype_width("bf16") == 2 and _dtype_width("u64") == 8
    assert _dtype_width("pred") == 1


def test_f8_collective_wire_bytes_regression():
    hlo = _PERMUTE_HLO.replace("f32", "f8e4m3fn")
    report = collective_counts(hlo)
    # 64 one-byte elements on the wire — the old table billed 4 B/elt
    assert report.wire_bytes["collective-permute"] == 64
    assert report.total == 1


def test_collective_report_is_structured():
    report = collective_counts(_ALLGATHER_HLO)
    assert report["all-gather"] == 1
    assert report.op_names["all-gather"] == ("ag.leak",)
    assert report.offending(("all-gather",)) == {"all-gather": ("ag.leak",)}
    assert report.offending(("all-reduce",)) == {}
    clean = assert_no_all_gather(_PERMUTE_HLO)  # returns the report now
    assert clean["collective-permute"] == 1 and clean.total == 1


# ---------------------------------------------------------------------------
# Satellite — bench payload schema gate
# ---------------------------------------------------------------------------

def test_bench_payload_gate():
    verify_bench_payload("step_time", {"ring/n8": {"mean_ms": 1.0}})
    with pytest.raises(InvariantViolation, match="non-empty dict"):
        verify_bench_payload("step_time", [])
    with pytest.raises(InvariantViolation, match="not a dict"):
        verify_bench_payload("step_time", {"ring/n8": 3.0})
    with pytest.raises(InvariantViolation, match="key"):
        verify_bench_payload("step_time", {"ring n8!": {"mean_ms": 1.0}})
    with pytest.raises(InvariantViolation, match="JSON"):
        verify_bench_payload("step_time", {"ring/n8": {"x": float("nan")}})


def test_save_bench_section_is_gated(tmp_path, monkeypatch):
    import benchmarks.common as common

    monkeypatch.setattr(common, "BENCH_PATH", str(tmp_path / "BENCH.json"))
    path = common.save_bench_section("step_time", {"ring/n8": {"ms": 2.0}})
    assert "BENCH" in path
    with pytest.raises(InvariantViolation):
        common.save_bench_section("step_time", {"bad key!": {"ms": 2.0}})


# ---------------------------------------------------------------------------
# Report plumbing
# ---------------------------------------------------------------------------

def test_run_pass_collects_findings_per_subject():
    def boom():
        raise InvariantViolation("synthetic")

    report = run_pass("invariants", [("good", lambda: None), ("bad", boom)])
    assert report.checked == 2 and not report.ok
    assert [f.subject for f in report.findings] == ["bad"]
    with pytest.raises(AssertionError, match="synthetic"):
        report.raise_if_failed()
    clean = PassReport("x", checked=3)
    assert clean.ok and "ok" in clean.summary()
