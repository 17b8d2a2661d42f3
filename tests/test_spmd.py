"""Multi-device SPMD engine tests (run in subprocesses: they need 8 host
devices, while the rest of the suite runs single-device)."""
import os
import re
import subprocess
import sys

import pytest

_HERE = os.path.dirname(__file__)


def _run(script, *args, timeout=900):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(_HERE, script), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}\nstdout:\n{r.stdout[-1000:]}"
    return r.stdout


def _extract(out, key):
    m = re.search(rf"{key}=([\d.e+-]+)", out)
    assert m, out
    return float(m.group(1))


def test_star_lowers_to_permutes_only():
    """PR-3 acceptance (fast, mixing-only): the edge-colored star and an
    irregular graph lower to collective-permutes with ZERO all-gathers
    (``assert_no_all_gather``), and the fused Pallas shard apply matches
    the dense oracle on 8 host devices."""
    out = _run("star_hlo_script.py", timeout=300)
    assert "STAR_HLO_OK" in out


def test_bucketed_overlap_lowering_and_probe_fold():
    """The simulator's overlap-scheduled gossip (fast, lowering-level): one
    per-bucket executor carries its permutes AND the optimizer compute in
    the SAME executable, with zero all-gathers (the dispatch-pipelining
    evidence — only the Ξ² token chains buckets); and the probe fold
    removes every standalone ``consensus_distance_jit`` dispatch after
    the first from a closed-loop run without changing the controller
    signal."""
    out = _run("overlap_hlo_script.py", timeout=300)
    assert "OVERLAP_HLO_OK" in out


@pytest.mark.slow
def test_fault_injection_matches_simulator():
    """Resilience subsystem: both engines draw the SAME seeded fault
    realizations (transient dropout; permanent crash + elastic rejoin; a
    2-node concurrent crash composed over runtime masks; a preemption
    drain-then-leave), agree on final parameters to float32 round-off,
    compile nothing beyond the pre-enumerated program set, and transient
    AND composed-concurrent runs' executable counts equal the fault-free
    run's."""
    out = _run("faults_spmd_script.py", timeout=900)
    assert "FAULTS_EQUIV_OK" in out
    assert _extract(out, "MAXDIFF") < 5e-5


@pytest.mark.slow
def test_resume_roundtrip_cli():
    """Crash-consistent resume through the real launcher: an interrupted
    faulted closed-loop run continued with --resume produces a step-8
    checkpoint BIT-identical to the uninterrupted run's (arrays + the
    controller/membership extra payload)."""
    out = _run("resume_cli_script.py", timeout=900)
    assert "RESUME_ROUNDTRIP_OK" in out


@pytest.mark.slow
def test_closed_loop_ada_matches_simulator():
    """Consensus-distance-triggered Ada (8 steps): both engines feed the
    controller the same measured signal, pick the SAME graph sequence
    (identical transition logs), hand off to one-peer at a measured step,
    agree to float32 round-off, and compile nothing beyond the ladder.
    ~50s on an idle 2-CPU box but up to ~10x under pytest contention —
    slow tier, like the other trainer-level equivalence runs."""
    out = _run("consensus_equivalence_script.py", timeout=900)
    assert "CONSENSUS_EQUIV_OK" in out
    assert _extract(out, "MAXDIFF") < 5e-5


@pytest.mark.slow
@pytest.mark.parametrize(
    "topo",
    [
        "d_ring", "d_exponential", "c_complete", "d_complete",
        # time-varying / irregular families ride the same GossipProgram path
        "d_one_peer_exp", "d_random_matching", "d_star",
    ],
)
def test_spmd_engine_matches_simulator(topo):
    """Production engine (compiled GossipProgram) == dense-matrix oracle."""
    out = _run("spmd_equivalence_script.py", topo, "ppermute")
    assert _extract(out, "MAXDIFF") < 5e-5
    assert _extract(out, "LOSSDIFF") < 5e-5


@pytest.mark.slow
def test_spmd_dense_mixing_matches_simulator():
    """The paper-faithful all-gather mixing path agrees too."""
    out = _run("spmd_equivalence_script.py", "d_ring", "dense")
    assert _extract(out, "MAXDIFF") < 5e-5


@pytest.mark.slow
@pytest.mark.parametrize("topo", ["d_star", "d_one_peer_exp"])
def test_spmd_fused_apply_matches_simulator(topo):
    """The fused Pallas optimizer+gossip kernel == dense-matrix oracle at
    trainer level (edge-colored star + time-varying one-peer)."""
    out = _run("spmd_equivalence_script.py", topo, "fused")
    assert _extract(out, "MAXDIFF") < 5e-5
    assert _extract(out, "LOSSDIFF") < 5e-5


@pytest.mark.slow
def test_mini_dryrun_lowers_and_compiles():
    """A miniature of launch/dryrun.py: production-mesh pattern on 8 devices."""
    out = _run("spmd_dryrun_script.py")
    assert "MINI_DRYRUN_OK" in out


@pytest.mark.slow
def test_manual_ep_matches_gather_oracle():
    """Hand-scheduled expert parallelism (one psum/layer) == GSPMD dispatch."""
    out = _run("spmd_manual_ep_script.py")
    assert "manual EP == gather oracle OK" in out
    assert "grads OK" in out
