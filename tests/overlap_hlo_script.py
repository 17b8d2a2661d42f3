"""Subprocess body for test_spmd.py: bucketed-overlap lowering + probe fold.

Locks in the simulator's overlap-scheduling acceptance bar:
  1. a single per-bucket executor (``build_bucket_step`` under GSPMD)
     carries its gossip permutes AND the optimizer compute in the SAME
     executable — the dispatch-pipelining evidence: bucket i's permutes
     have no dependency on bucket i+1's compute, only the tiny Ξ² token
     chains them — with no all-gather and at most the fold's one
     all-reduce;
  2. the Ξ_t probe fold removes the standalone probe executable from a
     closed-loop run: with ``bucket_mb`` set, ``consensus_distance_jit``
     runs only for the very first probe (no fold exists yet); every later
     probe reads the token accumulated inside the bucket dispatches, and
     the controller sees the same signal either way.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.buckets import build_bucket_step
from repro.core.graphs import Ring
from repro.core.schedule import compile_graph
from repro.launch.hlo_analysis import collective_counts
from repro.launch.mesh import make_mesh
from repro.optim.sgd import sgd

N = 8
mesh = make_mesh((N,), ("gossip",))

prog = compile_graph(Ring(N))
rng = np.random.default_rng(0)

# --- 1. per-bucket executor: permutes + compute in ONE executable -----------
WIDTH = 96
lead2 = NamedSharding(mesh, P("gossip", None))
rep = NamedSharding(mesh, P())
gvec = NamedSharding(mesh, P("gossip"))
step = jax.jit(
    build_bucket_step(prog, hyper=sgd(momentum=0.9).hyper, has_momentum=True),
    in_shardings=(lead2, lead2, lead2, rep, gvec),
    out_shardings=(lead2, lead2, gvec),
)
theta = jnp.asarray(rng.normal(size=(N, WIDTH)).astype(np.float32))
mom = jnp.asarray(rng.normal(size=(N, WIDTH)).astype(np.float32))
grad = jnp.asarray(rng.normal(size=(N, WIDTH)).astype(np.float32))
tok = jnp.zeros((N,), jnp.float32)
args = (theta, mom, grad, jnp.float32(0.05), tok)
counts = collective_counts(step, *args)
assert counts.get("collective-permute", 0) == len(prog.ops), counts
assert counts.get("all-gather", 0) == 0, counts
assert counts.get("all-reduce", 0) <= 1, counts  # the fold's mean, nothing else
compiled = step.lower(*args).compile().as_text()
assert "collective-permute" in compiled
assert any(op in compiled for op in ("fusion", "subtract", "multiply")), (
    "executor lost its compute: permutes were split into their own module"
)
print(f"per-bucket executor: {len(prog.ops)} permutes + optimizer compute "
      "in one executable, no all-gather")

# --- 2. probe fold: no standalone probe executable in closed-loop runs ------
from repro.core import consensus
from repro.core.dsgd import make_topology
from repro.core.simulator import DecentralizedSimulator

_orig_probe = consensus.consensus_distance_jit


def _run_closed_loop(bucket_mb):
    calls = []
    consensus.consensus_distance_jit = lambda p: calls.append(1) or _orig_probe(p)
    try:
        topo = make_topology("d_ada", N, k0=4, k_floor="one_peer",
                             consensus_target=0.6)
        sim = DecentralizedSimulator(
            lambda p, b: jnp.mean((p["w"] - b["t"]) ** 2),
            sgd(momentum=0.9), topo, bucket_mb=bucket_mb,
        )
        state = sim.init({"w": jnp.zeros((24,))})
        r = np.random.default_rng(0)
        for t in range(12):
            tgt = jnp.asarray(r.normal(size=(N, 24)).astype(np.float32))
            state, _, _ = sim.train_step(state, {"t": tgt}, 0.4 * 0.8 ** t,
                                         epoch=t // 5)
        return len(calls), topo.controller.trace
    finally:
        consensus.consensus_distance_jit = _orig_probe


mono_calls, mono_trace = _run_closed_loop(None)
fold_calls, fold_trace = _run_closed_loop(16 * 4 / (1 << 20))  # 16-elem buckets
assert mono_calls == len(mono_trace) and mono_calls > 1, (mono_calls, mono_trace)
# only the step-0 probe predates the first fold; every later one is folded
assert fold_calls == 1, fold_calls
assert [s for s, _, _ in fold_trace] == [s for s, _, _ in mono_trace]
xi_err = max(
    abs(a - b) for (_, a, _), (_, b, _) in zip(fold_trace, mono_trace)
)
assert xi_err < 1e-5, xi_err
print(f"probe fold: {mono_calls} standalone probes -> {fold_calls}, "
      f"same controller signal (max xi err {xi_err:.1e})")

print("OVERLAP_HLO_OK")
