"""Subprocess body for test_schedule.py: the shard interpreter on 8 host
devices.

Checks, for every registered topology family:
  1. ``GossipProgram.apply_shard`` inside a full-manual shard_map equals the
     dense mixing-matrix oracle to <= 1e-5;
  2. the compiled HLO carries exactly the collectives the program promises —
     a circulant graph lowers to ONE collective-permute per offset with no
     all-gather (the no-regression acceptance bar), complete to one
     all-reduce, and only the dense/irregular fallback may all-gather.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.graphs import (
    Complete, Exponential, Ring, RingLattice, Star, Torus,
    one_peer_exponential, random_matching,
)
from repro.core.schedule import (
    AllReduce, GatherRow, PPermute, compile_graph, dense_program,
)
from repro.launch.mesh import make_mesh

N = 8
mesh = make_mesh((N,), ("gossip",))
x = np.random.default_rng(0).normal(size=(N, 4, 3)).astype(np.float32)

graphs = [
    Ring(N), Torus(N), Torus(N, grid=(2, 4)), RingLattice(N, 4),
    Exponential(N), Complete(N), Star(N),
    one_peer_exponential(N, 1), random_matching(N, seed=2),
]
programs = [compile_graph(g) for g in graphs] + [dense_program(Ring(N))]
oracles = [g.mixing_matrix() for g in graphs] + [Ring(N).mixing_matrix()]

failures = []
for prog, w in zip(programs, oracles):
    f = jax.shard_map(
        lambda v: prog.apply_shard(v, "gossip"),
        mesh=mesh, check_vma=False, in_specs=P("gossip"), out_specs=P("gossip"),
    )
    jf = jax.jit(f)
    got = np.asarray(jf(jnp.asarray(x)))
    want = np.einsum("ij,j...->i...", w, x)
    err = float(np.abs(got - want).max())
    hlo = jf.lower(jnp.asarray(x)).compile().as_text()
    n_cp = hlo.count(" collective-permute(")
    n_ag = hlo.count(" all-gather(")
    n_ar = hlo.count(" all-reduce(")
    want_cp = sum(isinstance(op, PPermute) for op in prog.ops)
    want_ar = sum(isinstance(op, AllReduce) for op in prog.ops)
    want_ag = sum(isinstance(op, GatherRow) for op in prog.ops)
    ok = (
        err < 1e-5
        and n_cp == want_cp
        and n_ar == want_ar
        and n_ag == want_ag
    )
    print(
        f"{prog.name:24s} err={err:.2e} cp={n_cp}/{want_cp} "
        f"ar={n_ar}/{want_ar} ag={n_ag}/{want_ag} {'OK' if ok else 'FAIL'}"
    )
    if not ok:
        failures.append(prog.name)

if failures:
    print(f"SHARD_FAILURES={','.join(failures)}")
    sys.exit(1)

# --- consensus distance: shard realization == stacked realization ----------
from repro.core.consensus import (
    consensus_distance_shard, consensus_distance_stacked, consensus_sq_shard,
)

tree = {
    "a": jnp.asarray(
        np.random.default_rng(1).normal(size=(N, 4, 3)).astype(np.float32)
    ),
    "b": jnp.asarray(
        np.random.default_rng(2).normal(size=(N, 5)).astype(np.float32)
    ),
}
xi_stacked = float(consensus_distance_stacked(tree))
f_xi = jax.jit(
    jax.shard_map(
        lambda v: (
            consensus_distance_shard(v, "gossip")[None],
            consensus_sq_shard(v, "gossip")[None],
        ),
        mesh=mesh, check_vma=False,
        in_specs=P("gossip"),
        out_specs=(P("gossip"), P("gossip")),
    )
)
xi_shard, sq_shard = f_xi(tree)
xi_shard = np.asarray(xi_shard)  # (N,): the same scalar on every node
from repro.core.consensus import consensus_sq_stacked

sq_stacked = np.asarray(consensus_sq_stacked(tree))
err_xi = float(np.abs(xi_shard - xi_stacked).max())
err_sq = float(np.abs(np.asarray(sq_shard) - sq_stacked).max())
print(f"consensus shard==stacked xi_err={err_xi:.2e} sq_err={err_sq:.2e}")
if err_xi > 1e-5 or err_sq > 1e-4:
    print("CONSENSUS_SHARD_FAIL")
    sys.exit(1)

print("SHARD_INTERPRETER_OK")
