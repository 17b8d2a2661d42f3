"""Subprocess body for test_spmd.py: the edge-colored star on 8 host devices.

Locks in the PR-3 acceptance bar with ``assert_no_all_gather``:
  1. the star's compiled program is <= Δ+1 PPermutes, zero GatherRow;
  2. its shard-interpreter HLO carries collective-permutes ONLY (the dense
     all-gather fallback must not leak back onto the hot path) and matches
     the dense mixing-matrix oracle;
  3. ``fused_apply_shard`` (Pallas kernel + real ppermute landing buffers
     inside shard_map) equals optimizer-then-dense-mix to <= 1e-5.
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

from repro.core.graphs import Star, from_adjacency
from repro.core.schedule import GatherRow, PPermute, compile_graph
from repro.launch.hlo_analysis import assert_no_all_gather
from repro.launch.mesh import make_mesh

N = 8
mesh = make_mesh((N,), ("gossip",))

for graph in [Star(N), from_adjacency([(0, 1), (1, 2), (2, 3), (0, 3), (0, 2), (4, 5), (5, 6), (6, 7)], name="irregular")]:
    prog = compile_graph(graph)
    assert not any(isinstance(op, GatherRow) for op in prog.ops), prog.describe()
    assert all(isinstance(op, PPermute) for op in prog.ops)
    assert len(prog.ops) <= graph.degree + 1, (len(prog.ops), graph.degree)

    x = np.random.default_rng(0).normal(size=(N, 4, 3)).astype(np.float32)
    f = jax.jit(
        jax.shard_map(
            lambda v: prog.apply_shard(v, "gossip"),
            mesh=mesh, check_vma=False, in_specs=P("gossip"), out_specs=P("gossip"),
        )
    )
    counts = assert_no_all_gather(f, jnp.asarray(x))
    assert counts.get("collective-permute", 0) == len(prog.ops), counts
    got = np.asarray(f(jnp.asarray(x)))
    want = np.einsum("ij,j...->i...", graph.mixing_matrix(), x)
    err = float(np.abs(got - want).max())
    assert err < 1e-5, err
    print(f"{graph.name}: {len(prog.ops)} permutes, no all-gather, err={err:.2e}")

# --- fused Pallas apply inside shard_map == optimizer + dense mix oracle ----
from repro.kernels.gossip_update import fused_apply_shard

prog = compile_graph(Star(N))
rng = np.random.default_rng(1)
P_LEN = 96
theta = rng.normal(size=(N, P_LEN)).astype(np.float32)
grads = rng.normal(size=(N, P_LEN)).astype(np.float32)
mom = rng.normal(size=(N, P_LEN)).astype(np.float32)
lr, beta = 0.05, 0.9


def node_fused(t, g, m):
    new_p, new_m = fused_apply_shard(
        prog, {"w": t}, {"w": g}, {"w": m}, "gossip", lr=lr, beta=beta,
        block=32,
    )
    return new_p["w"], new_m["w"]


ff = jax.jit(
    jax.shard_map(
        node_fused, mesh=mesh, check_vma=False,
        in_specs=(P("gossip"), P("gossip"), P("gossip")),
        out_specs=(P("gossip"), P("gossip")),
    )
)
got_p, got_m = ff(jnp.asarray(theta), jnp.asarray(grads), jnp.asarray(mom))
m_new = beta * mom + grads
theta_star = theta - lr * m_new
want_p = prog.matrix() @ theta_star
np.testing.assert_allclose(np.asarray(got_p), want_p, atol=1e-5)
np.testing.assert_allclose(np.asarray(got_m), m_new, atol=1e-6)
assert_no_all_gather(ff, jnp.asarray(theta), jnp.asarray(grads), jnp.asarray(mom))
print("fused_apply_shard == dense oracle, no all-gather")

# --- fault rows inside shard_map == masked update + degraded dense mix ------
from repro.core.schedule import degraded_matrix

update = np.array([1, 1, 0, 1, 1, 1, 1, 0], bool)
alive = np.array([1, 0, 1, 1, 1, 1, 1, 1], bool)
fault = {
    "update": jnp.asarray(update, jnp.float32),
    "alive": jnp.asarray(alive, jnp.float32),
    "link": None,
}


def node_fused_faulty(t, g, m):
    new_p, new_m = fused_apply_shard(
        prog, {"w": t}, {"w": g}, {"w": m}, "gossip", lr=lr, beta=beta,
        fault=fault, block=32,
    )
    return new_p["w"], new_m["w"]


fff = jax.jit(
    jax.shard_map(
        node_fused_faulty, mesh=mesh, check_vma=False,
        in_specs=(P("gossip"), P("gossip"), P("gossip")),
        out_specs=(P("gossip"), P("gossip")),
    )
)
got_p, got_m = fff(jnp.asarray(theta), jnp.asarray(grads), jnp.asarray(mom))
m_want = np.where(update[:, None], beta * mom + grads, mom)
theta_star = np.where(update[:, None], theta - lr * m_want, theta)
want_p = degraded_matrix(prog.matrix(), alive) @ theta_star
np.testing.assert_allclose(np.asarray(got_p), want_p, atol=1e-5)
np.testing.assert_allclose(np.asarray(got_m), m_want, atol=1e-6)
print("fused_apply_shard fault rows == masked oracle")
print("STAR_HLO_OK")
