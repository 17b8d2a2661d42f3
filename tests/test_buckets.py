"""Bucketed, overlap-scheduled gossip (core/buckets.py).

Covers: ``BucketLayout`` round-trips exactly on random pytrees (buckets
crossing leaf boundaries, dtype preservation, <= 2 distinct widths so the
jit shape cache stays at <= 2 executables per program), the per-bucket
``apply_*`` (split -> apply -> merge, as ``build_bucket_step`` runs it) ==
the monolithic apply ==
the dense mixing-matrix oracle <= 1e-6 on random connected graphs —
including the runtime-masked fault paths — the per-bucket executor
(``build_bucket_step``) against a hand-rolled SGD+mix oracle for every
SGD-family flavor, the Ξ² probe-fold identity (summed bucket partials ==
``consensus_sq`` of the merged tree), end-to-end simulator equivalence
(bucketed engine == monolithic engine bit-for-bit on fault-free AND
faulty runs), the executable-accounting bar (bucket executables scale
with distinct programs x widths, never with realizations), and the
eligibility gates (SGD family only, decentralized only, post-mixing only).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.buckets import (
    BucketLayout, bucket_eligible_optimizer, build_bucket_step,
    xi_from_folded_sq,
)
from repro.core.consensus import consensus_distance_jit
from repro.core.dsgd import make_topology
from repro.core.faults import degraded_matrix, make_fault_model
from repro.core.graphs import Ring, from_adjacency
from repro.core.schedule import GossipProgram, compile_graph
from repro.core.simulator import DecentralizedSimulator
from repro.optim.sgd import adamw, lars, sgd


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


def _random_tree(rng, n, n_leaves, dtype=np.float32):
    """Random pytree with a leading (n, ...) node axis and mixed leaf ranks."""
    tree = {}
    for k in range(n_leaves):
        rank = int(rng.integers(1, 4))
        dims = [int(rng.integers(1, 5)) for _ in range(rank - 1)]
        tree[f"leaf{k}"] = jnp.asarray(
            rng.normal(size=[n] + dims).astype(dtype)
        )
    return tree


# ---------------------------------------------------------------------------
# BucketLayout: deterministic partition, exact round-trip
# ---------------------------------------------------------------------------

@given(
    st.integers(min_value=1, max_value=12),
    st.integers(min_value=1, max_value=6),
    st.integers(min_value=1, max_value=64),
    st.integers(min_value=0, max_value=10_000),
)
@settings(max_examples=40, deadline=None)
def test_layout_roundtrip_is_identity(n, n_leaves, bucket_elems, seed):
    """split -> merge == identity on random (n, ...) pytrees, at every
    bucket width."""
    rng = np.random.default_rng(seed)
    tree = _random_tree(rng, n, n_leaves)
    layout = BucketLayout(
        tuple(int(np.prod(x.shape[1:], dtype=np.int64)) for x in tree.values()),
        bucket_elems,
    )
    mats = layout.split_stacked(tree)
    assert [m.shape for m in mats] == [(n, w) for w in layout.widths]
    back = layout.merge_stacked(mats, tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(tree[k]))
        assert back[k].dtype == tree[k].dtype


@given(
    st.integers(min_value=1, max_value=10),
    st.integers(min_value=1, max_value=40),
)
@settings(max_examples=40, deadline=None)
def test_layout_partition_invariants(n_leaves, bucket_elems):
    """Bounds tile [0, P) exactly; at most TWO distinct widths (full +
    tail) — the executable-count bar; segments cover every leaf slice."""
    rng = np.random.default_rng(n_leaves * 1000 + bucket_elems)
    sizes = tuple(int(rng.integers(0, 30)) for _ in range(n_leaves))
    layout = BucketLayout(sizes, bucket_elems)
    p = sum(sizes)
    b = layout.bounds
    assert b[0] == 0 and b[-1] == p
    assert sum(layout.widths) == p
    assert len(layout.widths) == layout.num_buckets
    assert len(set(layout.widths)) <= 2
    covered = [0] * n_leaves
    for segs in layout.segments:
        for li, s, e in segs:
            assert 0 <= s < e <= sizes[li]
            covered[li] += e - s
    assert tuple(covered) == sizes


def test_layout_is_dtype_and_value_independent():
    """bf16 and f32 trees of the same shapes bucket identically, and the
    layout builds from ShapeDtypeStructs (abstract init) too."""
    shapes = {"a": (4, 3, 5), "b": (4, 7)}
    t32 = {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
    t16 = {k: jnp.zeros(s, jnp.bfloat16) for k, s in shapes.items()}
    abstract = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in shapes.items()}
    a = BucketLayout.for_stacked(t32, 1e-5)
    assert a == BucketLayout.for_stacked(t16, 1e-5)
    assert a == BucketLayout.for_stacked(abstract, 1e-5)
    assert a.total == 15 + 7
    # MiB accounting: 1 MiB == 262144 f32 elements
    assert BucketLayout.elems_for_mb(1.0) == (1 << 20) // 4
    assert BucketLayout.elems_for_mb(1e-9) == 1  # floor at one element


def test_layout_rejects_mismatched_tree():
    tree = {"a": jnp.zeros((4, 6))}
    layout = BucketLayout.for_stacked(tree, 1e-5)
    with pytest.raises(ValueError, match="do not match layout"):
        layout.split_stacked({"a": jnp.zeros((4, 7))})
    with pytest.raises(ValueError, match="bucket_elems"):
        BucketLayout((6,), 0)


# ---------------------------------------------------------------------------
# Bucketed apply == monolithic apply == dense oracle (incl. masked paths)
# ---------------------------------------------------------------------------

@given(
    st.integers(min_value=2, max_value=14),
    st.integers(min_value=0, max_value=10_000),
    st.integers(min_value=1, max_value=20),
)
@settings(max_examples=30, deadline=None)
def test_bucketed_apply_matches_monolithic_and_dense_oracle(n, seed, be):
    """On random connected graphs, per-bucket apply (split -> apply ->
    merge, as ``build_bucket_step`` runs it) == monolithic apply == W @ x
    <= 1e-6, fault-free and runtime-masked."""
    rng = np.random.default_rng(seed)
    g = _random_connected_graph(n, seed)
    prog = compile_graph(g)
    tree = _random_tree(rng, n, 3)
    sizes = tuple(
        int(np.prod(x.shape[1:], dtype=np.int64)) for x in tree.values()
    )
    layout = BucketLayout(sizes, be)
    flat = np.concatenate(
        [np.asarray(v).reshape(n, -1) for v in tree.values()], axis=1
    )
    w = np.asarray(prog.matrix())

    def _flat(t):
        return np.concatenate(
            [np.asarray(v).reshape(n, -1) for v in t.values()], axis=1
        )

    # fault-free
    mats = layout.split_stacked(tree)
    got = layout.merge_stacked([prog.apply_stacked(m) for m in mats], tree)
    mono = prog.apply_stacked(tree)
    np.testing.assert_allclose(_flat(got), _flat(mono), atol=1e-6)
    np.testing.assert_allclose(_flat(got), w @ flat, atol=1e-6)
    # masked: random alive set + random link failures
    alive = rng.random(n) > 0.3
    if not alive.any():
        alive[int(rng.integers(n))] = True
    up = np.triu(rng.random((n, n)) > 0.3, 1)
    link = up | up.T
    np.fill_diagonal(link, True)
    af = jnp.asarray(alive, jnp.float32)
    lf = jnp.asarray(link, jnp.float32)
    wd = degraded_matrix(w, alive, link)
    got_m = layout.merge_stacked(
        [prog.apply_masked(m, af, link_up=lf) for m in mats], tree
    )
    mono_m = prog.apply_masked(tree, af, link_up=lf)
    np.testing.assert_allclose(_flat(got_m), _flat(mono_m), atol=1e-6)
    np.testing.assert_allclose(_flat(got_m), wd @ flat, atol=1e-5)


def test_bucketed_apply_identity_program_shortcircuits():
    from repro.core.schedule import identity_program

    prog = identity_program(4)
    layout = BucketLayout((6,), 5)
    x = jnp.arange(24.0).reshape(4, 6)
    assert prog.apply_stacked(x) is x
    assert all(prog.apply_stacked(m) is m for m in layout.split_stacked(x))


# ---------------------------------------------------------------------------
# The per-bucket executor vs a hand-rolled SGD + mix oracle
# ---------------------------------------------------------------------------

def _sgd_oracle(theta, mom, grad, lr, hyper, update_mask=None):
    """Reference elementwise SGD on (n, w) matrices (float64 NumPy)."""
    beta = hyper.get("momentum", 0.0)
    wd = hyper.get("weight_decay", 0.0)
    nest = hyper.get("nesterov", False)
    t, m, g = (np.asarray(x, np.float64) for x in (theta, mom, grad))
    g = g + wd * t
    new_m = beta * m + g
    step = g + beta * new_m if nest else (new_m if beta else g)
    t_new = t - lr * step
    if update_mask is not None:
        u = np.asarray(update_mask, bool)[:, None]
        t_new = np.where(u, t_new, t)
        new_m = np.where(u, new_m, m)
    return t_new, new_m


@pytest.mark.parametrize(
    "hyper",
    [
        {"kind": "sgd", "momentum": 0.0, "weight_decay": 0.0, "nesterov": False},
        {"kind": "sgd", "momentum": 0.9, "weight_decay": 0.0, "nesterov": False},
        {"kind": "sgd", "momentum": 0.9, "weight_decay": 1e-3, "nesterov": True},
    ],
)
def test_bucket_step_matches_oracle_and_folds_xi(hyper):
    """Per-bucket executor == oracle update then W-mix; the threaded token
    accumulates exactly Σ_c (x_ic − x̄_c)² of the merged post-mix tree."""
    n, lr = 8, 0.05
    rng = np.random.default_rng(0)
    g = _random_connected_graph(n, 3)
    prog = compile_graph(g)
    w = np.asarray(prog.matrix())
    theta = rng.normal(size=(n, 17)).astype(np.float32)
    grad = rng.normal(size=(n, 17)).astype(np.float32)
    mom = rng.normal(size=(n, 17)).astype(np.float32)
    layout = BucketLayout((17,), 5)
    has_m = hyper["momentum"] != 0.0
    fn = build_bucket_step(prog, hyper=hyper, has_momentum=has_m)
    tok = jnp.zeros((n,), jnp.float32)
    out = np.empty_like(theta)
    for (lo, hi), width in zip(
        zip(layout.bounds[:-1], layout.bounds[1:]), layout.widths
    ):
        tb = jnp.asarray(theta[:, lo:hi])
        gb = jnp.asarray(grad[:, lo:hi])
        if has_m:
            t2, _, tok = fn(tb, jnp.asarray(mom[:, lo:hi]), gb, lr, tok)
        else:
            t2, tok = fn(tb, gb, lr, tok)
        out[:, lo:hi] = np.asarray(t2)
    t_star, _ = _sgd_oracle(theta, mom if has_m else 0 * mom, grad, lr, hyper)
    want = w @ t_star
    np.testing.assert_allclose(out, want, atol=1e-5)
    # probe fold: token == per-node Σ (x - x̄)² of the full post-mix matrix
    d = out - out.mean(axis=0, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(tok), (d * d).sum(axis=1), rtol=1e-4, atol=1e-5
    )
    assert xi_from_folded_sq(tok) == pytest.approx(
        float(np.sqrt(np.mean((d * d).sum(axis=1)))), rel=1e-4
    )


def test_bucket_step_faulty_matches_masked_oracle():
    """Fault path: stragglers skip their update, the mix renormalizes over
    surviving edges — per-bucket == gated oracle update then degraded W."""
    n, lr = 10, 0.1
    rng = np.random.default_rng(7)
    g = _random_connected_graph(n, 11)
    prog = compile_graph(g)
    hyper = {"kind": "sgd", "momentum": 0.9, "weight_decay": 0.0,
             "nesterov": False}
    theta = rng.normal(size=(n, 9)).astype(np.float32)
    grad = rng.normal(size=(n, 9)).astype(np.float32)
    mom = rng.normal(size=(n, 9)).astype(np.float32)
    alive = np.ones(n, bool)
    alive[[2, 5]] = False
    update = np.ones(n, np.float32)
    update[[2, 5, 7]] = 0.0  # 7 straggles but stays in the mix
    fault = {
        "update": jnp.asarray(update),
        "alive": jnp.asarray(alive, jnp.float32),
    }
    layout = BucketLayout((9,), 4)
    fn = build_bucket_step(prog, hyper=hyper, has_momentum=True, faulty=True)
    tok = jnp.zeros((n,), jnp.float32)
    out = np.empty_like(theta)
    for lo, hi in zip(layout.bounds[:-1], layout.bounds[1:]):
        t2, _, tok = fn(
            jnp.asarray(theta[:, lo:hi]), jnp.asarray(mom[:, lo:hi]),
            jnp.asarray(grad[:, lo:hi]), lr, tok, fault,
        )
        out[:, lo:hi] = np.asarray(t2)
    t_star, _ = _sgd_oracle(theta, mom, grad, lr, hyper, update_mask=update)
    want = degraded_matrix(np.asarray(prog.matrix()), alive) @ t_star
    np.testing.assert_allclose(out, want, atol=1e-5)


def test_bucket_step_validation_gates():
    prog = compile_graph(Ring(4))
    sgd_h = {"kind": "sgd", "momentum": 0.9}
    with pytest.raises(ValueError, match="mix_order"):
        build_bucket_step(prog, hyper=sgd_h, has_momentum=True, mix_order="pre")
    with pytest.raises(ValueError, match="SGD family"):
        build_bucket_step(prog, hyper={"kind": "adamw"}, has_momentum=True)
    with pytest.raises(ValueError, match="plain momentum-SGD"):
        build_bucket_step(
            prog,
            hyper={"kind": "sgd", "momentum": 0.9, "weight_decay": 1e-4},
            has_momentum=True,
            kernel_split=(prog, ()),
        )


def test_bucket_eligibility():
    assert bucket_eligible_optimizer(sgd())
    assert bucket_eligible_optimizer(sgd(momentum=0.0))
    assert not bucket_eligible_optimizer(adamw())
    assert not bucket_eligible_optimizer(lars())


# ---------------------------------------------------------------------------
# End-to-end: bucketed simulator == monolithic simulator
# ---------------------------------------------------------------------------

def _lin_loss(params, batch):
    y = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((y - batch["y"]) ** 2)


def _lin_setup(n, steps, seed=0):
    rng = np.random.default_rng(seed)
    params = {
        "w": jnp.asarray(rng.normal(size=(3, 2)).astype(np.float32)),
        "b": jnp.zeros((2,), jnp.float32),
    }
    batches = [
        {
            "x": jnp.asarray(rng.normal(size=(n, 4, 3)).astype(np.float32)),
            "y": jnp.asarray(rng.normal(size=(n, 4, 2)).astype(np.float32)),
        }
        for _ in range(steps)
    ]
    return params, batches


@pytest.mark.parametrize("topo_name", ["d_ring", "d_one_peer_exp"])
def test_simulator_bucketed_equals_monolithic(topo_name):
    """Multi-bucket engine == monolithic engine on the final params
    (<= 1e-6; observed bit-exact) and the folded Ξ² == the jit probe."""
    n, steps = 8, 6
    params, batches = _lin_setup(n, steps)
    finals = {}
    for mb in (None, 1e-5):  # 1e-5 MiB -> 2-elem buckets -> 4 buckets of 8
        sim = DecentralizedSimulator(
            _lin_loss, sgd(momentum=0.9), make_topology(topo_name, n),
            bucket_mb=mb,
        )
        st_ = sim.init(params)
        for t in range(steps):
            st_, _, _ = sim.train_step(st_, batches[t], 0.05)
        finals[mb] = st_.params
        if mb is not None:
            assert sim._bucket_layout.num_buckets == 4
            assert sim._folded_for_step == st_.step
            np.testing.assert_allclose(
                xi_from_folded_sq(sim._folded_sq),
                float(consensus_distance_jit(st_.params)),
                rtol=1e-5, atol=1e-7,
            )
    for a, b in zip(
        jax.tree.leaves(finals[None]), jax.tree.leaves(finals[1e-5])
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        )


def test_simulator_bucketed_equals_monolithic_under_faults():
    """Same equivalence with runtime fault masks (straggler model), and
    the executable-accounting bar: bucket executables count distinct
    (program, width) pairs, NOT realizations."""
    n, steps = 8, 8
    params, batches = _lin_setup(n, steps, seed=3)
    finals = {}
    for mb in (None, 2e-5):
        fm = make_fault_model("straggler", n, rate=0.4, seed=5)
        sim = DecentralizedSimulator(
            _lin_loss, sgd(momentum=0.9),
            make_topology("d_ring", n, fault_model=fm),
            bucket_mb=mb,
        )
        st_ = sim.init(params)
        for t in range(steps):
            st_, _, _ = sim.train_step(st_, batches[t], 0.05)
        finals[mb] = st_.params
        if mb is not None:
            keys = [
                k for k in sim._step_cache
                if isinstance(k, tuple) and k[0] == "__bucket__"
            ]
            # one ring program x two widths (full=5, tail=3) x one fault
            # signature: realizations never mint new executables
            assert len(keys) == len(set(keys)) == 2
    for a, b in zip(
        jax.tree.leaves(finals[None]), jax.tree.leaves(finals[2e-5])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("kind,kw,n_bucket_execs", [
    # preemption: float boost masks during the drain, then the depart's
    # degraded program — a SECOND (program, width) pair per width
    ("preempt", dict(rate=0.8, seed=1, drain_steps=3), 4),
    # deadline: transient masks over the base program only
    ("deadline", dict(rate=0.5, seed=4), 2),
])
def test_simulator_bucketed_faults_preempt_and_deadline(kind, kw, n_bucket_execs):
    """Satellite (PR 8): Preemption drain/boost masks and gossip-deadline
    masks dispatched per-bucket are bit-identical to the monolithic step,
    and bucket executables still count (program, width) pairs only."""
    n, steps = 8, 8
    params, batches = _lin_setup(n, steps, seed=3)
    finals = {}
    for mb in (None, 2e-5):
        fm = make_fault_model(kind, n, **kw)
        sim = DecentralizedSimulator(
            _lin_loss, sgd(momentum=0.9),
            make_topology("d_ring", n, fault_model=fm),
            bucket_mb=mb,
        )
        st_ = sim.init(params)
        for t in range(steps):
            st_, _, _ = sim.train_step(st_, batches[t], 0.05)
        finals[mb] = st_.params
        if mb is not None:
            keys = [
                k for k in sim._step_cache
                if isinstance(k, tuple) and k[0] == "__bucket__"
            ]
            assert len(keys) == len(set(keys)) == n_bucket_execs
    for a, b in zip(
        jax.tree.leaves(finals[None]), jax.tree.leaves(finals[2e-5])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_simulator_bucketed_respects_mix_every():
    """Off-cycle steps (mix_every=2) take the plain path; the bucketed
    dispatches only fire on gossip steps — and the two engines agree."""
    n, steps = 6, 6
    params, batches = _lin_setup(n, steps, seed=9)
    finals = {}
    for mb in (None, 2e-5):
        sim = DecentralizedSimulator(
            _lin_loss, sgd(momentum=0.9), make_topology("d_ring", n),
            mix_every=2, bucket_mb=mb,
        )
        st_ = sim.init(params)
        for t in range(steps):
            st_, _, _ = sim.train_step(st_, batches[t], 0.05)
        finals[mb] = st_.params
    for a, b in zip(
        jax.tree.leaves(finals[None]), jax.tree.leaves(finals[2e-5])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_simulator_bucket_validation():
    with pytest.raises(ValueError, match="SGD-family"):
        DecentralizedSimulator(
            _lin_loss, adamw(), make_topology("d_ring", 4), bucket_mb=1.0
        )
    with pytest.raises(ValueError, match="decentralized"):
        DecentralizedSimulator(
            _lin_loss, sgd(), make_topology("c_complete", 4), bucket_mb=1.0
        )
    with pytest.raises(ValueError, match="mix_order"):
        DecentralizedSimulator(
            _lin_loss, sgd(),
            make_topology("d_ring", 4, mix_order="pre"), bucket_mb=1.0
        )
