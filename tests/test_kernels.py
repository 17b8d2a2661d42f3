"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize(
    "b,h,kv,sq,sk,d",
    [
        (1, 2, 1, 128, 128, 64),
        (2, 4, 2, 128, 256, 64),
        (1, 8, 8, 256, 256, 32),
        (1, 6, 2, 128, 128, 128),
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, h, kv, sq, sk, d, causal):
    key = jax.random.PRNGKey(b * 100 + h)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kv, sk, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kv, sk, d), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 2, 128, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 2, 128, 64)).astype(dtype)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v)
    assert out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol
    )


@pytest.mark.parametrize("window", [32, 96])
def test_flash_attention_sliding_window(window):
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    out = ops.flash_attention(q, k, v, window=window, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("p,deg,block", [(1024, 2, 256), (4096, 6, 1024), (2048, 1, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_update_sweep(p, deg, block, dtype):
    key = jax.random.PRNGKey(p + deg)
    ks = jax.random.split(key, 4)
    theta = jax.random.normal(ks[0], (p,)).astype(dtype)
    nbr = jax.random.normal(ks[1], (deg, p)).astype(dtype)
    w = jnp.full((deg + 1,), 1.0 / (deg + 1))
    g = jax.random.normal(ks[2], (p,)).astype(dtype)
    m = jax.random.normal(ks[3], (p,)).astype(jnp.float32)
    o1, m1 = ops.gossip_update(theta, nbr, w, g, m, lr=0.1, beta=0.9, block=block)
    o2, m2 = ref.gossip_update_ref(theta, nbr, w, g, m, lr=0.1, beta=0.9)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o1, np.float32), np.asarray(o2, np.float32), atol=tol
    )
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-5)


def test_gossip_update_runtime_lr_beta_no_recompile():
    """LR schedules must not retrigger compiles: lr/beta ride in SMEM at
    runtime, so sweeping them leaves exactly one cached executable."""
    from repro.kernels.gossip_update import _leaf_update

    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    theta = jax.random.normal(ks[0], (512,))
    nbr = jax.random.normal(ks[1], (2, 512))
    w = jnp.full((3,), 1.0 / 3)
    g = jax.random.normal(ks[2], (512,))
    m = jax.random.normal(ks[3], (512,))
    _leaf_update._clear_cache()
    for lr, beta in [(0.1, 0.9), (0.05, 0.9), (0.01, 0.8), (0.2, 0.0)]:
        o, mm = ops.gossip_update(theta, nbr, w, g, m, lr=lr, beta=beta, block=256)
        o2, m2 = ref.gossip_update_ref(theta, nbr, w, g, m, lr=lr, beta=beta)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o2), atol=1e-5)
        np.testing.assert_allclose(np.asarray(mm), np.asarray(m2), atol=1e-5)
    assert _leaf_update._cache_size() == 1


@pytest.mark.parametrize("graph_name", ["star", "ring", "one_peer", "matching", "irregular"])
def test_fused_program_apply_matches_dense_oracle(graph_name):
    """The per-node-weight Pallas executor == optimizer update followed by
    the program's dense interpreter (PR-3 acceptance, <= 1e-6) on every
    PPermute program class: circulant, matching, and edge-colored."""
    from repro.core.graphs import (
        Ring, Star, from_adjacency, one_peer_exponential, random_matching,
    )
    from repro.core.schedule import compile_graph
    from repro.kernels.gossip_update import fused_apply_stacked
    from repro.optim.sgd import sgd

    graph = {
        "star": lambda: Star(8),
        "ring": lambda: Ring(8),
        "one_peer": lambda: one_peer_exponential(8, 1),
        "matching": lambda: random_matching(8, seed=3),
        "irregular": lambda: from_adjacency(
            [(0, 1), (1, 2), (2, 3), (0, 3), (0, 2), (4, 5), (5, 6), (6, 7)]
        ),
    }[graph_name]()
    prog = compile_graph(graph)
    n = prog.n
    kp = jax.random.split(jax.random.PRNGKey(n), 4)
    # deliberately non-block-aligned leaf sizes: exercises the zero-padding
    params = {"a": jax.random.normal(kp[0], (n, 33, 7)),
              "b": jax.random.normal(kp[1], (n, 10))}
    grads = {"a": jax.random.normal(kp[2], (n, 33, 7)),
             "b": jax.random.normal(kp[3], (n, 10))}
    mom = jax.tree.map(jnp.zeros_like, params)
    lr, beta = 0.07, 0.9
    new_p, new_m = fused_apply_stacked(
        prog, params, grads, mom, lr=lr, beta=beta, block=128
    )
    opt = sgd(momentum=beta)
    up, um = jax.vmap(opt.update, in_axes=(0, 0, 0, None))(
        grads, mom, params, jnp.float32(lr)
    )
    want = prog.apply_dense(up)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(new_p[k]), np.asarray(want[k]), atol=1e-6, err_msg=k
        )
        np.testing.assert_allclose(np.asarray(new_m[k]), np.asarray(um[k]), atol=1e-6)


def test_fused_program_apply_momentumless_and_pre_order():
    """beta=0 keeps the empty () optimizer state; mix_order='pre' mixes the
    raw params before descending (no theta* materialization on the wire)."""
    from repro.core.graphs import Ring
    from repro.core.schedule import compile_graph
    from repro.kernels.gossip_update import fused_apply_stacked
    from repro.optim.sgd import sgd

    prog = compile_graph(Ring(8))
    kp = jax.random.split(jax.random.PRNGKey(0), 2)
    params = {"w": jax.random.normal(kp[0], (8, 50))}
    grads = {"w": jax.random.normal(kp[1], (8, 50))}
    new_p, new_m = fused_apply_stacked(
        prog, params, grads, (), lr=0.1, beta=0.0, block=64
    )
    assert new_m == ()
    opt = sgd(momentum=0.0)
    up, _ = jax.vmap(opt.update, in_axes=(0, 0, 0, None))(
        grads, (), params, jnp.float32(0.1)
    )
    want = prog.apply_dense(up)
    np.testing.assert_allclose(np.asarray(new_p["w"]), np.asarray(want["w"]), atol=1e-6)

    # pre-order: mix raw params first, then descend
    mom = jax.tree.map(jnp.zeros_like, params)
    new_p, _ = fused_apply_stacked(
        prog, params, grads, mom, lr=0.1, beta=0.9, mix_order="pre", block=64
    )
    mixed = prog.apply_dense(params)
    want = jax.tree.map(
        lambda mx, g: mx - 0.1 * (0.9 * jnp.zeros_like(g) + g), mixed, grads
    )
    np.testing.assert_allclose(np.asarray(new_p["w"]), np.asarray(want["w"]), atol=1e-6)


def test_fused_kernel_composes_with_multi_round_fusion():
    """fused_apply × mix_rounds: kernel runs update + round 1, the stacked
    interpreter the remaining rounds — together == the fused program's
    dense product oracle (mirrors SPMDTrainer._fused_split)."""
    from repro.core.graphs import one_peer_exponential
    from repro.core.schedule import GossipProgram, compile_graph
    from repro.kernels.gossip_update import fused_apply_stacked
    from repro.optim.sgd import sgd

    n = 8
    progs = [compile_graph(one_peer_exponential(n, t)) for t in range(3)]
    fused = GossipProgram.fuse(progs)
    kp = jax.random.split(jax.random.PRNGKey(1), 2)
    params = {"w": jax.random.normal(kp[0], (n, 40))}
    grads = {"w": jax.random.normal(kp[1], (n, 40))}
    mom = jax.tree.map(jnp.zeros_like, params)
    lr, beta = 0.05, 0.9
    new_p, _ = fused_apply_stacked(
        fused.stages[0], params, grads, mom, lr=lr, beta=beta, block=40
    )
    for stage in fused.stages[1:]:
        new_p = stage.apply_stacked(new_p)
    opt = sgd(momentum=beta)
    up, _ = jax.vmap(opt.update, in_axes=(0, 0, 0, None))(
        grads, mom, params, jnp.float32(lr)
    )
    want = fused.apply_dense(up)
    np.testing.assert_allclose(
        np.asarray(new_p["w"]), np.asarray(want["w"]), atol=1e-5
    )


def test_fused_apply_rejects_non_permute_programs():
    from repro.core.graphs import Complete, Ring
    from repro.core.schedule import compile_graph, dense_program
    from repro.kernels.gossip_update import fused_apply_stacked

    params = {"w": jnp.ones((8, 16))}
    for prog in (compile_graph(Complete(8)), dense_program(Ring(8))):
        with pytest.raises(ValueError, match="PPermute"):
            fused_apply_stacked(prog, params, params, (), lr=0.1, beta=0.0)


@pytest.mark.parametrize("r,p,block", [(1, 512, 512), (7, 3000, 512), (16, 2048, 2048)])
def test_l2_norms_sweep(r, p, block):
    x = jax.random.normal(jax.random.PRNGKey(r), (r, p))
    out = ops.l2_norms(x, block=block)
    want = ref.l2_norms_ref(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_l2_norms_matches_dbench_probe():
    """The kernel agrees with the in-step jnp probe used by the trainer."""
    from repro.core.dbench import param_l2_norms

    params = {
        "a": jax.random.normal(jax.random.PRNGKey(0), (37, 11)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (257,)),
    }
    want = param_l2_norms(params)
    flat = [x.ravel() for x in jax.tree.leaves(params)]
    pmax = max(x.size for x in flat)
    mat = jnp.stack([jnp.pad(x, (0, pmax - x.size)) for x in flat])
    got = ops.l2_norms(mat, block=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
