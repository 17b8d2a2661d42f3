"""Attention-module unit tests (masks, GQA, chunked online softmax, cache,
the splash kernel in interpret mode and the choice to take it)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType

from repro.configs import get_config
from repro.models import transformer as tfm
from repro.models.attention import (
    KVCache, cache_update, decode_attention, multihead_attention,
    resolve_impl, splash_attention,
)
from repro.telemetry import profile

B, S, H, KV, D = 2, 16, 4, 2, 8


def _qkv(key, sq=S, sk=S):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, sq, H, D))
    k = jax.random.normal(ks[1], (B, sk, KV, D))
    v = jax.random.normal(ks[2], (B, sk, KV, D))
    pos = jnp.broadcast_to(jnp.arange(sq)[None], (B, sq))
    kpos = jnp.broadcast_to(jnp.arange(sk)[None], (B, sk))
    return q, k, v, pos, kpos


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 4])
def test_chunked_equals_reference(causal, window):
    q, k, v, pos, kpos = _qkv(jax.random.PRNGKey(0))
    a = multihead_attention(q, k, v, q_positions=pos, k_positions=kpos,
                            causal=causal, window=window, impl="reference")
    b = multihead_attention(q, k, v, q_positions=pos, k_positions=kpos,
                            causal=causal, window=window, impl="chunked",
                            chunk_size=5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_causal_mask_blocks_future():
    """Changing future K/V must not change earlier outputs."""
    q, k, v, pos, kpos = _qkv(jax.random.PRNGKey(1))
    a = multihead_attention(q, k, v, q_positions=pos, k_positions=kpos, causal=True)
    k2 = k.at[:, -1].set(99.0)
    v2 = v.at[:, -1].set(99.0)
    b = multihead_attention(q, k2, v2, q_positions=pos, k_positions=kpos, causal=True)
    np.testing.assert_allclose(np.asarray(a[:, :-1]), np.asarray(b[:, :-1]), atol=1e-6)
    assert not np.allclose(np.asarray(a[:, -1]), np.asarray(b[:, -1]))


def test_window_restricts_receptive_field():
    q, k, v, pos, kpos = _qkv(jax.random.PRNGKey(2))
    w = 3
    a = multihead_attention(q, k, v, q_positions=pos, k_positions=kpos,
                            causal=True, window=w)
    # perturbing a key more than w behind the last query leaves it unchanged
    k2 = k.at[:, 0].set(-50.0)
    b = multihead_attention(q, k2, v, q_positions=pos, k_positions=kpos,
                            causal=True, window=w)
    np.testing.assert_allclose(np.asarray(a[:, w:]), np.asarray(b[:, w:]), atol=1e-6)


def test_gqa_grouping_matches_repeated_kv():
    """GQA == MHA with kv heads explicitly repeated."""
    q, k, v, pos, kpos = _qkv(jax.random.PRNGKey(3))
    a = multihead_attention(q, k, v, q_positions=pos, k_positions=kpos, causal=True)
    krep = jnp.repeat(k, H // KV, axis=2)
    vrep = jnp.repeat(v, H // KV, axis=2)
    b = multihead_attention(q, krep, vrep, q_positions=pos, k_positions=kpos, causal=True)
    # repeat puts group g of kv-head j at index j*G+g while _split_gqa assumes
    # contiguous groups — matching layouts:
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_ring_cache_update_and_decode():
    n_slots = 4
    ck = jnp.zeros((B, n_slots, KV, D))
    cv = jnp.zeros((B, n_slots, KV, D))
    cp = jnp.full((B, n_slots), -1, jnp.int32)
    key = jax.random.PRNGKey(4)
    for t in range(6):  # wraps the ring
        kn = jax.random.normal(jax.random.fold_in(key, t), (B, 1, KV, D))
        ck, cv, cp = cache_update(ck, cv, cp, kn, kn, jnp.int32(t), ring=True)
    # slots hold the last 4 positions
    assert sorted(np.asarray(cp[0]).tolist()) == [2, 3, 4, 5]
    q = jax.random.normal(key, (B, 1, H, D))
    out = decode_attention(q, ck, cv, cp, pos=jnp.int32(6), window=4)
    assert out.shape == (B, 1, H, D)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_empty_cache_is_safe():
    ck = jnp.zeros((B, 4, KV, D))
    cp = jnp.full((B, 4), -1, jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, H, D))
    ck2, cv2, cp2 = cache_update(ck, ck, cp, q[:, :, :KV], q[:, :, :KV], jnp.int32(0), ring=False)
    out = decode_attention(q, ck2, cv2, cp2, pos=jnp.int32(0))
    assert bool(jnp.all(jnp.isfinite(out)))


def test_kvcache_empty_constructor():
    c = KVCache.empty(3, B, 8, KV, D)
    assert c.k.shape == (3, B, 8, KV, D)
    assert (c.positions == -1).all()


# ---------------------------------------------------------------------------
# splash: the kernel (interpret mode) against the reference, and its choice
# ---------------------------------------------------------------------------

# (S, H, KV, block): one block per sequence at the default block, and
# blocks of 128 at S 512, so the kernel skips the blocks the mask hides
SPLASH_CASES = [
    (256, 4, 2, None), (256, 8, 8, None), (512, 4, 2, None), (512, 8, 8, None),
    (512, 4, 2, 128),
]


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("s,h,kv,block", SPLASH_CASES,
                         ids=lambda c: str(c))
def test_splash_matches_reference(s, h, kv, block, dtype):
    """Output and gradients w.r.t. q, k and v of the splash kernel equal the
    reference's: to float32 rounding in float32; in bfloat16 both stay
    within two bfloat16 steps of the float32 answer, and of each other."""
    ks = jax.random.split(jax.random.PRNGKey(s + h), 4)
    q = jax.random.normal(ks[0], (1, s, h, 128)).astype(dtype)
    k = jax.random.normal(ks[1], (1, s, kv, 128)).astype(dtype)
    v = jax.random.normal(ks[2], (1, s, kv, 128)).astype(dtype)
    ct = jax.random.normal(ks[3], (1, s, h, 128)).astype(dtype)
    pos = jnp.arange(s)[None]

    def reference(q, k, v):
        return multihead_attention(q, k, v, q_positions=pos, k_positions=pos,
                                   impl="reference")

    def splash(q, k, v):
        return splash_attention(q, k, v, block=block, interpret=True)

    def out_and_grads(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return (out,) + vjp(ct.astype(out.dtype))

    ref = out_and_grads(reference, q, k, v)
    got = out_and_grads(jax.jit(splash), q, k, v)
    if dtype == jnp.float32:
        for r, g in zip(ref, got):
            assert _rel_err(g, r) < 1e-5
        return
    f32 = lambda x: x.astype(jnp.float32)
    truth = out_and_grads(reference, f32(q), f32(k), f32(v))
    for r, g, t in zip(ref, got, truth):
        assert _rel_err(g, t) < 2 ** -7
        assert _rel_err(r, t) < 2 ** -7
        assert _rel_err(g, r) < 2 ** -6


def _mesh(*sizes, manual=()):
    names = ("data", "model")
    return AbstractMesh(sizes, names, axis_types=tuple(
        AxisType.Manual if n in manual else AxisType.Auto for n in names
    ))


# the granite-8b cell's attention on one chip: every condition holds
SPLASH_OK = dict(seq_len=4096, head_dim=128, aligned=True, mesh=_mesh(1, 1),
                 platform="tpu")


@pytest.mark.parametrize("change", [
    {},
    {"mesh": _mesh(4, 1, manual=("data",))},  # the four-chip ring's shard_map
    {"seq_len": 256},
    {"seq_len": 1536},                  # tiled by 512, not by 1024
])
def test_resolve_takes_splash_where_exact(change):
    with jax.default_matmul_precision("default"):  # the trainer's
        assert resolve_impl("auto", **{**SPLASH_OK, **change}) == "splash"


@pytest.mark.parametrize("change", [
    {"platform": None},                 # this backend, the CPU
    {"window": 1024},
    {"k_valid": jnp.ones((1, 4096), bool)},
    {"head_dim": 64},
    {"seq_len": 4096 + 64},             # S not tiled by any block
    {"seq_len": 100},                   # S below the 128 lanes
    {"aligned": False},
    {"causal": False},
    {"mesh": _mesh(1, 2)},              # a tensor-parallel model axis
    {"mesh": _mesh(4, 1)},              # a data axis left to the partitioner
], ids=lambda c: next(iter(c)) + "=" + str(c[next(iter(c))])[:12])
def test_resolve_falls_back_to_reference(change):
    with jax.default_matmul_precision("default"):
        assert resolve_impl("auto", **{**SPLASH_OK, **change}) == "reference"


def test_resolve_keeps_reference_at_a_raised_precision():
    with jax.default_matmul_precision("highest"):
        assert resolve_impl("auto", **SPLASH_OK) == "reference"


@pytest.mark.parametrize("impl", ["reference", "chunked", "chunked_skip"])
def test_resolve_keeps_an_explicit_impl(impl):
    assert resolve_impl(impl, **SPLASH_OK) == impl


def test_cpu_forward_counts_reference_blocks():
    """A traced forward bills each attention block to the path it took:
    here on the CPU, the reference, once per layer."""
    cfg = get_config("granite-8b").reduced()
    assert cfg.attn_impl == "auto"
    cfg = dataclasses.replace(cfg, n_layers=3)
    params = tfm.init_model(cfg, jax.random.PRNGKey(0), tp_size=1)
    before = profile.traced()
    jax.jit(lambda p, t: tfm.forward(p, cfg, t)[0]).lower(
        params, jnp.zeros((1, 16), jnp.int32)
    )
    after = profile.traced()
    assert after["attention.path.reference"] - before["attention.path.reference"] == 3
    assert after["attention.path.splash"] == before["attention.path.splash"]
