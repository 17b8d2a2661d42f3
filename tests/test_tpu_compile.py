"""The fused gossip kernels compile for a TPU v5e chip.

Interpret mode accepts tilings the chip's compiler refuses, so each kernel
of the gossip path is compiled here for a described (not attached) v5e at
the compiled default block (1024), in float32 and bfloat16, at program
degrees 1 and 2: the stacked kernels over one 4096 x 14336 MLP matrix per
node, the one-node ``gossip_update`` over a leaf that tiles in place and
one with an odd vocab.  The HLO must hold the Mosaic ``tpu_custom_call``:
the kernel was compiled, not interpreted, and its tile passed the VMEM
budget check, which only compiled mode applies.  It must also carry the
kernel's stable name (``pallas_call(name=...)``), which a profiler trace
names its events by.  ``fused_apply_shard``,
the four-chip trainer's ``--fused-apply`` round, is compiled inside
``shard_map`` over the described 2x2 chips on the same two leaves.  The
gradient of one granite-8b attention block at the one-chip benchmark's
shapes compiles through the path ``attn_impl="auto"`` resolves to on a
mesh of one described chip: jax's splash kernel, forward and backward.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library, and every test worker
imports this file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as PS

from repro.configs import get_config
from repro.core.graphs import Ring, one_peer_exponential
from repro.core.schedule import compile_graph
from repro.kernels.gossip_update import (
    fused_apply_shard, fused_bucket_update, gossip_program_update,
    gossip_update,
)
from repro.models import transformer as tfm
from repro.models.attention import resolve_impl
from repro.models.common import abstract_params

N = 4
P = 4096 * 14336  # one granite-8b MLP matrix per node
BLOCK = 1024
DTYPES = [jnp.float32, jnp.bfloat16]
# one node's leaves: granite-8b's 4-layer stacked MLP matrix, which tiles in
# place, and internvl2-2b's output head, whose odd vocab (92553) does not
# and goes through the padded view
LEAVES = {"mlp": (4, 4096, 14336), "odd_vocab_head": (2048, 92553)}
# degree 1: one hop of the one-peer exponential graph; degree 2: the ring
PROGRAMS = {1: lambda: compile_graph(one_peer_exponential(N, 0)),
            2: lambda: compile_graph(Ring(N))}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler library in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _kernel_named(hlo, name):
    """The compiled text holds a Mosaic custom call named ``name``."""
    return any(
        line.strip().removeprefix("ROOT ").startswith(f"%{name}.")
        and "tpu_custom_call" in line
        for line in hlo.splitlines()
    )


@pytest.mark.parametrize("leaf", LEAVES, ids=list(LEAVES))
@pytest.mark.parametrize("deg", [1, 2])
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_gossip_update_compiles(one_chip, dtype, deg, leaf):
    shape = LEAVES[leaf]
    s = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn = functools.partial(
        gossip_update, lr=0.1, beta=0.9, block=BLOCK, interpret=False
    )
    hlo = _compiled_hlo(
        lambda th, nb, w, g, m: fn(th, nb, w, g, m),
        s(shape), (s(shape),) * deg, s((deg + 1,), jnp.float32), s(shape),
        s(shape, jnp.float32),
    )
    assert "tpu_custom_call" in hlo
    assert _kernel_named(hlo, "gossip_leaf_update")


@pytest.mark.parametrize("deg", [1, 2])
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_gossip_program_update_compiles(one_chip, dtype, deg):
    s = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(th, nb, w, f, g, m):
        return gossip_program_update(
            th, nb, w, g, m, lr=0.1, beta=0.9, fault=f, block=BLOCK,
            interpret=False,
        )

    hlo = _compiled_hlo(
        fn, s((N, P)), (s((N, P)),) * deg, s((N, deg + 1), jnp.float32),
        s((N, deg + 1), jnp.float32), s((N, P)), s((N, P), jnp.float32),
    )
    assert "tpu_custom_call" in hlo
    assert _kernel_named(hlo, "gossip_program_update")


@pytest.mark.parametrize("deg", [1, 2])
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_fused_bucket_update_compiles(one_chip, dtype, deg):
    program = PROGRAMS[deg]()
    assert program.permute_tables()[0].shape == (N, deg)
    s = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(th, g, m):
        return fused_bucket_update(
            program, th, g, m, lr=0.1, beta=0.9, block=BLOCK, interpret=False
        )

    hlo = _compiled_hlo(fn, s((N, P)), s((N, P)), s((N, P), jnp.float32))
    assert "tpu_custom_call" in hlo
    assert _kernel_named(hlo, "gossip_program_update")


@pytest.mark.parametrize("leaf", LEAVES, ids=list(LEAVES))
@pytest.mark.parametrize("deg", [1, 2])
def test_fused_apply_shard_compiles(topo, deg, leaf):
    mesh = Mesh(np.array(topo.devices).reshape(N, 1), ("data", "model"))
    program = PROGRAMS[deg]()
    leaf = (1,) + LEAVES[leaf]  # this node's slice of the stacked leaf
    spec = PS(("data", "model"))

    def node(th, g, m):
        return fused_apply_shard(
            program, th, g, m, "data", lr=0.1, beta=0.9, interpret=False
        )

    fn = jax.shard_map(
        node, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec, spec),
        axis_names={"data", "model"}, check_vma=False,
    )
    s = lambda dt: jax.ShapeDtypeStruct(
        (N,) + leaf[1:], dt, sharding=NamedSharding(mesh, spec)
    )
    hlo = _compiled_hlo(fn, s(jnp.bfloat16), s(jnp.bfloat16), s(jnp.float32))
    assert "tpu_custom_call" in hlo
    assert _kernel_named(hlo, "gossip_leaf_update")
    assert "collective-permute" in hlo


def test_attention_block_grad_compiles_through_splash(topo):
    """granite-8b (32/8 heads of 128), batch 1, S 4096, bfloat16, on a
    1x1 mesh of one described v5e: the resolved path is the splash kernel,
    whose forward (with the residuals the backward needs) and fused
    backward are compiled Mosaic calls."""
    cfg = get_config("granite-8b")
    s = 4096
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    rep = NamedSharding(mesh, PS())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        abstract_params(tfm.attn_block_defs(cfg, 1, with_ffn=False)),
    )
    h = jax.ShapeDtypeStruct((1, s, cfg.d_model), cfg.dtype, sharding=rep)
    impls = []

    def loss(p, h):
        impl = resolve_impl(cfg.attn_impl, seq_len=s, head_dim=cfg.head_dim,
                            aligned=True)
        impls.append(impl)
        positions = jnp.arange(s, dtype=jnp.int32)[None]
        out, _, _ = tfm.apply_attn_block(
            p, cfg, h, positions=positions, window=None,
            collect_cache=False, impl=impl,
        )
        return out.astype(jnp.float32).sum()

    # the trainer's matmul precision (this suite's conftest raises it)
    with jax.set_mesh(mesh), jax.default_matmul_precision("default"):
        hlo = _compiled_hlo(jax.grad(loss, argnums=(0, 1)), params, h)
    assert impls == ["splash"]
    assert _kernel_named(hlo, "splash_mha_fwd_residuals")
    assert _kernel_named(hlo, "splash_mha_dkv_no_residuals")


def test_rwkv_block_grad_compiles_through_the_wkv_kernel(topo):
    """One RWKV-6 Finch 1.6B block (d_model 2048, 32 heads of 64, channel
    mix 7168), batch 1, S 4096, bfloat16, on a 1x1 mesh of one described
    v5e: the WKV path resolves to the Pallas kernel, and its forward (with
    the per-chunk states the backward reads) and backward are compiled
    Mosaic calls that carry the ``wkv`` scope and read and write their
    sequences in the model's own (B, L, H·N) layout, not transposed."""
    from repro.models.rwkv6 import (RWKVState, apply_rwkv_block, resolve_wkv,
                                    rwkv_block_defs)

    d, heads, s = 2048, 32, 4096
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    rep = NamedSharding(mesh, PS())
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep)
    params = jax.tree.map(
        shape, abstract_params(rwkv_block_defs(d, heads, 7168, jnp.bfloat16)))
    x = jax.ShapeDtypeStruct((1, s, d), jnp.bfloat16, sharding=rep)
    state = jax.tree.map(shape, jax.eval_shape(
        lambda: RWKVState.empty(1, heads, d // heads, d, jnp.bfloat16)))
    impls = []

    def loss(p, x, st):
        impl = resolve_wkv()
        impls.append(impl)
        out, _ = apply_rwkv_block(p, x, st, n_heads=heads, wkv_impl=impl)
        return out.astype(jnp.float32).sum()

    with jax.set_mesh(mesh):
        hlo = _compiled_hlo(jax.value_and_grad(loss, argnums=(0, 1)), params, x, state)
    assert impls == ["pallas"]
    calls = [line for line in hlo.splitlines()
             if "tpu_custom_call" in line and " = " in line]
    for kernel in ("wkv_fwd_states", "wkv_bwd"):
        mine = [line for line in calls
                if kernel in line.split(" = ", 1)[0]]
        assert mine, kernel
        assert all("/wkv/" in line.split("op_name=", 1)[-1] for line in mine), kernel
        for line in mine:
            operands = line.split("operand_layout_constraints={", 1)[1].split("}}", 1)[0]
            seqs = [dims for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", operands)
                    if dtype == "bf16"]
            # r, k, v, the log decay; and the backward's output gradient
            assert seqs == [f"1,{s},{d}"] * (4 if kernel == "wkv_fwd_states" else 5), seqs
