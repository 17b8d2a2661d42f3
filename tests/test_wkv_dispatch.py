"""Which WKV path an RWKV-6 block takes, and what tracing the model counts.

``models/rwkv6.resolve_wkv`` takes the Pallas kernel on a TPU whose mesh
leaves no axis of size > 1 to the partitioner, and XLA's chunk scan
everywhere else; ``transformer.forward`` bills each traced block to the
counter of its path, ``wkv.pallas`` or ``wkv.chunked``, and a kernel block
whose heads share the kernel's lanes also to ``wkv.pallas.packed``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, AxisType

from repro.configs import get_config
from repro.models import transformer as tfm
from repro.models.rwkv6 import resolve_wkv
from repro.telemetry import profile

EMPTY = AbstractMesh((), ())


@pytest.mark.parametrize("platform, mesh, path", [
    ("tpu", EMPTY, "pallas"),
    ("tpu", AbstractMesh((1, 1), ("data", "model")), "pallas"),
    ("tpu", AbstractMesh((2,), ("data",), axis_types=(AxisType.Manual,)), "pallas"),
    ("tpu", AbstractMesh((2,), ("data",)), "chunked"),
    ("cpu", EMPTY, "chunked"),
    ("gpu", EMPTY, "chunked"),
], ids=["tpu_no_mesh", "tpu_one_chip", "tpu_manual_axis", "tpu_auto_partitioned",
        "cpu", "gpu"])
def test_resolve_wkv_takes_the_kernel_only_on_an_unpartitioned_tpu(platform, mesh, path):
    assert resolve_wkv(mesh, platform) == path


def test_resolve_wkv_on_this_cpu_is_the_chunk_scan():
    assert jax.default_backend() == "cpu"
    assert resolve_wkv() == "chunked"


def _traced_counts(cfg):
    before = profile.traced()
    params = jax.eval_shape(lambda: tfm.init_model(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    jax.eval_shape(lambda p, t: tfm.forward(p, cfg, t)[0], params, tokens)
    after = profile.traced()
    return {k: after[k] - before[k]
            for k in ("wkv.pallas", "wkv.chunked", "wkv.pallas.packed")}


def test_tracing_on_the_cpu_counts_every_block_on_the_chunk_scan():
    cfg = get_config("rwkv6-1.6b").reduced()
    assert _traced_counts(cfg) == {"wkv.pallas": 0, "wkv.chunked": cfg.n_layers,
                                   "wkv.pallas.packed": 0}


def test_tracing_where_the_kernel_resolves_counts_every_block_on_it(monkeypatch):
    cfg = get_config("rwkv6-1.6b").reduced()   # 4 heads of 64: two a lane row
    monkeypatch.setattr(tfm, "resolve_wkv", lambda: "pallas")
    assert _traced_counts(cfg) == {"wkv.pallas": cfg.n_layers, "wkv.chunked": 0,
                                   "wkv.pallas.packed": cfg.n_layers}
