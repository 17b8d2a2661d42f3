"""The program's RWKV-6 against the chip benchmark's plain float32
reference (``bench/reference/rwkv6.py``) on seeded random weights, at a
size the CPU runs in seconds: the loss and the first gradient through
``transformer.loss_fn``, the path ``SPMDTrainer`` trains, and the chunked
WKV recurrence against the step-by-step scan."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from repro.models.recurrence import rwkv_chunked, rwkv_scan_reference  # noqa: E402

CONFIG = "rwkv6-1.6b-l8-v2"
SIZES = {"hidden_size": 256, "intermediate_size": 896, "num_hidden_layers": 2,
         "vocab_size": 2048}
SEQ = 1024
SEEDS = (3000000401, 3000000402)
# Per-leaf gap of the first gradient's norm, as the benchmark's check
# computes it (``benchlib/check.py``: over the larger of the leaf's and the
# median leaf's reference norm).  In float32 the program agrees with the
# reference to round-off.  In bfloat16, as the configuration states, the
# program read 1.5e-3 to 2.7e-3 at these sizes (four seeds), the dense
# granite family 4.6e-4 to 7.7e-4 at the same widths, depth and sequence:
# the RWKV-6 step rounds more before its per-head norm (the lerps, r, k and
# v ahead of the recurrence, the WKV output), and the norm amplifies that
# rounding where a head's output is small.  The reference with float8
# products read 1.5e-2 to 2.9e-2, so the bound lies between the two.
F32_BOUND = 1e-5
BF16_BOUND = 5e-3


def _bench():
    from benchlib.files import Bench

    b = Bench(BENCH)
    cfg = b.config(CONFIG)
    cfg.update(SIZES)
    return cfg, b.reference(cfg["family"])


def _first_grads(seed, dtype, side="program"):
    """(loss, per-leaf norms of the first gradient) of the program in
    ``dtype``, or of the reference in float32 (``side`` "f32") or with
    float8 products (``side`` "fp8"), from the weights the seed makes."""
    import run
    from benchlib import data
    from benchlib.precision import EINSUMS
    from benchlib.refstep import leaf_norms, seed_key
    from repro.models.transformer import loss_fn

    cfg, ref = _bench()
    params = ref.init(cfg, seed_key(seed), jnp.bfloat16)
    rows = data.stacked({"seq": SEQ, "per_node_batch": 1, "structure": 0.85},
                        cfg["vocab_size"], 1, 0, seed)
    batch = {k: jnp.asarray(v[0]) for k, v in rows.items()}
    if side == "program":
        pcfg = dataclasses.replace(run.program_config(cfg, ref), dtype=dtype)
        fn = lambda p: loss_fn(p, pcfg, batch)
        params = jax.tree.map(lambda x: x.astype(dtype), params)
    else:
        fn = lambda p: ref.loss(p, batch, cfg, EINSUMS[side])
        params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(fn))(params)
    return float(loss), np.asarray(leaf_norms(grads, False))[0].astype(np.float64)


@pytest.fixture(scope="module")
def reference():
    return {seed: _first_grads(seed, jnp.float32, "f32") for seed in SEEDS}


def _gap(prog, ref):
    med = np.median(ref)
    return float(np.max(np.abs(prog - ref) / np.maximum(ref, med)))


def test_program_eps_is_the_configurations():
    from repro.models.rwkv6 import GROUP_NORM_EPS

    cfg, _ = _bench()
    assert GROUP_NORM_EPS == cfg["group_norm_epsilon"]


def test_float32_program_matches_the_reference(reference):
    seed = SEEDS[0]
    loss, norms = _first_grads(seed, jnp.float32)
    ref_loss, ref_norms = reference[seed]
    assert abs(loss - ref_loss) <= 1e-6 * abs(ref_loss)
    assert _gap(norms, ref_norms) <= F32_BOUND


@pytest.mark.parametrize("seed", SEEDS)
def test_bfloat16_program_is_within_its_bound(reference, seed):
    loss, norms = _first_grads(seed, jnp.bfloat16)
    ref_loss, ref_norms = reference[seed]
    assert abs(loss - ref_loss) <= 1e-3 * abs(ref_loss)
    assert _gap(norms, ref_norms) <= BF16_BOUND


def test_float8_control_is_outside_the_bound(reference):
    """The bound is tight enough that the reference computed with float8
    products, the precision below the configuration's, exceeds it."""
    seed = SEEDS[0]
    _, norms = _first_grads(seed, None, "fp8")
    assert _gap(norms, reference[seed][1]) > 2 * BF16_BOUND


@pytest.mark.parametrize("seq, chunk", [(64, 16), (60, 16), (50, 64), (96, 32)],
                         ids=["divides", "remainder", "one_short_chunk", "three"])
def test_chunked_wkv_matches_the_scan_with_gradients(seq, chunk):
    """Output, final state and the gradients of both with respect to every
    input, at the published widths' head size and the published
    initialisation's decay range."""
    b, h, n = 1, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(seq * 100 + chunk), 6)
    r, k, v = (jax.random.normal(ks[i], (b, seq, h, n)) for i in range(3))
    logw = -jnp.exp(jax.random.uniform(ks[3], (b, seq, h, n), minval=-6.0, maxval=1.0))
    u = jax.random.normal(ks[4], (h, n)) * 0.3
    s0 = jax.random.normal(ks[5], (b, h, n, n)) * 0.1
    cot = jax.random.normal(jax.random.PRNGKey(1), (b, seq, h, n))

    def scalar(f):
        def g(*args):
            o, s = f(*args)
            return jnp.sum(o * cot) + jnp.sum(s)
        return g

    chunked = lambda *a: rwkv_chunked(*a, chunk=chunk)
    args = (r, k, v, logw, u, s0)
    with jax.default_matmul_precision("highest"):
        o1, s1 = chunked(*args)
        o2, s2 = rwkv_scan_reference(*args)
        g1 = jax.grad(scalar(chunked), argnums=tuple(range(6)))(*args)
        g2 = jax.grad(scalar(rwkv_scan_reference), argnums=tuple(range(6)))(*args)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)
    for a, b_ in zip(g1, g2):
        scale = float(jnp.max(jnp.abs(b_)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=0,
                                   atol=1e-5 * scale)
