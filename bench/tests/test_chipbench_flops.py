"""Model FLOPs, parameter counts and kernel bytes against hand counts and
the program's own parameter count."""
from __future__ import annotations

import jax
import numpy as np

from chipbench_util import BENCH


def _bench():
    from benchlib.files import Bench

    return Bench(BENCH)


def test_granite_matmul_params_are_two_to_the_thirty():
    b = _bench()
    cfg = b.config("granite-8b-l4")
    assert b.flops("dense").matmul_params(cfg) == 2 ** 30
    assert b.flops("dense").flops_per_token(cfg, 4096) == 6 * 2 ** 30 + 12 * 4 * 4096 * 4096


def test_rwkv_matmul_params():
    b = _bench()
    cfg = b.config("rwkv6-1.6b-l8")
    per_layer = 6 * 2048 * 2048 + 2 * 2048 * 64 + 2 * 2048 * 7168
    assert b.flops("ssm").matmul_params(cfg) == 8 * per_layer + 2048 * 65536


def _program_param_count(name):
    import run
    from repro.core.dsgd import make_topology
    from repro.launch.mesh import make_mesh
    from repro.launch.train import SPMDTrainer
    from repro.optim.sgd import sgd

    b = _bench()
    cfg = b.config(name)
    pcfg = run.program_config(cfg, b.reference(cfg["family"]))
    trainer = SPMDTrainer(pcfg, make_mesh((1, 1), ("data", "model")),
                          make_topology("d_ring", 1), sgd(momentum=0.9))
    made = jax.eval_shape(lambda k: b.reference(cfg["family"]).init(cfg, k, pcfg.dtype),
                          jax.random.PRNGKey(0))
    run.check_tree(made, trainer.abstract_state[0])
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(trainer.abstract_state[0]))


def test_granite_total_params_match_the_trainer():
    assert _program_param_count("granite-8b-l4") == 1_275_105_280


def test_rwkv_reference_tree_matches_the_trainer():
    assert _program_param_count("rwkv6-1.6b-l8") > 0


def test_gossip_kernel_bytes_hand_count():
    """One 4096 x 14336 bf16 leaf at degree 2: read theta, 2 neighbours and
    the gradient (2 bytes each) and the f32 momentum; write theta and the
    momentum: 18 bytes per element."""
    mod = _bench().metric("kernel.gossip_update.roofline")
    p = 4096 * 14336
    hand = p * (2 + 2 * 2 + 2 + 4) + p * (2 + 4)
    assert mod.bytes_per_step([p], 2, 2) == hand == 1_056_964_608
