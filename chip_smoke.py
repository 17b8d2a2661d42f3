"""Smoke test of the decentralized trainer on TPU chips.

    python chip_smoke.py                # one chip: phases 1-3
    python chip_smoke.py --four-chips   # four chips (one 2x2 host): phase 4

1. Find the chips: fail unless JAX's first device is a TPU.
2. Train granite-8b at its published widths (d_model 4096, 32/8 heads,
   d_ff 14336, vocab 49152), cut to 4 layers, on one chip through the
   trainer's own entry point, ``repro.launch.train.main``, with random
   weights from a seed: every loss finite, the parameters changed.
3. Run the fused gossip kernels compiled for the chip (``tpu_custom_call``
   in the lowered program) on bf16 leaves at real widths (a 4096 x 14336
   MLP matrix, alone and on 4 nodes, and a 2048 x 92553 output head) and
   compare them with ``repro.kernels.ref`` in float32.
4. (``--four-chips``) Gossip across four chips, one node per chip: the
   trainer's entry point on a 4x1 mesh for ``d_ring`` and for the
   time-varying ``d_ada --k-floor one_peer`` program set, each with the
   interpreter and the fused-kernel apply, at the widths and depth of phase 2;
   the two apply paths must agree, and the trainer must agree with the
   dense-matrix ``DecentralizedSimulator`` oracle.

Everything runs in this one process, which holds the chips.  The last line
of standard output is one JSON object; any failed check exits non-zero
before it is printed.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# phase 2: granite-8b at published widths, depth cut to fit 16 GB (AOT
# rehearsal for a v5e: 12.3 GiB peak of 15.75 GiB at 4 layers, seq 4096)
ONE_CHIP_TRAIN = [
    "--arch", "granite-8b", "--layers", "4", "--mesh", "1,1",
    "--topology", "d_ring", "--steps", "4", "--steps-per-epoch", "4",
    "--seq", "4096", "--per-node-batch", "1", "--lr", "0.02",
]
# phase 3: one granite-8b MLP matrix, which tiles in place, alone and on 4
# nodes; and internvl2-2b's output head, whose odd vocab (92553) takes the
# kernel's padded view
KERNEL_LEAF = (4096, 14336)
KERNEL_ODD_LEAF = (2048, 92553)
KERNEL_NODES = 4
# phase 4: the same model over 4 chips; 2 gossip neighbors' bf16 landing
# buffers add 2 x 2.4 GiB, so the sequence is cut to 1024 (AOT: 14.8 GiB)
FOUR_CHIP_TRAIN = [
    "--arch", "granite-8b", "--layers", "4", "--mesh", "4,1",
    "--steps", "3", "--steps-per-epoch", "1", "--seq", "1024",
    "--per-node-batch", "1", "--lr", "0.02",
]
# d_ada at 4 nodes starts on the k=2 ring lattice and, at gamma_k = 1 (the
# paper's ResNet50 setting), hands off to the one-peer exponential graphs
# after one epoch: 3 programs in 3 steps, degrees 2 then 1.
FOUR_CHIP_TOPOLOGIES = {
    "d_ring": ["--topology", "d_ring"],
    "d_ada": ["--topology", "d_ada", "--k-floor", "one_peer", "--gamma-k", "1"],
}

# bf16 tolerances.  A kernel output is rounded to bf16 once, from float32
# math done in another order than the reference's: at most one bf16 ulp
# (2^-8 relative) apart.  The fused and interpreter applies round their
# updates differently (the interpreter rounds the local step to bf16 before
# mixing), so after 3 steps they may differ by a few ulps of each leaf's
# largest value: 2^-5 of it (four ulps), and 2^-5 of the loss.  A bf16 CPU
# run at d_model 512 measured 1.4e-2 after 3 steps.
BF16_ULP = 2.0 ** -8
APPLY_PATHS_RTOL = 2.0 ** -5
# float32 at highest matmul precision: the CPU equivalence test's bar
ORACLE_ATOL = 5e-5


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def import_repro():
    if not (SRC / "repro").is_dir():
        fail(f"{SRC / 'repro'} not found: run this script from a checkout of the repo")
    sys.path.insert(0, str(SRC))


def device_info(count: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", f"JAX found no TPU (first device: {d.platform})")
    check(len(devs) >= count, f"need {count} TPU chip(s), found {len(devs)}")
    print(f"device: {d.platform} {d.device_kind} x{len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def free_device_memory() -> None:
    import jax

    gc.collect()
    jax.clear_caches()


def leaf_checksums(tree) -> list:
    import jax
    import jax.numpy as jnp

    return [float(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def train(argv: list):
    from repro.launch.train import main as train_main

    print("train:", " ".join(argv), flush=True)
    return train_main(argv)


# ---------------------------------------------------------------------------
# phase 2: one chip, the trainer's main path
# ---------------------------------------------------------------------------

def _rounding_stats(theta, mom, lr: float) -> tuple:
    """(max |m|, max over elements of lr * |m| / (eps/4 * |theta|)) of one
    leaf and its float32 momentum.  eps/4 * |x| is at most half an ulp on
    either side of x in the leaf's dtype, so a ratio under 1 means every
    element's update rounds back to the old value."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stats(theta, mom):
        upd = lr * jnp.abs(mom.astype(jnp.float32))
        room = jnp.finfo(theta.dtype).eps / 4 * jnp.abs(theta.astype(jnp.float32))
        return jnp.max(jnp.abs(mom)), jnp.max(upd / room)

    return tuple(float(x) for x in stats(theta, mom))


def phase_train(argv: list) -> None:
    import jax
    import numpy as np

    res = train(argv)
    secs = res.step_seconds
    steady = secs[1:]
    check(len(steady) >= 1, "need at least 2 steps to separate compile time")
    print(f"train: compile+first step {secs[0]:.2f} s; compile ~ "
          f"{secs[0] - float(np.median(steady)):.2f} s (first step minus "
          "the median later step)")
    print("train: step seconds after warm-up (host clock, ended by "
          "block_until_ready): " + " ".join(f"{s:.4f}" for s in steady))
    losses = [float(l.mean()) for l in res.losses]
    print("train: losses " + " ".join(f"{l:.5f}" for l in losses))
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"train: peak_bytes_in_use {peak} "
          f"({peak / 2**30:.2f} GiB)" if peak else
          "train: peak_bytes_in_use not reported")
    check(all(np.isfinite(l).all() for l in res.losses),
          f"non-finite loss: {losses}")

    # the parameters changed: final per-leaf checksums against a fresh
    # init from the same seed (the run donated its initial buffers)
    paths, _ = jax.tree_util.tree_flatten_with_path(res.state.params)
    names = [jax.tree_util.keystr(path) for path, _ in paths]
    final = leaf_checksums(res.state.params)
    moms = jax.tree.leaves(res.state.opt_state)
    check(len(moms) == len(names), "expected one momentum buffer per leaf")
    rounding = [_rounding_stats(x, m, res.lr)
                for x, m in zip(jax.tree.leaves(res.state.params), moms)]
    trainer = res.trainer
    del res, moms
    free_device_memory()
    init = trainer.init_state(jax.random.PRNGKey(0))
    first = leaf_checksums(init.params)
    del init, trainer
    free_device_memory()
    check(all(np.isfinite(final)), "the parameters are not finite")
    same = [i for i, (a, b) in enumerate(zip(final, first)) if a == b]
    print(f"train: {len(final) - len(same)}/{len(final)} parameter leaves "
          "changed")
    check(len(same) < len(final), "the parameters did not change")
    # a leaf the run left as it was must owe that to rounding alone: its
    # momentum is nonzero and finite (the step did update it), and its
    # last update lr * m rounds away in the leaf's dtype
    for i in same:
        m_max, ratio = rounding[i]
        print(f"train: unchanged leaf {names[i]}: max |m| {m_max:.3e}, max "
              f"over elements of lr*|m| / (eps/4 * |theta|) {ratio:.3e} "
              "(< 1: the update rounds away)")
        check(0 < m_max < float("inf") and ratio < 1,
              f"leaf {names[i]} did not change, and rounding does not explain it")


# ---------------------------------------------------------------------------
# phase 3: the fused gossip kernels, compiled for the chip
# ---------------------------------------------------------------------------

def _compare(name: str, got, want, rtol: float, atol: float) -> None:
    import jax.numpy as jnp

    got = got.astype(jnp.float32)
    err = jnp.abs(got - want)
    bound = rtol * jnp.abs(want) + atol
    worst = float(jnp.max(err - bound))
    print(f"kernels: {name}: max |err| {float(jnp.max(err)):.3e}, "
          f"max |ref| {float(jnp.max(jnp.abs(want))):.3e}, "
          f"rtol {rtol:.3e} atol {atol:.1e}: "
          f"{'ok' if worst <= 0 else 'FAIL'}")
    check(worst <= 0, f"{name} disagrees with kernels/ref.py")


def _timed(fn, *args, reps: int = 3):
    import jax

    out = jax.block_until_ready(fn(*args))
    secs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        secs.append(time.perf_counter() - t0)
    return out, secs


def phase_kernels(leaf: tuple, odd_leaf: tuple, n: int, *,
                  interpret=None) -> None:
    """``gossip_update`` on one node's leaf that tiles in place and on one
    that goes through the padded view, and ``gossip_program_update`` on n
    nodes' rows of the first leaf's size, each at degree 2 with a ring's
    weight rows.  Neighbors arrive as separate landing buffers, as the
    trainer's ppermutes deliver them."""
    import jax
    import jax.numpy as jnp

    from repro.core.graphs import Ring
    from repro.core.schedule import compile_graph
    from repro.kernels.gossip_update import gossip_program_update, gossip_update
    from repro.kernels.ref import gossip_update_ref

    lr, beta = 0.02, 0.9
    _, weights = compile_graph(Ring(n)).permute_tables()  # (n, 3)
    weights = jnp.asarray(weights, jnp.float32)
    f32 = lambda x: x.astype(jnp.float32).reshape(-1)

    def ref_one(t, nb, w, g, m):
        p, mm = gossip_update_ref(f32(t), jnp.stack([f32(x) for x in nb]), w,
                                  f32(g), f32(m), lr=lr, beta=beta)
        return p.reshape(t.shape), mm.reshape(t.shape)

    def one(t, nb, w, g, m):
        return gossip_update(t, nb, w, g, m, lr=lr, beta=beta, interpret=interpret)

    def stacked(t, nb, w, g, m):
        return gossip_program_update(t, nb, w, g, m, lr=lr, beta=beta,
                                     interpret=interpret)

    p = math.prod(leaf)
    cases = [
        ("gossip_update", one, ref_one, leaf, weights[0]),
        ("gossip_update", one, ref_one, odd_leaf, weights[0]),
        ("gossip_program_update", stacked, jax.vmap(ref_one), (n, p), weights),
    ]
    for name, fn, ref, shape, w in cases:
        k = jax.random.split(jax.random.PRNGKey(1), 5)
        theta = jax.random.normal(k[0], shape, jnp.bfloat16) * 0.02
        nbrs = tuple(jax.random.normal(k[1 + i], shape, jnp.bfloat16) * 0.02
                     for i in range(2))
        grad = jax.random.normal(k[3], shape, jnp.bfloat16) * 0.01
        mom = jax.random.normal(k[4], shape, jnp.float32) * 0.01
        args = (theta, nbrs, w, grad, mom)
        with jax.default_matmul_precision("highest"):
            want_p, want_m = jax.jit(ref)(*args)
        lowered = jax.jit(fn).lower(*args)
        kernel = "tpu_custom_call" in lowered.as_text()
        t0 = time.perf_counter()
        compiled = lowered.compile()
        print(f"kernels: {name} on {shape} {theta.dtype}, 2 neighbors: "
              f"compile {time.perf_counter() - t0:.2f} s, "
              f"tpu_custom_call in lowered program: {kernel}")
        if interpret is None:
            check(kernel, f"{name} was not compiled as a Mosaic kernel")
        (out_p, out_m), secs = _timed(compiled, *args)
        print(f"kernels: {name}: host-clock seconds per jitted call "
              + " ".join(f"{s:.5f}" for s in secs))
        # theta' is rounded to bf16 once: one bf16 ulp of the f32 reference
        _compare(f"{name} theta'", out_p, want_p, BF16_ULP, 1e-6)
        # m' is f32 on both sides: beta * m + g in another association
        _compare(f"{name} momentum'", out_m, want_m, 1e-6, 1e-7)
        del theta, nbrs, grad, mom, args, want_p, want_m, out_p, out_m, compiled
        free_device_memory()


# ---------------------------------------------------------------------------
# phase 4: four chips, gossip over ppermute
# ---------------------------------------------------------------------------

def _host_params(state) -> list:
    import jax
    import numpy as np

    return [np.asarray(jax.device_get(x)) for x in jax.tree.leaves(state.params)]


def _check_placement(res, n: int) -> None:
    import jax

    leaf = jax.tree.leaves(res.state.params)[0]
    devices = {s.device for s in leaf.addressable_shards}
    rows = {s.data.shape[0] for s in leaf.addressable_shards}
    print(f"four-chip: stacked params {leaf.shape} on {len(devices)} distinct "
          f"devices, {sorted(rows)} node row(s) per shard")
    check(len(devices) == n and rows == {1},
          f"stacked params are not one node per device: {devices}")
    spread = float(res.losses[-1].max() - res.losses[-1].min())
    print(f"four-chip: loss spread across nodes at the last step {spread:.6f}")
    check(spread > 0, "every node has the same loss: no decentralized state")


def _rel_leaf_diff(a_leaves, b_leaves) -> float:
    import numpy as np

    worst = 0.0
    for a, b in zip(a_leaves, b_leaves):
        a32, b32 = a.astype(np.float32), b.astype(np.float32)
        scale = float(np.max(np.abs(b32))) or 1.0
        worst = max(worst, float(np.max(np.abs(a32 - b32))) / scale)
    return worst


def phase_apply_paths(base: list, topologies: dict, n: int) -> None:
    """The trainer's entry point on n chips: interpreter vs fused apply."""
    import numpy as np

    for name, topo_args in topologies.items():
        runs = {}
        for apply in ("interpreter", "fused"):
            argv = base + topo_args + (["--fused-apply"] if apply == "fused" else [])
            res = train(argv)
            print(f"four-chip: {name} {apply}: step seconds (host clock, "
                  "ended by block_until_ready; a step that meets a new "
                  "mixing program compiles it) "
                  + " ".join(f"{t:.4f}" for t in res.step_seconds))
            _check_placement(res, n)
            check(all(np.isfinite(l).all() for l in res.losses),
                  f"{name} {apply}: non-finite loss")
            runs[apply] = (np.stack(res.losses), _host_params(res.state))
            del res
            free_device_memory()
        (la, pa), (lb, pb) = runs["fused"], runs["interpreter"]
        loss_rel = float(np.max(np.abs(la - lb) / np.abs(lb)))
        param_rel = _rel_leaf_diff(pa, pb)
        print(f"four-chip: {name}: fused vs interpreter apply: max loss "
              f"rel diff {loss_rel:.3e}, max param diff / leaf max "
              f"{param_rel:.3e} (bound {APPLY_PATHS_RTOL:.3e})")
        check(loss_rel <= APPLY_PATHS_RTOL and param_rel <= APPLY_PATHS_RTOL,
              f"{name}: fused and interpreter applies disagree")
        del runs
        gc.collect()


def oracle_config(d_model: int, layers: int):
    """granite-8b's family at a cut width: head_dim 128, 4:1 GQA, d_ff/d_model
    3.5 and the published vocab, in float32."""
    import dataclasses

    import jax.numpy as jnp

    from repro.configs import get_config

    heads = d_model // 128
    return dataclasses.replace(
        get_config("granite-8b"), n_layers=layers, d_model=d_model,
        n_heads=heads, n_kv=max(heads // 4, 1), d_ff=d_model * 7 // 2,
        dtype=jnp.float32,
    )


def phase_oracle(cfg, n: int, seq: int, topologies: dict, steps: int = 3) -> None:
    """SPMD trainer (both applies) vs the dense-matrix simulator, float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.dsgd import make_topology
    from repro.core.simulator import DecentralizedSimulator
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.launch.train import SPMDTrainer
    from repro.models import transformer as tfm
    from repro.optim.sgd import sgd

    mesh = make_mesh((n, 1), ("data", "model"))
    src = SyntheticLM(vocab=cfg.vocab, seq_len=seq, seed=0)
    batches = [{k: jnp.asarray(v) for k, v in src.stacked(n, t, 1).items()}
               for t in range(steps)]
    key = jax.random.PRNGKey(42)
    lr = 0.02
    topo_kw = {"d_ring": {}, "d_ada": {"k_floor": "one_peer", "gamma_k": 1.0}}
    for name in topologies:
        results = {}
        with jax.default_matmul_precision("highest"):
            for apply in ("interpreter", "fused"):
                tr = SPMDTrainer(cfg, mesh, make_topology(name, n, **topo_kw[name]),
                                 sgd(momentum=0.9), fused_apply=apply == "fused")
                st = tr.init_state(key)
                losses = []
                for t, b in enumerate(batches):
                    st, loss, _ = tr.train_step(st, b, lr, epoch=t)
                    losses.append(np.asarray(loss))
                results[apply] = (np.stack(losses), _host_params(st))
                del tr, st
                free_device_memory()
            sim = DecentralizedSimulator(
                lambda p, b: tfm.loss_fn(p, cfg, b), sgd(momentum=0.9),
                make_topology(name, n, **topo_kw[name]), mixing="dense",
                shard_nodes=True,
            )
            st = sim.init(tfm.init_model(cfg, key, tp_size=1))
            losses = []
            for t, b in enumerate(batches):
                st, loss, _ = sim.train_step(st, b, lr, epoch=t)
                losses.append(np.asarray(loss))
            want_l, want_p = np.stack(losses), _host_params(st)
            del sim, st
            free_device_memory()
        for apply, (got_l, got_p) in results.items():
            pdiff = max(float(np.max(np.abs(a - b))) for a, b in zip(got_p, want_p))
            ldiff = float(np.max(np.abs(got_l - want_l)))
            print(f"oracle: {name} {apply} apply vs dense simulator over "
                  f"{steps} steps: MAXDIFF {pdiff:.3e} LOSSDIFF {ldiff:.3e} "
                  f"(bound {ORACLE_ATOL:.0e})")
            check(pdiff <= ORACLE_ATOL and ldiff <= ORACLE_ATOL,
                  f"{name} {apply}: trainer disagrees with the dense oracle")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip gossip phase")
    args = ap.parse_args(argv)
    import_repro()
    from repro.launch.train import use_repo_compile_cache

    use_repo_compile_cache()
    if args.four_chips:
        device = device_info(4)
        phase_apply_paths(FOUR_CHIP_TRAIN, FOUR_CHIP_TOPOLOGIES, 4)
        # The dense oracle holds all 4 float32 replicas on every chip (W θ
        # needs each node's θ), next to the trainer's state; at d_model 2048
        # that is 4 x 1.2 GB.  The published d_model 4096 needs 2.5 GB per
        # replica at one layer (the 49152-word embedding and head alone are
        # 1.6 GB), over 16 GB with the state.
        cfg = oracle_config(2048, 2)
        print(f"oracle: granite-8b family at d_model {cfg.d_model}, "
              f"{cfg.n_heads}/{cfg.n_kv} heads, d_ff {cfg.d_ff}, vocab "
              f"{cfg.vocab}, {cfg.n_layers} layers, float32, highest matmul "
              "precision: the largest width tried at which 4 float32 "
              "replicas fit each chip beside the trainer state")
        phase_oracle(cfg, 4, 512, FOUR_CHIP_TOPOLOGIES)
    else:
        device = device_info(1)
        phase_train(ONE_CHIP_TRAIN)
        phase_kernels(KERNEL_LEAF, KERNEL_ODD_LEAF, KERNEL_NODES)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
