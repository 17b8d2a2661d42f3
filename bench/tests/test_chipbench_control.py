"""The correctness check fails what it must fail.

At a CPU size, with the limits of the cells they stand for: in bfloat16,
as the configurations state it, the float8 control put in the program's
place is not correct and strays further than the program; in float32,
where the sound program passes, a run whose timed path is broken
underneath (state returned unchanged, half of the rows left out of the
mean, one leaf's update lost, the exchange between chips left out) comes
out with ``correct`` false.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from chipbench_util import BENCH, SRC, run_args, tiny_bench

SEED = 2**32 + 11


def _readings(root, precision="f32"):
    import run
    from benchlib.files import Bench
    from benchlib.refstep import Reference

    bench = Bench(root)
    cell = bench.workload("tiny")
    prog = run.Program(bench, cell)
    state, program = prog.start(SEED)
    del state
    ref = lambda p: Reference(prog.ref_mod, prog.cfg, prog.traffic, cell,
                              devices=prog.devices, precision=p).run(SEED)
    return cell, program, ref("f32"), ref(precision)


@pytest.mark.parametrize("cell", ["granite-1chip-s4096", "rwkv6-1chip-s4096"])
def test_float8_control_is_not_correct(tmp_path, cell):
    from benchlib.check import gaps, judge

    root = tiny_bench(tmp_path, cell, dtype="bfloat16")
    w, program, reference, control = _readings(root, "fp8")
    ok, checks = judge(gaps(control, reference), w["limits"])
    assert not ok, checks
    assert gaps(control, reference)["loss_gap"] > 3 * gaps(program, reference)["loss_gap"]


def _state_unchanged(orig):
    import jax
    import jax.numpy as jnp
    from repro.launch.train import TrainState

    def step(self, state, batch, lr, **kw):
        keep = jax.tree.map(jnp.copy, (state.params, state.opt_state))
        new, loss, norms = orig(self, state, batch, lr, **kw)
        return TrainState(*keep, new.step), loss, norms
    return step


def _half_batch(orig):
    def step(self, state, batch, lr, **kw):
        t = batch["targets"]
        t = (t.at[:, t.shape[1] // 2:].set(-1) if t.shape[1] > 1
             else t.at[..., t.shape[2] // 2:].set(-1))
        return orig(self, state, {**batch, "targets": t}, lr, **kw)
    return step


def _leaf_lost(orig):
    import jax
    import jax.numpy as jnp
    from repro.launch.train import TrainState

    def step(self, state, batch, lr, **kw):
        p, o = jax.tree.leaves(state.params), jax.tree.leaves(state.opt_state)
        i = int(np.argmax([x.size for x in p]))
        old_p, old_o = jnp.copy(p[i]), jnp.copy(o[i])
        new, loss, norms = orig(self, state, batch, lr, **kw)
        p, o = jax.tree.leaves(new.params), jax.tree.leaves(new.opt_state)
        p[i], o[i] = old_p, old_o
        return TrainState(jax.tree.unflatten(jax.tree.structure(new.params), p),
                          jax.tree.unflatten(jax.tree.structure(new.opt_state), o),
                          new.step), loss, norms
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _leaf_lost],
                         ids=["state_unchanged", "half_batch", "leaf_lost"])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    import run
    from repro.launch.train import SPMDTrainer

    root = tiny_bench(tmp_path, "granite-1chip-s4096")
    monkeypatch.setattr(SPMDTrainer, "train_step", fault(SPMDTrainer.train_step))
    out = run.run(run_args(seconds=0.5), root=root, src=SRC, require_tpu=False,
                  compile_cache=False)
    assert out is not None and not out["correct"], out and out["checks"]


SCRIPT = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{bench!r}, {src!r}, {tests!r}]
    from pathlib import Path
    import jax
    import run
    from chipbench_util import run_args
    root = Path({root!r})
    sound = run.run(run_args(seconds=0.5), root=root, src={src!r},
                    require_tpu=False, compile_cache=False)
    jax.lax.ppermute = lambda x, axis_name, perm: x   # the exchange left out
    broken = run.run(run_args(seconds=0.5), root=root, src={src!r},
                     require_tpu=False, compile_cache=False)
    print(json.dumps([sound["correct"], broken["correct"], broken["checks"]]))
""")


def test_exchange_left_out_is_not_correct(tmp_path):
    """Four CPU devices stand for the four chips of the gossip cell."""
    root = tiny_bench(tmp_path, "granite-ring4-s1024", rows=1)
    script = SCRIPT.format(bench=str(BENCH), src=str(SRC), root=str(root),
                           tests=str(BENCH / "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    sound, broken, checks = json.loads(out.stdout.strip().splitlines()[-1])
    assert sound and not broken, checks
