"""Chip benchmark of the decentralized trainer, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files under ``bench/`` say what runs: ``workloads/<cell>.json``
names the configuration (``configs/``), the data mix (``traffic/``), the
mesh, the trainer options and the limits of the correctness check.

Set-up (``setup_s``, from process start to the first timed step): find the
chips, build ``SPMDTrainer`` as the training CLI does, make the weights on
the device from the seed, and run the first three steps through the
window's own call and feed (the first compiles, or loads from the compile
cache).  Their losses, the first gradient's norms (read from the momentum
after step one) and the parameters' change after step three are kept for
the check.

Set-up ends with a full garbage collection, and what survives it is
frozen, so that no collection inside the window walks set-up's objects.

Window: steps run for ``--seconds``.  Each step builds its rows on the
host with the program's own ``repro.data.SyntheticLM``, dispatches
``train_step`` and then waits for the previous step's loss, so at most one
step queues behind the running one.  ``tokens_per_s`` is the tokens of all
nodes over the window; ``step_p90_s`` the 90th percentile of the intervals
between consecutive step completions, over every step of the window.  With
``--trace 1`` the window is traced and the per-layer metrics are read from
the trace instead.  Every interval over 1.25 times the median is logged on
standard error with the host's time in each phase of its step and the
garbage collections that ran in it.

After the window: the peak device memory is read, the program's state is
freed, and the plain reference (``reference/<family>.py``, float32) runs
the same first three steps from the same seed; ``correct`` holds when
every window loss is finite and each compared number is within its limit.

The last line of standard output is one JSON object; a run that finds no
TPU, or fewer chips than the cell needs, prints none and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

from benchlib.files import Bench, BenchError  # noqa: E402

COMPARED_STEPS = 3
TOP_ENTRIES = 10


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def program_config(cfg: dict, ref_mod):
    """The program's ArchConfig for the configuration file: the arch's
    registered config with the file's sizes and dtype."""
    import dataclasses

    from benchlib.refstep import DTYPES
    from repro.configs import get_config

    base = get_config(cfg["arch"])
    return dataclasses.replace(base, dtype=DTYPES[cfg["dtype"]],
                               **ref_mod.program_sizes(cfg))


def check_tree(made, abstract) -> None:
    import jax

    if jax.tree.structure(made) != jax.tree.structure(abstract):
        raise BenchError("the reference's parameter tree does not match the "
                         f"program's:\n{jax.tree.structure(made)}\n"
                         f"{jax.tree.structure(abstract)}")
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(abstract)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise BenchError(f"leaf {a.shape} {a.dtype} != program's {b.shape} {b.dtype}")


def find_chips(cell: dict, require_tpu: bool):
    """(devices, device kind), or None where the cell cannot run here."""
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    if require_tpu and devices[0].platform != "tpu":
        log(f"JAX found no TPU (first device: {devices[0].platform} {kind})")
        return None
    if len(devices) < cell["chips"]:
        log(f"the cell needs {cell['chips']} chips, JAX found {len(devices)}")
        return None
    log(f"device {devices[0].platform} {kind} x{len(devices)}; cell {cell['name']}")
    return devices, kind


class Program:
    """The program under test, built for one cell as the training CLI builds
    it: ``SPMDTrainer`` over the cell's mesh and topology, momentum SGD,
    gradient-norm collection on, the cell's apply path."""

    def __init__(self, bench: Bench, cell: dict):
        import jax

        from benchlib.refstep import lr_of
        from repro.core.dsgd import make_topology
        from repro.launch.mesh import make_mesh
        from repro.launch.train import SPMDTrainer
        from repro.optim.sgd import sgd

        self.cell = cell
        self.cfg = bench.config(cell["config"])
        self.traffic = bench.traffic(cell["traffic"])
        self.ref_mod = bench.reference(self.cfg["family"])
        self.pcfg = program_config(self.cfg, self.ref_mod)
        self.n = n = cell["mesh"][0]
        self.mesh = make_mesh(tuple(cell["mesh"]), ("data", "model"))
        self.devices = list(self.mesh.devices.flat)
        self.trainer = SPMDTrainer(
            self.pcfg, self.mesh, make_topology(cell["topology"], n),
            sgd(momentum=cell["momentum"]), collect_norms=True,
            fused_apply=cell["fused_apply"],
        )
        self.stacked = n > 1
        self.lr = lr_of(cell)
        self.tokens_per_step = n * self.traffic["per_node_batch"] * self.traffic["seq"]
        rows = self.rows(0, 0)
        self.batch_sharding = self.trainer.batch_shardings(rows)
        key = jax.random.PRNGKey(0)
        check_tree(jax.eval_shape(self.theta0, key), self.trainer.abstract_state[0])
        from benchlib.refstep import change_norms, leaf_norms

        def make(k):
            p = self.theta0(k)
            return p, self.trainer.optimizer.init(p)

        self._make = jax.jit(make, out_shardings=(
            self.trainer.param_shardings, self.trainer.opt_shardings))
        self._grad_norms = jax.jit(lambda o: leaf_norms(o, self.stacked))
        self._change_norms = jax.jit(lambda p, p0: change_norms(p, p0, self.stacked))

    def theta0(self, key):
        """The weights from the seed's key, as the reference makes them,
        stacked over the nodes when there are several."""
        import jax
        import jax.numpy as jnp

        p = self.ref_mod.init(self.cfg, key, self.pcfg.dtype)
        if self.stacked:
            p = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (self.n,) + x.shape), p)
        return p

    def rows(self, step: int, seed: int) -> dict:
        """The program's own rows (``repro.data.SyntheticLM``), as the
        training CLI builds them; the reference makes the same rows with
        the yardstick's copy (``benchlib/data.py``)."""
        from repro.data import SyntheticLM

        t = self.traffic
        src = SyntheticLM(vocab=self.cfg["vocab_size"], seq_len=t["seq"],
                          seed=seed, structure=t["structure"])
        return src.stacked(self.n, step, t["per_node_batch"])

    def feed(self, step: int, seed: int):
        """One step's rows, built on the host and handed to the device."""
        import jax

        with jax.profiler.TraceAnnotation("bench.batch"):
            return jax.device_put(self.rows(step, seed), self.batch_sharding)

    def step(self, state, batch):
        return self.trainer.train_step(state, batch, self.lr)

    def start(self, seed: int):
        """The weights from the seed, then the compared steps through the
        window's own call and feed: (state, readings)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchlib.refstep import seed_key
        from repro.launch.train import TrainState

        key = seed_key(seed)
        with jax.set_mesh(self.mesh):
            params, opt = self._make(key)
        theta0 = jax.tree.map(jnp.copy, params)   # the steps donate params
        state = TrainState(params, opt, 0)
        del params, opt
        losses, grad_norms = [], None
        for t in range(COMPARED_STEPS):
            state, loss, _ = self.step(state, self.feed(t, seed))
            losses.append(np.asarray(loss).reshape(-1))
            if t == 0:
                grad_norms = np.asarray(self._grad_norms(state.opt_state))
        change = np.asarray(self._change_norms(state.params, theta0))
        del theta0
        return state, {"losses": np.stack(losses), "grad_norms": grad_norms,
                       "change_norms": change}


class GcPauses:
    """Start, end and generation of each garbage collection, host clock."""

    def __init__(self):
        self.pauses, self._t = [], None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((self._t, time.perf_counter(), info["generation"]))

    def within(self, a: float, b: float) -> list:
        return [(g, round(e - s, 6)) for s, e, g in self.pauses if s < b and e > a]


def log_slow_intervals(done, phases, pauses: GcPauses, t0: float) -> None:
    """Every interval between completions over 1.25 times the median, with
    the host's seconds in each phase of the step that ended it."""
    import numpy as np

    iv = np.diff(done)
    med = float(np.median(iv))
    for i in np.flatnonzero(iv > 1.25 * med):
        a, b = done[i], done[i + 1]
        log(f"slow interval {i + 1} of {len(iv)}: {iv[i]:.6f} s (median {med:.6f}), "
            f"ends {b - t0:.3f} s into the window; phases "
            f"{json.dumps(phases[i + 1])}; gc {pauses.within(a, b)}")
    gen2 = [e - s for s, e, g in pauses.pauses if g == 2]
    log(f"gc in the window: {len(pauses.pauses)} collections, "
        f"{len(gen2)} of generation 2 ({sum(gen2):.6f} s)")


def use_compile_cache() -> None:
    """JAX's persistent compile cache at the program's fixed place in the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), for every program."""
    import jax

    from repro.launch.train import use_repo_compile_cache

    use_repo_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run(args, *, root: Path = BENCH_DIR, src: Path | None = None,
        require_tpu: bool = True, compile_cache: bool = True) -> dict | None:
    bench = Bench(root)
    cell = bench.workload(args.workload)
    limits = cell.get("limits")
    if not limits:
        raise BenchError(f"cell {args.workload} has no limits for its correctness "
                         "check: they are set from bench/control.py's readings")
    src = Path(src) if src else root.parent / "src"
    if not (src / "repro").is_dir():
        log(f"the program under test is not at {src / 'repro'}")
        return None
    sys.path.insert(0, str(src))

    import jax
    import numpy as np

    found = find_chips(cell, require_tpu)
    if found is None:
        return None
    devices, kind = found
    peaks = bench.peaks(kind) if require_tpu else None
    if compile_cache:
        use_compile_cache()

    from benchlib.check import gaps, judge
    from benchlib.refstep import Reference, degree

    prog = Program(bench, cell)
    cfg, traffic, used, seed = prog.cfg, prog.traffic, prog.devices, args.seed
    state, readings = prog.start(seed)
    gc.collect()
    gc.freeze()

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0    # host annotations, not every call
        options.host_tracer_level = 2
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    setup_s = time.perf_counter() - T_START

    # -- the timed window --
    done, window_losses = [], []
    phases = []     # per step: host seconds building rows, dispatching, waiting
    step = COMPARED_STEPS
    pending = None
    t0 = time.perf_counter()
    deadline = t0 + args.seconds
    with jax.profiler.TraceAnnotation("bench.window"):
        batch = prog.feed(step, seed)
        t_fed = time.perf_counter() - t0
        while True:
            t_a = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                state, loss, _ = prog.step(state, batch)
            step += 1
            t_b = time.perf_counter()
            if pending is not None:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    window_losses.append(np.asarray(pending))
                done.append(time.perf_counter())
                phases.append({"batch": round(t_fed, 6), "dispatch": round(t_b - t_a, 6),
                               "wait": round(done[-1] - t_b, 6)})
            pending = loss
            if done and done[-1] >= deadline:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    window_losses.append(np.asarray(pending))
                done.append(time.perf_counter())
                phases.append({"batch": 0.0, "dispatch": 0.0,
                               "wait": round(done[-1] - done[-2], 6)})
                break
            t_c = time.perf_counter()
            batch = prog.feed(step, seed)
            t_fed = time.perf_counter() - t_c
    window_s = done[-1] - t0
    gc.callbacks.remove(pauses)
    gc.unfreeze()
    if trace_dir:
        jax.profiler.stop_trace()

    tokens_per_s = len(done) * prog.tokens_per_step / window_s
    intervals = np.diff(done)
    step_p90_s = float(np.percentile(intervals, 90))
    finite = [bool(np.all(np.isfinite(x))) for x in window_losses]
    log(f"window: {len(done)} steps in {window_s:.3f} s, setup {setup_s:.3f} s, "
        f"slowest intervals {sorted(intervals.tolist())[-3:]}")
    log_slow_intervals(done, phases, pauses, t0)

    stats = [d.memory_stats() or {} for d in used]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    leaf_sizes = [int(np.prod(x.shape[1:] if prog.stacked else x.shape))
                  for x in jax.tree.leaves(state.params)]
    itemsize = np.dtype(prog.pcfg.dtype).itemsize
    ref_mod = prog.ref_mod
    del state, loss, pending, batch, prog
    gc.collect()
    jax.clear_caches()

    # -- per-layer metrics from the trace --
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {}
    if trace_dir:
        from benchlib import trace as tr

        trace = tr.load(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        names = sorted(trace.ops)
        if used and devices[0].platform == "tpu":
            names = [tr.DEVICE_PREFIX + str(d.id) for d in used]
        ctx = types.SimpleNamespace(
            trace=trace, devices=names, steps=len(done), chips=len(used),
            tokens_per_s=tokens_per_s, peaks=peaks, cell=cell, config=cfg,
            flops_per_token=bench.flops(cfg["family"]).flops_per_token(
                cfg, traffic["seq"]),
            leaf_sizes=leaf_sizes, param_itemsize=itemsize,
            degree=degree(cell),
        )
        metrics = {}
        for m in bench.per_layer_metrics(cell["name"]):
            value = bench.metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy = [tr.busy_s(trace, d) for d in names]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = trace.window_s
        ops: dict = {}
        for d in names:
            for name, secs in tr.op_seconds(trace, d).items():
                ops[name] = ops.get(name, 0.0) + secs / len(names)
        gaps_all = [g for d in names for g in tr.idle_gaps(trace, d)]
        result["breakdown"] = {
            "device_ops": sorted(ops.items(), key=lambda x: -x[1])[:TOP_ENTRIES],
            "idle_gaps": sorted(gaps_all, key=lambda x: -x[1])[:TOP_ENTRIES],
        }
    else:
        metrics = {
            "setup_s": {"value": setup_s, "unit": "s"},
            "tokens_per_s": {"value": tokens_per_s, "unit": "tokens/s"},
            "step_p90_s": {"value": step_p90_s, "unit": "s"},
        }

    # -- the plain reference, after the program's state is freed --
    t_ref = time.perf_counter()
    ref = Reference(ref_mod, cfg, traffic, cell, devices=used).run(
        seed, COMPARED_STEPS)
    values = gaps(readings, ref)
    log("gaps: " + json.dumps(values))
    ok, checks = judge(values, limits)
    checks["finite_window_losses"] = {"value": len(finite) - sum(finite), "limit": 0}
    correct = ok and all(finite)
    log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")

    return {
        "correct": bool(correct), "attempted": len(done),
        "failed": len(finite) - sum(finite), "metrics": metrics,
        "device": device, **result, "checks": checks,
    }


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run(args)
    except BenchError as e:
        log(str(e))
        return 2
    if out is None:
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
