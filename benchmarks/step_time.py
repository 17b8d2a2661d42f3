"""Microbenchmarks: mixing-program classes, fusion, and overlap scheduling.

One row per *program class* — circulant (ring), matching (pairwise
averaging), edge_colored (star: the PR-3 sparse decomposition), and gather
(the dense GatherRow all-gather the star used to compile to) — with
median/p90 apply wall time and the analytic bytes-on-wire per node.  A
second block measures multi-step fusion: a full one-peer exponential cycle
as H separate dispatches vs ONE fused executable (``GossipProgram.fuse``).

``run_overlap`` (the ``overlap`` section) measures bucketed overlap
scheduling at the gossip-dispatch level on an 8-host-device mesh: one
closed-loop mixing step — SGD update, program permutes, Ξ_t probe — as
(a) a monolithic executable plus the standalone whole-tree probe
dispatch, vs (b) token-chained per-bucket dispatches with the probe
FOLDED into the bucket passes (``core/buckets.py``).  It runs in a
subprocess because the 8-device ``xla_force_host_platform_device_count``
flag must be set before jax initializes, and the other sections time
single-device dispatches.  Expected shape: deep permute schedules
(edge-colored star: Δ+1 sequential matching rounds) win from pipelining
bucket i's rendezvous against bucket i+1's compute; shallow one-permute
schedules (ring, one-peer) pay the extra dispatches instead.

Timing uses per-call samples (best/median/p90) because the 2-CPU CI box is
noisy; bytes come from ``program_comm_bytes`` (mean per node) and
``program_max_node_bytes`` (busiest node), both validated against HLO
collective parses elsewhere.  Everything lands in the committed
``BENCH_step_time.json`` so the perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, save_bench_section, save_json
from repro.core.graphs import Star, make_graph, one_peer_period, random_matching
from repro.core.schedule import (
    GossipProgram, compile_graph, dense_program, program_comm_bytes,
    program_max_node_bytes,
)

DEFAULT_BUCKET_MB = 1.0  # the sweep value the acceptance row is read at


def _sample(fn, *args, reps=20):
    """Per-call wall-time samples in µs (first call = compile, excluded)."""
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(1e6 * (time.perf_counter() - t0))
    return out


def _stats(samples):
    return {
        "best_us": float(np.min(samples)),
        "median_us": float(np.median(samples)),
        "p90_us": float(np.percentile(samples, 90)),
    }


def _program_classes(n: int):
    """One representative compiled program per class."""
    star = Star(n)
    return {
        "circulant": compile_graph(make_graph("ring", n)),
        "matching": compile_graph(random_matching(n, seed=0)),
        "edge_colored": compile_graph(star),
        "gather": dense_program(star),
    }


def run(*, quick: bool = False) -> list[Row]:
    rows, payload = [], {}
    n = 16
    reps = 8 if quick else 20
    sizes = (1 << 14,) if quick else (1 << 16, 1 << 20)
    for size in sizes:
        x = {"w": jax.random.normal(jax.random.PRNGKey(0), (n, size))}
        param_bytes = 4 * size
        for cls, prog in _program_classes(n).items():
            fn = jax.jit(prog.apply_stacked)
            stats = _stats(_sample(fn, x, reps=reps))
            stats["bytes_per_node"] = program_comm_bytes(prog, param_bytes)
            stats["max_node_bytes"] = program_max_node_bytes(prog, param_bytes)
            stats["n_collectives"] = prog.num_collectives
            payload[f"{cls}/n{n}/p{size}"] = stats
            rows.append(
                Row(
                    f"mixing/{cls}/p{size}",
                    stats["median_us"],
                    f"median_us={stats['median_us']:.0f} "
                    f"p90_us={stats['p90_us']:.0f} "
                    f"bytes_per_node={stats['bytes_per_node']} "
                    f"ops={stats['n_collectives']}",
                )
            )

    # -- multi-step fusion: H one-peer dispatches vs one fused executable ----
    size = sizes[0]
    x = {"w": jax.random.normal(jax.random.PRNGKey(1), (n, size))}
    period = one_peer_period(n)
    progs = [
        compile_graph(make_graph("one_peer_exponential", n, step=t))
        for t in range(period)
    ]
    fns = [jax.jit(p.apply_stacked) for p in progs]

    def run_separate(v):
        for f in fns:
            v = f(v)
        return v

    fused = GossipProgram.fuse(progs)
    fused_fn = jax.jit(fused.apply_stacked)
    sep = _stats(_sample(run_separate, x, reps=reps))
    fus = _stats(_sample(fused_fn, x, reps=reps))
    fusion = {
        "period": period,
        "separate": {**sep, "executables": len(fns)},
        "fused": {**fus, "executables": 1},
        "dispatch_reduction": f"{len(fns)}->1",
    }
    payload["fusion/one_peer"] = fusion
    rows.append(
        Row(
            "fusion/one_peer",
            fus["median_us"],
            f"H={period} separate_us={sep['median_us']:.0f} "
            f"fused_us={fus['median_us']:.0f} executables={len(fns)}->1",
        )
    )

    save_json("step_time", payload)
    save_bench_section("step_time", payload)
    return rows


# -- overlap-scheduled gossip: monolithic+probe vs bucketed+fold -------------

OVERLAP_TOPOS = ("d_ring", "d_star", "d_one_peer_exp")


def _overlap_worker(quick: bool) -> dict:
    """Subprocess body (8 host devices): one closed-loop mixing step per
    variant.  Monolithic = jitted update+permutes over the whole (n, P)
    matrix, then the standalone Ξ probe executable.  Bucketed = the
    engines' per-bucket chain — ``build_bucket_step`` dispatches threaded
    on the Ξ² token under the bounded window, probe folded, host √ last.
    """
    from collections import deque

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.buckets import (
        MAX_INFLIGHT_BUCKETS, BucketLayout, build_bucket_step,
        xi_from_folded_sq,
    )
    from repro.core.dsgd import make_topology
    from repro.launch.mesh import make_mesh
    from repro.optim.sgd import sgd

    n = 8
    size = (1 << 18) if quick else (1 << 20)
    reps = 8 if quick else 16
    mbs = (0.25, DEFAULT_BUCKET_MB) if quick else (0.5, DEFAULT_BUCKET_MB, 2.0)

    mesh = make_mesh((n,), ("gossip",))
    lead2 = NamedSharding(mesh, P("gossip", None))
    rep_s = NamedSharding(mesh, P())
    gvec = NamedSharding(mesh, P("gossip"))
    hyper = sgd(momentum=0.9).hyper
    beta = hyper["momentum"]
    rng = np.random.default_rng(0)
    theta = jax.device_put(
        jnp.asarray(rng.normal(size=(n, size)).astype(np.float32)), lead2
    )
    mom = jax.device_put(jnp.zeros((n, size), jnp.float32), lead2)
    grad = jax.device_put(
        jnp.asarray(rng.normal(size=(n, size)).astype(np.float32)), lead2
    )
    lr = jnp.float32(0.05)

    payload = {}
    for topo_name in OVERLAP_TOPOS:
        prog = make_topology(topo_name, n).program_at(step=0, epoch=0)
        rounds = len(prog.ops)

        def mono_step(t, m, g, lr):
            new_m = beta * m + g
            return prog.apply_stacked(t - lr * new_m), new_m

        def probe(t):
            d = t - t.mean(axis=0)
            return jnp.sqrt(jnp.mean(jnp.sum(d * d, axis=-1)))

        mono = jax.jit(
            mono_step, in_shardings=(lead2, lead2, lead2, rep_s),
            out_shardings=(lead2, lead2),
        )
        probe_j = jax.jit(probe, in_shardings=(lead2,), out_shardings=rep_s)

        def run_mono():
            t2, m2 = mono(theta, mom, grad, lr)
            xi = probe_j(t2)
            jax.block_until_ready((t2, m2, xi))
            return float(xi)

        stats = _stats(_sample(run_mono, reps=reps))
        stats.update(probe="standalone", permute_rounds=rounds,
                     bucket_mb=None, num_buckets=1)
        payload[f"{topo_name}/mono/n{n}"] = stats

        step = build_bucket_step(prog, hyper=hyper, has_momentum=True)
        for mb in mbs:
            layout = BucketLayout.for_stacked({"w": theta}, mb)
            fns = {
                w: jax.jit(
                    step,
                    in_shardings=(lead2, lead2, lead2, rep_s, gvec),
                    out_shardings=(lead2, lead2, gvec),
                )
                for w in set(layout.widths)
            }
            bounds = layout.bounds

            def run_buck():
                tok = jax.device_put(jnp.zeros((n,), jnp.float32), gvec)
                outs = []
                window: deque = deque()
                for b, w in enumerate(layout.widths):
                    if len(window) >= MAX_INFLIGHT_BUCKETS:
                        jax.block_until_ready(window.popleft())
                    lo, hi = bounds[b], bounds[b + 1]
                    t2, m2, tok = fns[w](
                        theta[:, lo:hi], mom[:, lo:hi], grad[:, lo:hi],
                        lr, tok,
                    )
                    outs.append((t2, m2))
                    window.append(tok)
                jax.block_until_ready((outs, tok))
                return xi_from_folded_sq(tok)

            stats = _stats(_sample(run_buck, reps=reps))
            stats.update(probe="folded", permute_rounds=rounds,
                         bucket_mb=mb, num_buckets=layout.num_buckets)
            payload[f"{topo_name}/mb{mb}/n{n}"] = stats
    return payload


def run_overlap(*, quick: bool = False) -> list[Row]:
    """The ``overlap`` section — spawned as a subprocess so the 8-device
    host-platform flag never leaks into the other sections' timings."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "benchmarks.step_time", "--overlap-worker"]
    if quick:
        cmd.append("--quick")
    r = subprocess.run(
        cmd, capture_output=True, text=True, timeout=1800, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    if r.returncode != 0:
        raise RuntimeError(f"overlap worker failed:\n{r.stderr[-3000:]}")
    payload = json.loads(r.stdout)
    # no engine runs here (raw-kernel microbench), so derive each entry's
    # provenance by billing the measured program through a recorder: one
    # application at the worker's vector width = the bytes each rep moved
    from repro.core.dsgd import make_topology
    from repro.telemetry import MemorySink, MetricsRecorder

    n, size = 8, (1 << 18) if quick else (1 << 20)
    recs = {}
    for key in payload:
        topo_name = key.split("/")[0]
        rec = MetricsRecorder(sinks=[MemorySink()], metrics_every=0)
        rec.comm(
            make_topology(topo_name, n).program_at(step=0, epoch=0),
            size * 4, step=0,
        )
        recs[key] = rec
    rows = [
        Row(
            f"overlap/{key}",
            stats["median_us"],
            f"median_us={stats['median_us']:.0f} "
            f"p90_us={stats['p90_us']:.0f} probe={stats['probe']} "
            f"buckets={stats['num_buckets']} rounds={stats['permute_rounds']}",
        )
        for key, stats in payload.items()
    ]
    save_json("overlap", payload)
    save_bench_section("overlap", payload, telemetry=recs)
    return rows


if __name__ == "__main__":
    if "--overlap-worker" in sys.argv:
        print(json.dumps(_overlap_worker(quick="--quick" in sys.argv)))
    else:
        sys.exit("usage: python -m benchmarks.step_time --overlap-worker "
                 "[--quick]  (sections run via benchmarks.run)")
