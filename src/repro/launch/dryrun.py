import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh).

This is how the distribution config is proven coherent without hardware:
512 placeholder host devices build the production meshes; every step
function must ``.lower().compile()`` and report its memory/cost analysis
and collective schedule.  Results stream into a JSON artifact consumed by
``launch/roofline.py`` and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun \
      [--arch all] [--shape all] [--mesh single,multi] \
      [--topology d_ada] [--mixing ppermute] [--out dryrun_results.json]
"""

import argparse
import json
import re
import time
import traceback


_COLL_RE = re.compile(
    r"(\w[\w\.\-]*)\s*=\s*((?:\([^)]*\))|(?:\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\b"
)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s64|s32|u32|s16|u16|s8|u8|pred)\[([\d,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}
_DTYPE_BYTES.update({f"f8{suf}": 1 for suf in ("e4m3fn", "e5m2", "e4m3", "e4m3b11fnuz")})


def _type_bytes(type_str: str) -> int:
    """Total bytes of an HLO type string (handles tuples)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-op-kind (count, result bytes, est. wire bytes/device)."""
    stats: dict[str, dict] = {}
    for m in _COLL_RE.finditer(hlo_text):
        _, type_str, kind = m.groups()
        b = _type_bytes(type_str)
        # wire-byte model per device: all-reduce ring = 2N; gather/scatter/
        # permute/alltoall move ~their result/input once.
        wire = 2 * b if kind == "all-reduce" else b
        s = stats.setdefault(kind, {"count": 0, "result_bytes": 0, "wire_bytes": 0})
        s["count"] += 1
        s["result_bytes"] += b
        s["wire_bytes"] += wire
    stats["total_wire_bytes"] = sum(
        v["wire_bytes"] for k, v in stats.items() if isinstance(v, dict)
    )
    return stats


def _apply_overrides(cfg, override: str):
    """--override "remat=False,capacity_factor=2.0" -> dataclasses.replace."""
    import dataclasses

    if not override:
        return cfg
    kw = {}
    for item in override.split(","):
        k, v = item.split("=", 1)
        cur = getattr(cfg, k)
        if isinstance(cur, bool):
            kw[k] = v.lower() in ("1", "true", "yes")
        elif isinstance(cur, int):
            kw[k] = int(v)
        elif isinstance(cur, float):
            kw[k] = float(v)
        elif cur is None and v.isdigit():
            kw[k] = int(v)
        else:
            kw[k] = v
    return dataclasses.replace(cfg, **kw)


def run_one(arch: str, shape_name: str, mesh_kind: str, topology: str, mixing: str,
            override: str = "", tag: str = "") -> dict:
    import jax

    from repro.configs import get_config
    from repro.configs.base import SHAPES
    from repro.core.dsgd import make_topology
    from repro.launch.mesh import gossip_axes_for, gossip_size, make_production_mesh
    from repro.launch.serve import ServeEngine
    from repro.launch.train import SPMDTrainer
    from repro.optim.sgd import sgd

    cfg = _apply_overrides(get_config(arch), override)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(mesh.shape), "kind": shape.kind,
        "mixing": mixing,
    }
    if override:
        rec["override"] = override
    if tag:
        rec["tag"] = tag
    t0 = time.time()

    if shape.kind == "train":
        gx = gossip_axes_for(cfg.name, mesh)
        g = gossip_size(mesh, gx)
        topo = make_topology(
            topology if g > 1 else "d_ring", max(g, 2) if g == 1 else g
        )
        if g == 1:
            topo = make_topology("d_ring", 1)
        trainer = SPMDTrainer(
            cfg, mesh, topo, sgd(momentum=0.9), mixing=mixing,
        )
        rec["gossip_axes"] = list(gx)
        rec["gossip_nodes"] = g
        rec["topology"] = topo.name
        graph = topo.graph_at(0)
        rec["graph"] = graph.describe() if graph else "none"
        lowered = trainer.lower_step(shape)
    else:
        engine = ServeEngine(cfg, mesh)
        if shape.kind == "prefill":
            lowered = engine.lower_prefill(shape)
        else:
            lowered = engine.lower_decode(shape)
            rec["window"] = engine.decode_window(shape)

    rec["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 2)

    mem = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
    }
    cost = compiled.cost_analysis()
    rec["cost"] = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
    }
    hlo_text = compiled.as_text()
    rec["collectives"] = collective_stats(hlo_text)
    # loop-aware accounting (cost_analysis counts while bodies once; scans
    # over layers/KV-chunks would otherwise undercount by the trip count)
    from repro.launch.hlo_analysis import analyze_hlo

    rec["hlo"] = analyze_hlo(hlo_text)
    return rec


def main() -> None:
    import jax

    from repro.configs import ARCH_NAMES

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--topology", default="d_ada")
    ap.add_argument("--mixing", default="ppermute", choices=["ppermute", "dense"])
    ap.add_argument("--override", default="", help="cfg field overrides k=v,k=v (perf hillclimbs)")
    ap.add_argument("--tag", default="", help="label stored in the record")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument(
        "--isolate",
        action="store_true",
        help="run every (arch, shape, mesh) combo in its own subprocess — "
        "XLA compile memory for ~80 large modules does not fit one process",
    )
    args = ap.parse_args()

    if args.isolate:
        import subprocess
        import sys

        from repro.configs import ARCH_NAMES as _AN
        from repro.configs.base import SHAPES as _SH

        archs = list(_AN) if args.arch == "all" else args.arch.split(",")
        shapes = list(_SH) if args.shape == "all" else args.shape.split(",")
        meshes = args.mesh.split(",")
        for arch in archs:
            for shape in shapes:
                for mesh_kind in meshes:
                    cmd = [
                        sys.executable, "-m", "repro.launch.dryrun",
                        "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                        "--topology", args.topology, "--mixing", args.mixing,
                        "--out", args.out, "--skip-existing",
                    ] + (["--override", args.override] if args.override else []) \
                      + (["--tag", args.tag] if args.tag else [])
                    r = subprocess.run(cmd)
                    if r.returncode not in (0, 1):
                        print(
                            f"[DIED] {arch} × {shape} × {mesh_kind}: "
                            f"rc={r.returncode} (likely OOM)",
                            flush=True,
                        )
        return

    from repro.configs.base import SHAPES

    archs = list(ARCH_NAMES) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")

    results = []
    if args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {
        (r["arch"], r["shape"], r["mesh"], r.get("tag", ""))
        for r in results
        if "error" not in r
    }

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                key = (arch, shape, mesh_kind, args.tag)
                if key in done:
                    continue
                tag = f"{arch} × {shape} × {mesh_kind}"
                try:
                    rec = run_one(
                        arch, shape, mesh_kind, args.topology, args.mixing,
                        args.override, args.tag,
                    )
                    coll = rec["collectives"].get("total_wire_bytes", 0)
                    print(
                        f"[OK]   {tag}: compile {rec['compile_s']}s  "
                        f"flops/dev {rec['cost']['flops']:.3e}  "
                        f"coll {coll/1e6:.1f} MB/dev",
                        flush=True,
                    )
                except Exception as e:
                    n_fail += 1
                    rec = {
                        "arch": arch, "shape": shape, "mesh": mesh_kind,
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-2000:],
                    }
                    print(f"[FAIL] {tag}: {type(e).__name__}: {str(e)[:200]}", flush=True)
                results = [
                    r for r in results
                    if (r["arch"], r["shape"], r["mesh"], r.get("tag", "")) != key
                ]
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
                # one process compiles up to 80 large modules: drop executables
                # and tracing caches between combos or host RAM accumulates.
                jax.clear_caches()
                import gc

                gc.collect()
    print(f"\n{len(results)} records, {n_fail} failures -> {args.out}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
