"""Readings that set the limits of the correctness check, on the chip.

    python3 bench/control.py --workload <cell> --seeds 101,102,... \
        [--control-seeds 3] [--fault-seeds 3]

In one process, for each seed: the program's compared steps (as a
benchmark run makes them in its set-up) against the float32 reference.
For the first ``--control-seeds`` seeds also the control, the reference in
float8 (``benchlib/precision.py``) put in the program's place, and for the
first ``--fault-seeds`` the faults a training cell can have, planted in the
reference put in the program's place (``Reference(fault=...)``).  A state
left unchanged reads 1 on ``grad_gap`` by definition and is not run.

Each reading is one JSON line on standard output:
``{"seed", "side", "loss_gap", "grad_gap", "change_gap"}``.  The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

import run as bench_run  # noqa: E402
from benchlib.files import Bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    bench = Bench()
    cell = bench.workload(args.workload)
    sys.path.insert(0, str(BENCH_DIR.parent / "src"))
    if bench_run.find_chips(cell, require_tpu=True) is None:
        return 1
    bench_run.use_compile_cache()
    from benchlib.check import gaps
    from benchlib.refstep import Reference

    prog = bench_run.Program(bench, cell)
    faults = ["half_batch", "leaf_dropped"] + (["no_exchange"] if prog.n > 1 else [])

    def reference(**kw):
        return Reference(prog.ref_mod, prog.cfg, prog.traffic, cell,
                         devices=prog.devices, **kw)

    def emit(seed, side, ref, other):
        print(json.dumps({"seed": seed, "side": side, **gaps(other, ref)}), flush=True)

    for i, seed in enumerate(seeds):
        state, readings = prog.start(seed)
        del state
        gc.collect()
        ref = reference().run(seed)
        emit(seed, "program", ref, readings)
        if i < args.control_seeds:
            emit(seed, "control_fp8", ref, reference(precision="fp8").run(seed))
        if i < args.fault_seeds:
            for fault in faults:
                emit(seed, f"fault_{fault}", ref, reference(fault=fault).run(seed))
    return 0


if __name__ == "__main__":
    sys.exit(main())
