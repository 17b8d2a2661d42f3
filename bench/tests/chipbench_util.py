"""Helpers shared by the benchmark's CPU tests: paths, and a copy of the
benchmark with a cell cut to a size the CPU runs in seconds."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
SRC = REPO / "src"
for p in (str(BENCH), str(SRC)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_SIZES = {
    "dense": {"hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
              "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512},
    "ssm": {"hidden_size": 256, "head_size": 64, "intermediate_size": 512,
            "num_hidden_layers": 2, "vocab_size": 512},
}


LIMITS_CELL = "granite-1chip-s4096"


def tiny_bench(tmp: Path, cell: str, *, name: str = "tiny", seq: int = 64,
               rows: int = 2, dtype: str = "float32", limits: str | None = "cell"
               ) -> Path:
    """A copy of the benchmark under ``tmp`` with one more cell, ``name``:
    the given cell's mesh and options on its configuration cut to
    TINY_SIZES, in ``dtype``, on ``rows`` rows of ``seq`` tokens per node.
    Only new files are added, as a later cell would be.  Its limits are the
    cell's own, or, for a workload file that has none yet, those of
    LIMITS_CELL, the cell whose limits were set from readings on the chip;
    ``limits=None`` leaves them out.

    In float32 the sound program agrees with the reference to round-off,
    so it passes those limits, which were set for bfloat16 at full size; a
    small bfloat16 model strays further than a full-size one."""
    root = tmp / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    w = json.loads((root / "workloads" / f"{cell}.json").read_text())
    cfg = json.loads((root / "configs" / f"{w['config']}.json").read_text())
    cfg.update(TINY_SIZES[cfg["family"]], dtype=dtype)
    (root / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (root / "traffic" / f"{name}.json").write_text(
        json.dumps({"seq": seq, "per_node_batch": rows, "structure": 0.85}))
    w.update(config=name, traffic=name)
    if limits is None:
        w.pop("limits", None)
    elif "limits" not in w:
        w["limits"] = json.loads(
            (root / "workloads" / f"{LIMITS_CELL}.json").read_text())["limits"]
    (root / "workloads" / f"{name}.json").write_text(json.dumps(w))
    return root


def run_args(workload: str = "tiny", seed: int = 2**33 + 5, seconds: float = 1.0,
             trace: int = 0):
    import run

    return run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
