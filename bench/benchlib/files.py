"""Find the benchmark's files by name.

Layout under the benchmark directory (``bench/``):

    configs/<config>.json      one model configuration each
    traffic/<traffic>.json     one data mix each (read by ``benchlib.data``)
    workloads/<cell>.json      one cell each: config, traffic, mesh, trainer
                               options and the limits of the correctness check
    reference/<family>.py      plain float32 reference of a model family
    flops/<family>.py          model FLOPs per token of a model family
    metrics/<metric>.py        one reader per per-layer metric
    peaks.json                 published chip peaks keyed by device kind

A new cell, configuration, traffic mix or metric is a new file; nothing
here changes.  ``BENCHMARK.json`` sits beside the benchmark directory.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


class BenchError(Exception):
    """A benchmark file is missing or does not fit what the harness needs."""


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"{path} not found")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file as a module under a name made from its path, so files
    named after metrics (``host.batch_ms.py``) load like any other."""
    if not path.is_file():
        raise BenchError(f"{path} not found")
    name = "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix("").parts)
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """The benchmark's files under ``root`` (default: this ``bench/``)."""

    def __init__(self, root: Path = BENCH_DIR):
        self.root = Path(root)

    def workload(self, name: str) -> dict:
        w = load_json(self.root / "workloads" / f"{name}.json")
        w["name"] = name
        return w

    def config(self, name: str) -> dict:
        c = load_json(self.root / "configs" / f"{name}.json")
        c["name"] = name
        return c

    def traffic(self, name: str) -> dict:
        t = load_json(self.root / "traffic" / f"{name}.json")
        t["name"] = name
        return t

    def reference(self, family: str):
        return load_module(self.root / "reference" / f"{family}.py")

    def flops(self, family: str):
        return load_module(self.root / "flops" / f"{family}.py")

    def metric(self, name: str):
        return load_module(self.root / "metrics" / f"{name}.py")

    def peaks(self, device_kind: str) -> dict:
        table = load_json(self.root / "peaks.json")["devices"]
        if device_kind not in table:
            raise BenchError(
                f"no peaks for device kind {device_kind!r} in peaks.json "
                f"(known: {sorted(table)})"
            )
        return table[device_kind]

    def benchmark(self) -> dict:
        return load_json(self.root.parent / "BENCHMARK.json")

    def per_layer_metrics(self, cell: str) -> list[dict]:
        """The ``per_layer`` entries of BENCHMARK.json that this cell reports."""
        return [
            m for m in self.benchmark().get("per_layer", [])
            if cell in m.get("workloads", [cell])
        ]
