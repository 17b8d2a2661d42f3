"""The RWKV-6 step names its time mix and WKV recurrence, counts its
chunked WKV blocks as it is traced, and ``model.wkv_ms`` reads the WKV's
device time from a trace joined to the step's scopes."""
from __future__ import annotations

import types

import pytest

import chipbench_util
from chipbench_util import BENCH, tiny_bench
from benchlib import scopes
from benchlib import trace as tr

CELL = "rwkv6-1chip-s4096-b1"
# the tiny sizes of the RWKV-6 family (``reference/rwkv6.py``) are those
# of the family it supersedes
chipbench_util.TINY_SIZES.setdefault("rwkv6", chipbench_util.TINY_SIZES["ssm"])

DEV = tr.DEVICE_PREFIX + "0"
KINDS = ("fusion", "dot", "convolution", "custom-call", "reduce", "while")
PHASES = ("forward", "backward", "recompute")


def _step_scopes(tmp_path, cell):
    from benchlib.files import Bench

    root = tiny_bench(tmp_path, cell)
    return scopes.step_scopes(root, Bench(root).workload("tiny"))


@pytest.fixture(scope="module")
def rwkv_scopes(tmp_path_factory):
    """The tiny RWKV-6 cell's step, compiled on the CPU from shapes alone,
    and what tracing it added to the trace-time counters."""
    from repro.telemetry import profile

    before = profile.traced()
    found = _step_scopes(tmp_path_factory.mktemp("wkv"), CELL)
    after = profile.traced()
    return found, {k: after[k] - before[k] for k in after}


def test_rwkv_step_carries_the_time_mix_and_wkv_scopes(rwkv_scopes):
    found, _ = rwkv_scopes
    seen = {}
    for op_name, sig in found.values():
        if sig is None or sig[0] not in KINDS:
            continue
        segs = scopes._segments(op_name)
        for scope in ("time_mix", "wkv", "mlp"):
            if scope in segs:
                seen.setdefault(scope, set()).add(scopes.phase_of(op_name))
        if "wkv" in segs:
            assert "time_mix" in segs[:segs.index("wkv")], op_name
    for scope in ("time_mix", "wkv", "mlp"):
        assert set(PHASES) <= seen.get(scope, set()), (scope, seen.get(scope))


def test_tracing_the_step_counts_its_chunked_wkv_blocks(rwkv_scopes):
    """One trace of the tiny cell's two-layer step bills two chunked WKV
    blocks and no attention block."""
    _, added = rwkv_scopes
    assert added["wkv.chunked"] == 2
    assert not any(v for k, v in added.items() if k.startswith("attention.path."))


def _trace_of(found, secs=1e-3):
    """One event of ``secs`` per instruction of the step, back to back: the
    trace's text of an instruction is its name, result shape and opcode."""
    ops, t = [], 0.0
    for name, (_, sig) in sorted(found.items()):
        if sig is None or sig[0] not in KINDS:
            continue
        opcode, shape = sig
        ops.append((t, t + secs, f"%{name} = {shape} {opcode}()"))
        t += secs
    return tr.Trace(ops={DEV: ops}, spans=[(0.0, t, "bench.window")], window=(0.0, t))


def _read(name, trace, found, steps=1):
    from benchlib.files import Bench

    ctx = types.SimpleNamespace(trace=trace, devices=[DEV], steps=steps,
                                op_scopes=found)
    return Bench(BENCH).metric(name).read(ctx)


def test_wkv_ms_reads_the_wkv_scope_of_every_phase(rwkv_scopes):
    found, _ = rwkv_scopes
    trace = _trace_of(found)
    wkv = [n for n, (op, sig) in found.items()
           if sig is not None and sig[0] in KINDS and "wkv" in scopes._segments(op)]
    assert wkv
    assert _read("model.wkv_ms", trace, found) == pytest.approx(len(wkv) * 1.0)
    assert _read("model.wkv_ms", trace, found, steps=2) == pytest.approx(len(wkv) / 2)
    assert _read("model.attention_ms", trace, found) == 0.0


def test_wkv_ms_reads_nothing_without_the_scope(tmp_path):
    """A step with no ``wkv`` scope (the dense cell, or a program that
    predates the scope) gives no reading."""
    found = _step_scopes(tmp_path, "granite-1chip-s4096")
    assert _read("model.wkv_ms", _trace_of(found), found) is None
