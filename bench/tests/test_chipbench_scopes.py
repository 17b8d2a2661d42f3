"""The device time of a window put down to the step's phases and the
model's parts, by joining the trace's events to the step's HLO scopes."""
from __future__ import annotations

import types
from pathlib import Path

import pytest

from chipbench_util import BENCH, tiny_bench
from benchlib import scopes
from benchlib import trace as tr

DATA = Path(__file__).resolve().parent / "data"
DEV = tr.DEVICE_PREFIX + "0"
KINDS = ("fusion", "dot", "convolution", "custom-call", "reduce", "while")


@pytest.fixture(scope="module")
def tiny_scopes(tmp_path_factory):
    """The tiny cell's step (float32, remat as the configuration has it),
    compiled on the CPU from shapes alone, as the readers compile it."""
    from benchlib.files import Bench

    root = tiny_bench(tmp_path_factory.mktemp("scopes"), "granite-1chip-s4096")
    return scopes.step_scopes(root, Bench(root).workload("tiny"))


def test_every_instruction_of_the_step_has_a_phase(tiny_scopes):
    phases, parts = set(), set()
    for name, (op_name, sig) in tiny_scopes.items():
        if sig is None or sig[0] not in KINDS:
            continue
        phase = scopes.phase_of(op_name)
        assert phase != "other", (name, op_name)
        phases.add(phase)
        parts.add(scopes.part_of(op_name))
    assert {"forward", "backward", "recompute", "optimizer", "norms"} <= phases
    assert {"attention", "mlp", "head"} <= parts


@pytest.mark.parametrize("op_name, phase, part", [
    ("jit(f)/model/jvp()/while/body/closed_call/attention/dot_general",
     "forward", "attention"),
    ("jit(f)/model/transpose(jvp())/while/body/closed_call/checkpoint/mlp/"
     "dot_general", "backward", "mlp"),
    ("jit(f)/model/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/exp", "recompute", "attention"),
    ("jit(f)/model/transpose(jvp(head))/mul;jit(f)/model/transpose(jvp(head))/"
     "broadcast_in_dim", "backward", "head"),
    ("jit(f)/attention/le", "forward", "attention"),
    ("jit(f)/optimizer/sub", "optimizer", None),
    ("jit(f)/gossip/collective_permute", "gossip", None),
    ("jit(f)/jit(_leaf_update)/fused_update/gossip_leaf_update/pallas_call",
     "fused_update", None),
    ("jit(f)/norms/reduce_sum", "norms", None),
    ("jit(f)/probe/sqrt", "probe", None),
    ("jit(f)/optimizers/sub", "other", None),
    ("", "other", None),
])
def test_phase_and_part_of_an_op_name(op_name, phase, part):
    assert scopes.phase_of(op_name) == phase
    assert scopes.part_of(op_name) == part


HLO = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %exponential.1 = f32[8]{0} exponential(f32[8]{0} %param_0)
}

ENTRY %main.9 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/model/jvp()/while/body/closed_call/attention/exp" source_file="t.py" source_line=1}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %fusion.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/model/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attention/exp"}
  %dot.3 = f32[8]{0} dot(f32[8]{0} %fusion.2, f32[8]{0} %p.1), metadata={op_name="jit(step)/model/transpose(jvp())/while/body/closed_call/checkpoint/mlp/dot_general"}
  %copy.4 = f32[8]{0} copy(f32[8]{0} %dot.3)
  ROOT %fusion.5 = f32[8]{0} fusion(f32[8]{0} %copy.4), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/optimizer/sub"}
}
"""


def _event(line_name: str) -> str:
    """The trace's text of an HLO instruction: without its metadata."""
    line = next(x for x in HLO.splitlines() if f"%{line_name} = " in x)
    return line.strip().removeprefix("ROOT ").split(", metadata=")[0]


def _ctx(renamed: dict | None = None):
    # window 0..10 s, two steps.  forward 1 s, recompute 2 s, backward 2.5 s,
    # optimizer 1.5 s (with a copy that has no metadata, named by its user):
    # busy 7 s.  ``renamed`` swaps an event's text for another's.
    renamed = renamed or {}
    spans = [(0.0, 1.0, "fusion.1"), (1.0, 3.0, "fusion.2"), (3.0, 5.5, "dot.3"),
             (5.5, 6.0, "copy.4"), (6.0, 7.0, "fusion.5")]
    ops = {DEV: [(s, e, renamed.get(n, _event(n))) for s, e, n in spans]}
    trace = tr.Trace(ops=ops, spans=[(0.0, 10.0, "bench.window")], window=(0.0, 10.0))
    return types.SimpleNamespace(trace=trace, devices=[DEV], steps=2,
                                 op_scopes=scopes.op_scopes(HLO))


def _read(ctx, name):
    from benchlib.files import Bench

    return Bench(BENCH).metric(name).read(ctx)


def test_device_scope_readers_on_a_trace():
    ctx = _ctx()
    assert _read(ctx, "step.forward_ms") == pytest.approx(500.0)
    assert _read(ctx, "step.recompute_ms") == pytest.approx(1000.0)
    assert _read(ctx, "step.backward_ms") == pytest.approx(1250.0)
    assert _read(ctx, "step.optimizer_ms") == pytest.approx(750.0)
    assert _read(ctx, "model.attention_ms") == pytest.approx(1500.0)
    split = scopes.split_ms(ctx, BENCH)
    assert sum(split["phases"].values()) == pytest.approx(
        1e3 * tr.busy_s(ctx.trace, DEV) / ctx.steps)


@pytest.mark.parametrize("renamed", [
    {"dot.3": "%dot.99 = f32[8]{0} dot(f32[8]{0} %a, f32[8]{0} %b)"},
    {"dot.3": "%dot.3 = f32[16]{0} dot(f32[16]{0} %a, f32[16]{0} %b)"},
    {"fusion.5": "%fusion.5 = f32[8]{0} copy(f32[8]{0} %a)"},
], ids=["name_not_in_the_step", "other_shape", "other_opcode"])
def test_a_failed_join_reads_nothing(renamed):
    ctx = _ctx(renamed)
    for name in ("step.forward_ms", "step.backward_ms", "step.recompute_ms",
                 "step.optimizer_ms", "model.attention_ms"):
        assert _read(ctx, name) is None


@pytest.fixture(scope="module")
def chip_trace():
    """Two steps of the CLI's tiny granite run (``--reduced``, one chip),
    traced on a TPU v5e with ``--profile-dir``: the trace and the step's
    HLO text it wrote, each compressed with xz.  The window runs from the
    first ``repro.train_step`` to the last device op."""
    import lzma

    from jax.profiler import ProfileData

    data = DATA / "small_1chip_scopes"
    prof = ProfileData.from_serialized_xspace(
        lzma.decompress((data.parent / (data.name + ".xplane.pb.xz")).read_bytes()))
    ops, spans = {}, []
    for plane in prof.planes:
        for line in plane.lines:
            events = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                      for e in line.events]
            if plane.name.startswith(tr.DEVICE_PREFIX) and line.name == tr.OPS_LINE:
                ops[plane.name] = sorted(events)
            elif plane.name.startswith("/host:"):
                spans += [e for e in events if e[2].startswith("repro.")]
    spans.sort()
    start = min(s for s, _, n in spans if n == "repro.train_step")
    end = max(e for _, e, _ in ops[DEV])
    trace = tr.Trace(ops=ops, spans=spans, window=(start, end))
    hlo = lzma.decompress((data.parent / (data.name + ".hlo.txt.xz")).read_bytes())
    return trace, scopes.op_scopes(hlo.decode())


def test_chip_trace_holds_the_program_spans(chip_trace):
    trace, _ = chip_trace
    names = [n for _, _, n in trace.spans]
    assert names.count("repro.train_step") == names.count("repro.step.dispatch") == 2
    assert "repro.data.rows" in names


def test_chip_trace_joins_to_the_step_scopes(chip_trace):
    trace, op_scopes = chip_trace
    split = scopes.split_seconds(trace, DEV, op_scopes)
    busy = tr.busy_s(trace, DEV)
    assert split["phases"].get(scopes.UNATTRIBUTED, 0.0) <= scopes.MAX_UNATTRIBUTED * busy
    assert sum(split["phases"].values()) == pytest.approx(busy, rel=1e-6)
    for phase in ("forward", "backward", "recompute", "optimizer", "norms"):
        assert split["phases"].get(phase, 0.0) > 0, phase
    for part in ("attention", "mlp", "head", "embed"):
        assert split["parts"].get(part, 0.0) > 0, part
