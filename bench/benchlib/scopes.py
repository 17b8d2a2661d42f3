"""Put the device time of a traced window down to the training step's
phases and the model's parts, by the names the program gives its work.

The profiler's ``XLA Ops`` events name the HLO instruction only
(``fusion.487``), and the trace carries no HLO metadata (the harness turns
the HLO protos off).  The program names its work with ``jax.named_scope``
(``repro.telemetry.profile``), and the optimized HLO text of its step
executable keeps that name in each instruction's ``metadata={op_name=...}``.
So the reduction compiles the cell's step again, which loads it from the
persistent compile cache, reads ``{instruction: op_name}`` from its text,
and joins the window's events to it by name.  An event joins only where its
opcode and result shape are those of the instruction of that name; every
other event counts as ``unattributed``, and a reader reads nothing where
those exceed ``MAX_UNATTRIBUTED`` of the busy time, so a failed join gives
no number rather than a wrong one.

Phases, from the op_name alone (``phase_of``): JAX marks the forward pass
``jvp(...)``, the backward pass ``transpose(jvp(...))`` and what
``jax.remat`` computes again ``rematted_computation``; work of the model's
parts that depends on no parameter (the causal mask, RoPE tables) JAX
traces outside ``jvp`` and counts here as forward; the program's own
scopes name the rest.  Parts (``part_of``): the model's scopes ``attention``,
``mlp``, ``head`` and ``embed``, in every phase.
"""
from __future__ import annotations

import re
from collections import defaultdict, deque
from pathlib import Path

from benchlib import trace as tr

PARTS = ("attention", "mlp", "head", "embed")
STEP_SCOPES = ("optimizer", "gossip", "fused_update", "norms", "probe")
UNATTRIBUTED = "unattributed"
MAX_UNATTRIBUTED = 0.05

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_OPCODE = re.compile(r"^(.*?) ([a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_WRAP = re.compile(r"^[\w\-]+\((.*)\)$")
_REF = re.compile(r"%([\w.\-]+)")


def signature(rest: str):
    """(opcode, result shape without layouts) of an instruction's text after
    ``name = ``: the part the trace's event text and the HLO share."""
    m = _OPCODE.match(rest)
    if not m:
        return None
    shape = m.group(1)
    while True:
        bare = _LAYOUT.sub("", shape)
        if bare == shape:
            break
        shape = bare
    return m.group(2), shape


def op_scopes(hlo_text: str) -> dict:
    """``{instruction name: (op_name, signature)}`` of an optimized HLO
    module's text.  An instruction the compiler made without metadata (a
    split reduction, a broadcast of a constant) takes the op_name of its
    nearest user that has one, else of its nearest operand; "" where
    neither has."""
    ops, sigs, operands, users = {}, {}, {}, defaultdict(list)
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        ops[name] = op.group(1) if op else ""
        sigs[name] = signature(rest)
        operands[name] = [r for r in _REF.findall(rest.split(", metadata=")[0])
                          if r != name]
        for r in operands[name]:
            users[r].append(name)
    return {n: (ops[n] or _nearest(n, ops, users) or _nearest(n, ops, operands),
                sigs[n]) for n in ops}


def _nearest(name: str, ops: dict, graph: dict) -> str:
    """The op_name of the nearest instruction from ``name`` along ``graph``
    that has one (breadth first), or ""."""
    seen, queue = {name}, deque(graph.get(name, ()))
    while queue:
        n = queue.popleft()
        if n in seen or n not in ops:
            continue
        if ops[n]:
            return ops[n]
        seen.add(n)
        queue.extend(graph.get(n, ()))
    return ""


def _segments(op_name: str) -> list:
    """The scope names of an op_name, with JAX's transform wrappers taken
    off (``transpose(jvp(head))`` is ``head``); several op_names of one
    fused instruction are joined by ``;``."""
    out = []
    for path in op_name.split(";"):
        for seg in path.split("/"):
            while True:
                m = _WRAP.match(seg)
                if not m:
                    break
                seg = m.group(1)
            out.append(seg)
    return out


def phase_of(op_name: str) -> str:
    """The step's phase an instruction belongs to: ``forward``,
    ``backward``, ``recompute``, one of ``STEP_SCOPES``, or ``other``."""
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(jvp(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    segs = set(_segments(op_name))
    for scope in STEP_SCOPES:
        if scope in segs:
            return scope
    if segs & ({"model"} | set(PARTS)):
        return "forward"
    return "other"


def part_of(op_name: str):
    """The model part an instruction belongs to (one of ``PARTS``), or None."""
    for seg in _segments(op_name):
        if seg in PARTS:
            return seg
    return None


def split_seconds(trace: tr.Trace, device: str, scopes: dict) -> dict:
    """Self seconds of the device's window ops by phase (with
    ``unattributed``) and by part: ``{"phases": {...}, "parts": {...}}``."""
    texts = {tr.short(text): text for _, _, text in tr.window_ops(trace, device)}
    phases, parts = {}, {}
    for name, secs in tr.op_seconds(trace, device).items():
        found = scopes.get(name)
        rest = texts[name].split(" = ", 1)[-1]
        if found is None or found[1] != signature(rest):
            phases[UNATTRIBUTED] = phases.get(UNATTRIBUTED, 0.0) + secs
            continue
        phase, part = phase_of(found[0]), part_of(found[0])
        phases[phase] = phases.get(phase, 0.0) + secs
        if part:
            parts[part] = parts.get(part, 0.0) + secs
    return {"phases": phases, "parts": parts}


def step_scopes(root: Path, cell: dict):
    """``op_scopes`` of the step executable the cell's window runs, built as
    the harness builds the program and compiled again from shapes alone
    (``_LazyStep.hlo_text``); None for a program whose step cannot give its
    text, which names no phases."""
    import jax
    import jax.numpy as jnp

    import run
    from benchlib.files import Bench

    prog = run.Program(Bench(root), cell)
    fn = prog.trainer.step_fn(0)
    if not hasattr(fn, "hlo_text"):
        return None
    params, opt = prog.trainer.abstract_state
    rows = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        prog.rows(0, 0))
    return op_scopes(fn.hlo_text(params, opt, rows,
                                 jax.ShapeDtypeStruct((), jnp.float32)))


def split_ms(ctx, root: Path):
    """ms per step by phase and by part, averaged over the cell's chips, or
    None where the join fails.  The step's scopes are kept on ``ctx`` as
    ``op_scopes``, so that the readers of one run compile the step once."""
    chips = [d for d in ctx.devices if ctx.trace.ops.get(d)]
    if not chips or not ctx.steps:
        return None
    if not hasattr(ctx, "op_scopes"):
        ctx.op_scopes = step_scopes(root, ctx.cell)
    if ctx.op_scopes is None:
        return None
    total = {"phases": {}, "parts": {}}
    busy = 0.0
    for d in chips:
        busy += tr.busy_s(ctx.trace, d)
        for kind, secs in split_seconds(ctx.trace, d, ctx.op_scopes).items():
            for k, v in secs.items():
                total[kind][k] = total[kind].get(k, 0.0) + v
    if total["phases"].get(UNATTRIBUTED, 0.0) > MAX_UNATTRIBUTED * busy:
        return None
    per_step = 1e3 / (len(chips) * ctx.steps)
    return {kind: {k: v * per_step for k, v in secs.items()}
            for kind, secs in total.items()}


def phase_ms(ctx, root: Path, phase: str):
    split = split_ms(ctx, root)
    return None if split is None else split["phases"].get(phase, 0.0)


def part_ms(ctx, root: Path, part: str):
    split = split_ms(ctx, root)
    return None if split is None else split["parts"].get(part, 0.0)
