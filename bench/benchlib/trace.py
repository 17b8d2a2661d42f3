"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Device planes (``/device:TPU:<i>``) give each chip's operations from their
``XLA Ops`` line (a while loop's event encloses the events of its body),
and the in-flight time of asynchronous operations (copies, collectives)
from their ``Async XLA Ops`` line.  An event's name is the HLO instruction's
text; ``short`` keeps its name.  The host plane gives the harness's own spans, the
``TraceAnnotation``s named ``bench.*`` (``bench.window`` around the timed
window, and inside it ``bench.batch``, ``bench.dispatch``, ``bench.wait``).
Host and device events share one clock in the profiler's output.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    """Times in seconds on the trace's clock."""

    ops: dict          # device name -> [(start, end, op text)], sorted by start
    spans: list        # [(start, end, span name)] of the harness, sorted
    window: tuple      # (start, end) of ``bench.window``
    async_ops: dict = dataclasses.field(default_factory=dict)  # like ``ops``

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path) -> Trace:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(path))
    ops, async_ops, spans = {}, {}, []
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                into = {OPS_LINE: ops, ASYNC_LINE: async_ops}.get(line.name)
                if into is not None:
                    into[plane.name] = sorted(
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9, e.name))
    spans.sort()
    windows = [s for s in spans if s[2] == SPAN_PREFIX + "window"]
    if not windows:
        raise ValueError(f"{path}: no {SPAN_PREFIX}window span")
    return Trace(ops=ops, spans=spans, window=windows[0][:2], async_ops=async_ops)


def short(text: str) -> str:
    """The instruction name of an op's HLO text: ``fusion.487``."""
    return text.split(" = ", 1)[0].lstrip("%")


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) + tuple(rest)
            for s, e, *rest in intervals if e > lo and s < hi]


def union(intervals) -> list:
    """Merged, sorted (start, end) pairs covering the given intervals."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a: list, b: list) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def window_ops(trace: Trace, device: str) -> list:
    return clip(trace.ops.get(device, []), *trace.window)


def busy_s(trace: Trace, device: str) -> float:
    """Seconds of the window in which some operation ran on the device."""
    return length(union(window_ops(trace, device)))


def op_seconds(trace: Trace, device: str) -> dict:
    """Self seconds per operation name within the window: each event's
    duration less that of the events nested in it."""
    out: dict = {}
    stack: list = []   # open events: [end, name, self seconds]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, secs = stack.pop()
            out[name] = out.get(name, 0.0) + secs

    for s, e, text in sorted(window_ops(trace, device), key=lambda o: (o[0], -o[1])):
        close(s)
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, short(text), e - s])
    close(float("inf"))
    return out


def matching(trace: Trace, device: str, pattern: str) -> list:
    """The device's operations, synchronous and in flight, whose HLO text
    holds ``pattern``."""
    lo_hi = trace.window
    ops = window_ops(trace, device) + clip(trace.async_ops.get(device, []), *lo_hi)
    return sorted(op for op in ops if pattern in op[2])


def exposed_s(trace: Trace, device: str, pattern: str) -> float:
    """Seconds in which an operation whose text holds ``pattern`` runs or is
    in flight on the device and no other operation runs."""
    mine = union(matching(trace, device, pattern))
    others = union(op for op in window_ops(trace, device) if pattern not in op[2])
    return length(mine) - overlap(mine, others)


def idle_gaps(trace: Trace, device: str) -> list:
    """[(label, seconds)] of each idle stretch in the window, labelled by
    the innermost harness span open at its midpoint (``batch``,
    ``dispatch``, ``wait``), or ``other``."""
    lo, hi = trace.window
    busy = union(window_ops(trace, device))
    gaps, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    inner = [sp for sp in trace.spans if sp[2] != SPAN_PREFIX + "window"]
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        open_ = [sp for sp in inner if sp[0] <= mid <= sp[1]]
        label = (min(open_, key=lambda sp: sp[1] - sp[0])[2][len(SPAN_PREFIX):]
                 if open_ else "other")
        out.append((label, e - s))
    return out


def span_seconds(trace: Trace, name: str) -> list:
    """Durations of the harness's ``bench.<name>`` spans inside the window."""
    lo, hi = trace.window
    return [e - s for s, e, n in trace.spans
            if n == SPAN_PREFIX + name and s >= lo and e <= hi]


def chips_in_flight(trace: Trace, devices, pattern: str) -> list:
    """The devices whose trace records ``pattern`` operations in flight."""
    return [d for d in devices
            if any(pattern in op[2] for op in trace.async_ops.get(d, []))]
