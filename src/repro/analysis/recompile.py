"""Pass 3 — the recompile sanitizer.

The zero-mid-run-recompile invariant is the engines' core perf contract:
time-varying topologies rotate through the pre-enumerated
``Topology.distinct_programs`` set, fault masks are runtime operands, so
after warm-up NO training step may trace or compile anything new.  Until
now every test asserted this by hand-counting ``_step_cache`` entries or
diffing executable counts against a fault-free run.  This module replaces
those with two reusable primitives:

``assert_no_retrace`` / ``watch_retrace``
    A context manager hooking jax's monitoring events
    (``jaxpr_trace_duration`` / ``backend_compile_duration`` — the
    counters ``jax.jit`` emits on every trace and XLA compile).  Each
    frame registers its own listener and unregisters it on exit.  Works
    for ANY jit — including the engines' internal executables that never
    appear under a program key.

``assert_executables_preenumerated``
    The executable-set half of the invariant: every program-keyed
    executable an engine compiled must belong to the statically
    enumerable set (``Topology.distinct_programs`` for the simulator,
    ``SPMDTrainer.precompile_programs`` for the SPMD engine).  Knows both
    engines' cache-key layouts (bare ``cache_key``, ``(key, "faulty")``,
    ``("__bucket__", key, ...)``, ``__``-prefixed internals).

The simulator exposes the same guard at runtime as
``DecentralizedSimulator(..., debug_no_retrace=True)``: once a step's
executable is warm, re-invoking it under a trace event raises.
"""
from __future__ import annotations

import contextlib
import dataclasses

from repro.analysis.report import RetraceError

__all__ = [
    "RetraceStats",
    "watch_retrace",
    "assert_no_retrace",
    "used_program_keys",
    "allowed_program_keys",
    "assert_executables_preenumerated",
]

try:  # pragma: no cover - exercised on every jax version in CI
    from jax._src.dispatch import BACKEND_COMPILE_EVENT, JAXPR_TRACE_EVENT
except ImportError:  # pragma: no cover - jax moved the constants
    JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
    BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class RetraceStats:
    """Counts observed while a ``watch_retrace`` frame was active."""

    label: str = ""
    traces: int = 0
    compiles: int = 0

    @property
    def clean(self) -> bool:
        return self.traces == 0 and self.compiles == 0


@contextlib.contextmanager
def watch_retrace(label: str = ""):
    """Count jit traces / XLA compiles inside the ``with`` body."""
    import jax.monitoring

    stats = RetraceStats(label)

    def listener(event, duration, **kwargs):
        if event == JAXPR_TRACE_EVENT:
            stats.traces += 1
        elif event == BACKEND_COMPILE_EVENT:
            stats.compiles += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield stats
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


@contextlib.contextmanager
def assert_no_retrace(label: str = "", *, allow_traces: int = 0,
                      allow_compiles: int | None = None):
    """Raise ``RetraceError`` if the body traced/compiled beyond allowance.

    Steady-state training sections must run at 0/0 (the default).  Warm-up
    phases that legitimately compile at first use (one executable per
    distinct program) should either run OUTSIDE the context or pass an
    explicit allowance.
    """
    cap_c = allow_traces if allow_compiles is None else allow_compiles
    with watch_retrace(label) as stats:
        yield stats
    if stats.traces > allow_traces or stats.compiles > cap_c:
        who = f" in {label!r}" if label else ""
        raise RetraceError(
            f"mid-run recompile{who}: {stats.traces} trace(s) / "
            f"{stats.compiles} compile(s) observed "
            f"(allowed {allow_traces}/{cap_c}) — a step executable was not "
            "pre-enumerated or a static argument changed between steps"
        )


def used_program_keys(step_cache) -> set:
    """Program cache keys behind an engine ``_step_cache``'s entries.

    Strips the engines' wrappers — ``(key, "faulty")`` fault signatures,
    and the simulator's ``("__bucket__", key, width, has_m, faulty)``
    bucket executables (the trainer does not bucket) — and drops
    ``__``-prefixed internal executables (grads, centralized/local
    closures) plus the SPMD trainer's ``None`` programless key.
    """
    used = set()
    for k in step_cache:
        if k is None:
            continue
        if isinstance(k, tuple) and len(k) == 2 and k[1] == "faulty":
            k = k[0]
            if k is None:
                continue
        if isinstance(k, tuple) and k and k[0] == "__bucket__":
            k = k[1]
        if isinstance(k, tuple) and k and isinstance(k[0], str) \
                and k[0].startswith("__"):
            continue
        used.add(k)
    return used


def allowed_program_keys(engine, n_epochs: int = 1) -> set:
    """The statically enumerable program-key set for either engine."""
    if hasattr(engine, "precompile_programs"):  # SPMDTrainer
        return {p.cache_key for p in engine.precompile_programs(n_epochs)}
    return {
        p.cache_key for _, p in engine.topology.distinct_programs(n_epochs)
    }


def assert_executables_preenumerated(engine, *, n_epochs: int = 1,
                                     require_used: bool = True) -> set:
    """Every program-keyed executable must come from the enumerable set.

    Returns the used program-key set for further assertions (e.g. exact
    counts).  ``require_used`` guards against the assertion passing
    vacuously because the run never reached a program-keyed step.
    """
    allowed = allowed_program_keys(engine, n_epochs)
    used = used_program_keys(engine._step_cache)
    if require_used and not used:
        raise RetraceError(
            "no program-keyed executables were compiled at all — the run "
            "never exercised a mixing step (vacuous invariant)"
        )
    stray = used - allowed
    if stray:
        raise RetraceError(
            f"{len(stray)} executable(s) beyond the pre-enumerated program "
            f"set: {sorted(map(str, stray))[:4]} — a program was built "
            "mid-run that Topology.distinct_programs cannot see"
        )
    return used
