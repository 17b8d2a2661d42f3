"""The shared run-telemetry recorder both engines emit into.

One :class:`MetricsRecorder` instance rides a whole run.  It is pure
host-side Python: no method traces, compiles, or dispatches device work,
so attaching one is provably free w.r.t. the zero-mid-run-recompile
invariant (``debug_no_retrace`` and ``assert_executables_preenumerated``
hold with telemetry enabled — asserted in ``tests/test_telemetry.py``).

Cost model, so callers know exactly what they pay:

  * no sinks, no deadline model (the default every engine constructs):
    every emitting method returns immediately — the engines' hot path
    gains a handful of attribute checks and nothing else;
  * a deadline fault model (``GossipDeadline``): per-``round`` span
    timing is on, which blocks on the loss once per step — exactly the
    synchronization the old per-engine ``_record_round`` already did;
  * sinks attached (``--telemetry``): counters/gauges/events cost a dict
    update + a JSONL line; span timing additionally requires
    ``record_spans=True`` (the CLI sets it) because the per-step block is
    a real synchronization benches must not silently inherit.

Same-step event coalescing (``coalesce_into``) lives here — ONE
implementation — and the consensus controller routes its transition /
rearm / redensify log through it, so the simulator and the SPMD trainer
produce identical event streams for identical runs.
"""
from __future__ import annotations

import time
from typing import Any, Optional

from repro.telemetry.schema import SCHEMA_VERSION, validate_record

__all__ = ["MetricsRecorder", "coalesce_into", "host_grad_norm"]


def coalesce_into(events: list, step: int, reason: str) -> Optional[str]:
    """Append ``(step, reason)`` to an event log, coalescing same-step
    entries: distinct reasons observed in one step merge into a single
    ``"a+b"`` entry, duplicates are dropped (re-arming is idempotent
    within a step).  Returns the entry's merged reason string, or None
    when the reason was already present.  This is the single coalescing
    implementation — ``ConsensusController._log_event`` delegates here,
    so both engines share its semantics by construction.
    """
    step = int(step)
    reason = str(reason)
    if events and events[-1][0] == step:
        prev = events[-1][1]
        if reason in prev.split("+"):
            return None
        merged = f"{prev}+{reason}"
        events[-1] = (step, merged)
        return merged
    events.append((step, reason))
    return reason


def host_grad_norm(grads) -> float:
    """Global L2 norm of a gradient pytree, computed on the host from
    already-materialized arrays (no device dispatch, no compile)."""
    import jax
    import numpy as np

    total = 0.0
    for leaf in jax.tree.leaves(grads):
        a = np.asarray(leaf, dtype=np.float64)
        total += float(np.vdot(a, a).real)
    return float(total ** 0.5)


class MetricsRecorder:
    """Typed per-run metrics: counters, gauges, spans, events, variance.

    Counters are monotone totals (``comm_bytes``, ``permutes``,
    ``program_applications``) billed at dispatch time; gauges are
    point-in-time scalars; ``round`` spans carry the deadline trace the
    engines used to keep privately (``round_ms`` / ``deadline_overruns``
    remain available as thin views); events record discrete occurrences;
    variance records stream the DBench Fig-5 signal.
    """

    def __init__(
        self,
        *,
        sinks=(),
        metrics_every: int = 0,
        record_spans: bool = False,
        deadline_ms: Optional[float] = None,
    ):
        self.sinks = list(sinks)
        self.metrics_every = max(int(metrics_every), 0)
        self.record_spans = bool(record_spans)
        self.deadline_ms = deadline_ms
        # this-process deadline trace (the engines' former private lists)
        self.round_ms: list = []
        self._overruns = 0
        # totals carried across a --resume (load_state_dict)
        self._rounds_prior = 0
        self._overruns_prior = 0
        self.totals: dict[str, float] = {}
        self.last_gauges: dict[str, Optional[float]] = {}
        self.last_variance: Optional[dict] = None
        self.event_count = 0

    # -- wiring ----------------------------------------------------------------
    def configure(self, *, deadline_ms: Optional[float] = None) -> None:
        """Engine-side late configuration (the deadline rides on the fault
        model, which the recorder's creator does not see)."""
        if deadline_ms is not None:
            self.deadline_ms = float(deadline_ms)

    @property
    def active(self) -> bool:
        """True when records fan out to sinks (telemetry requested)."""
        return bool(self.sinks)

    @property
    def timing(self) -> bool:
        """True when ``round`` spans are measured — which synchronizes the
        host on the loss once per step."""
        return self.deadline_ms is not None or (
            self.active and self.record_spans
        )

    @property
    def deadline_overruns(self) -> int:
        return self._overruns

    @property
    def rounds_total(self) -> int:
        return self._rounds_prior + len(self.round_ms)

    @property
    def overruns_total(self) -> int:
        return self._overruns_prior + self._overruns

    def _emit(self, rec: dict) -> None:
        if not self.sinks:
            return
        validate_record(rec)
        for s in self.sinks:
            s.emit(rec)

    def close(self) -> None:
        for s in self.sinks:
            close = getattr(s, "close", None)
            if close is not None:
                close()

    # -- manifest ----------------------------------------------------------------
    def manifest(self, run: dict) -> None:
        self._emit({"kind": "manifest", "schema": SCHEMA_VERSION, "run": run})

    # -- counters ----------------------------------------------------------------
    def counter(self, name: str, inc, *, step: int) -> None:
        total = self.totals.get(name, 0) + inc
        self.totals[name] = total
        self._emit({"kind": "counter", "step": int(step), "name": name,
                    "inc": inc, "total": total})

    def comm(self, program, param_bytes: int, *, step: int,
             alive=None, link_up=None) -> None:
        """Bill one program application at dispatch time: bytes on the wire
        (``program_comm_bytes`` — the same accounting ``benchmarks/ada.py``
        replays offline) and the PPermute dispatch count."""
        if program is None or not self.active:
            return
        from repro.core.schedule import PPermute, program_comm_bytes

        bytes_ = program_comm_bytes(
            program, int(param_bytes), alive=alive, link_up=link_up
        )
        step = int(step)
        self.counter("comm_bytes", int(bytes_), step=step)
        permutes = sum(1 for op in program.ops if isinstance(op, PPermute))
        if permutes:
            self.counter("permutes", permutes, step=step)
        self.counter("program_applications", 1, step=step)

    # -- gauges ----------------------------------------------------------------
    def gauge(self, name: str, value, *, step: int) -> None:
        value = None if value is None else float(value)
        self.last_gauges[name] = value
        self._emit({"kind": "gauge", "step": int(step), "name": name,
                    "value": value})

    # -- spans ----------------------------------------------------------------
    def round_start(self) -> Optional[float]:
        """Host timestamp opening a ``round`` span, or None when timing is
        off — the engines' former ``t_start = perf_counter() if ...``."""
        return time.perf_counter() if self.timing else None

    def round_end(self, t_start: Optional[float], *, step: int,
                  mix: bool = False) -> None:
        """Close a ``round`` span.  The caller has already blocked on the
        step's output so the duration covers the whole dispatched round.
        Deadline attribution is purely observational — the averaging
        masks stay seeded (determinism + engine equivalence)."""
        if t_start is None:
            return
        ms = (time.perf_counter() - t_start) * 1e3
        self.round_ms.append(ms)
        rec = {"kind": "span", "step": int(step), "name": "round",
               "ms": ms, "mix": bool(mix)}
        if self.deadline_ms is not None:
            overrun = ms > float(self.deadline_ms)
            if overrun:
                self._overruns += 1
            rec["deadline_ms"] = float(self.deadline_ms)
            rec["overrun"] = overrun
        self._emit(rec)

    # -- events ----------------------------------------------------------------
    def event(self, name: str, step: int, *, data: Optional[dict] = None) -> None:
        self.event_count += 1
        rec: dict = {"kind": "event", "step": int(step), "name": name}
        if data is not None:
            rec["data"] = data
        self._emit(rec)

    # -- streamed DBench variance ------------------------------------------------
    def due(self, step: int) -> bool:
        """True when ``step`` is a metrics emission step (``--metrics-every``
        cadence).  Engines gate the host transfer of loss/norms on this, so
        disabled telemetry never forces a synchronization."""
        return (
            self.active
            and self.metrics_every > 0
            and int(step) % self.metrics_every == 0
        )

    def step_metrics(self, step: int, *, loss=None, lr=None,
                     norms=None, grads=None) -> None:
        """Emit one metrics sample: loss/lr gauges, the streamed DBench
        ``variance_report`` over the per-node norm matrix the step already
        computed on device (``collect_norms`` folds ``param_l2_norms``
        into the existing grads/step executable — zero extra executables),
        and, when the bucketed path materializes grads on the host, the
        global gradient norm."""
        import numpy as np

        step = int(step)
        if loss is not None:
            self.gauge("loss", float(np.mean(np.asarray(loss))), step=step)
        if lr is not None:
            self.gauge("lr", float(lr), step=step)
        if grads is not None:
            self.gauge("grad_norm", host_grad_norm(grads), step=step)
        if norms is not None:
            a = np.asarray(norms)
            if a.ndim == 2 and a.shape[1] > 0:
                self.variance(step, a)

    def variance(self, step: int, norms) -> None:
        """The paper's Fig-5 signal as a live metric: ``variance_report``
        (gini, CV, index-of-dispersion, quartile coefficient) over the
        (n_nodes, n_leaves) pre-mixing parameter-norm matrix — numerically
        identical to the offline ``DBenchRecorder`` computation because it
        IS the same function on the same array."""
        import numpy as np

        from repro.core.dbench import variance_report

        report = variance_report(norms)
        metrics, per_layer = {}, {}
        for name, per_leaf in report.items():
            arr = np.asarray(per_leaf, dtype=np.float64)
            mean = float(np.mean(arr)) if arr.size else None
            metrics[name] = (
                mean if mean is not None and np.isfinite(mean) else None
            )
            per_layer[name] = [
                float(v) if np.isfinite(v) else None for v in arr
            ]
        self.last_variance = {"step": int(step), "metrics": metrics}
        self._emit({"kind": "variance", "step": int(step),
                    "metrics": metrics, "per_layer": per_layer})

    # -- resume ----------------------------------------------------------------
    def state_dict(self) -> dict:
        """JSON-serializable run totals for the checkpoint ``extra=``
        payload: a resumed run continues its counters and span/overrun
        totals instead of restarting them at zero."""
        return {
            "schema": SCHEMA_VERSION,
            "counters": dict(self.totals),
            "rounds": self.rounds_total,
            "overruns": self.overruns_total,
            "events": int(self.event_count),
        }

    def load_state_dict(self, d: dict) -> None:
        self.totals.update(d.get("counters") or {})
        self._rounds_prior = int(d.get("rounds", 0))
        self._overruns_prior = int(d.get("overruns", 0))
        self.event_count += int(d.get("events", 0))

    # -- bench provenance --------------------------------------------------------
    def provenance(self) -> dict:
        """The ``provenance`` stamp bench sections carry when derived from
        a recorder (``save_bench_section(..., telemetry=...)``); validated
        by ``repro.analysis.invariants.verify_bench_payload``."""
        return {
            "source": "telemetry",
            "schema": SCHEMA_VERSION,
            "counters": {k: float(v) for k, v in sorted(self.totals.items())},
            "rounds": self.rounds_total,
            "events": int(self.event_count),
        }
