"""The names the program gives its work in a profiler trace.

Two kinds, both seen by ``jax.profiler`` on one clock:

* host spans (``span``, ``step_span``): ``jax.profiler.TraceAnnotation``s
  named ``repro.<name>``.  With no trace running each costs well under a
  microsecond.  ``repro.train_step`` is a step span whose ``step_num`` is
  the training step; the spans opened inside it are its phases.
* device scopes (``scope``): ``jax.named_scope``s.  They act while a step
  is traced and add metadata only (no operation): each instruction of the
  optimized HLO carries its scope path in ``metadata={op_name=...}``, and
  JAX adds its own markers there, ``jvp(...)`` on the forward pass,
  ``transpose(jvp(...))`` on the backward pass and ``rematted_computation``
  on what ``jax.remat`` computes again.  The profiler's events name the
  instruction only, so a reduction joins them to the executable's text
  (``SPMDTrainer.step_hlo_texts``).

A third kind is counted on the host as the program is traced, not run
(``count``, ``traced``): how many calls of each kind one trace of a step
puts in its executable, such as the attention path each block takes or
the RWKV-6 blocks whose WKV runs the chunked scan.  The
trainer bills what a step's trace added to its ``MetricsRecorder``.

Every name the program emits is listed in ``SPANS``, ``SCOPES`` and
``COUNTERS``; the README beside this file says which metric reads each one.
Nothing else in the program calls ``jax.profiler`` or ``jax.named_scope``
directly.
"""
from __future__ import annotations

import collections

import jax

__all__ = ["SPANS", "SCOPES", "COUNTERS", "SPAN_PREFIX", "TRAIN_STEP",
           "span", "step_span", "scope", "count", "traced"]

SPAN_PREFIX = "repro."
TRAIN_STEP = SPAN_PREFIX + "train_step"

# host spans, without the prefix; ``train_step`` is the step span
SPANS = (
    "train_step",
    "step.faults",     # fault realization, rejoin, depart, membership
    "step.probe",      # consensus probe and controller (syncs on Xi)
    "step.compile",    # a call that builds or first runs an executable
    "step.dispatch",   # the call of an executable already built
    "data.rows",       # SyntheticLM.stacked: one step's rows on the host
)

# device scopes
SCOPES = (
    "model",           # loss and gradient: JAX marks forward, backward, recompute
    "embed",           # token embedding
    "attention",       # QKV, RoPE, scores, mask, softmax, weighted sum, output
    "time_mix",        # RWKV-6 time mix: lerps, projections, decay, WKV, norm, output
    "wkv",             # the chunked WKV recurrence, inside time_mix
    "mlp",             # feed-forward (dense, mixture of experts, RWKV-6 channel mix)
    "head",            # final norm, logits, cross entropy
    "optimizer",       # the local optimizer update
    "gossip",          # a mixing program's apply
    "fused_update",    # the fused Pallas update + first gossip round
    "norms",           # DBench per-leaf parameter norms
    "probe",           # consensus distance
)

# trace-time counters: attention blocks by the path they resolved to, and
# RWKV-6 blocks whose WKV runs the chunked scan
COUNTERS = tuple(
    "attention.path." + p
    for p in ("splash", "reference", "chunked", "chunked_skip")
) + ("wkv.chunked",)

_traced: collections.Counter = collections.Counter()


def span(name: str) -> jax.profiler.TraceAnnotation:
    """Host span ``repro.<name>`` (a name from ``SPANS``)."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; add it to SPANS")
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def step_span(step: int) -> jax.profiler.StepTraceAnnotation:
    """The span around one training step; its child spans share ``step``."""
    return jax.profiler.StepTraceAnnotation(TRAIN_STEP, step_num=int(step))


def scope(name: str):
    """Device scope ``name`` (a name from ``SCOPES``) for the ops traced
    inside it."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; add it to SCOPES")
    return jax.named_scope(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` calls to the trace-time counter ``name`` (from ``COUNTERS``)."""
    if name not in COUNTERS:
        raise ValueError(f"unknown counter {name!r}; add it to COUNTERS")
    _traced[name] += n


def traced() -> dict:
    """Each trace-time counter's total over this process's traces."""
    return {name: _traced[name] for name in COUNTERS}
