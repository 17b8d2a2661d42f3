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

Every name the program emits is listed in ``SPANS`` and ``SCOPES``; the
README beside this file says which metric reads each one.  Nothing else in
the program calls ``jax.profiler`` or ``jax.named_scope`` directly.
"""
from __future__ import annotations

import jax

__all__ = ["SPANS", "SCOPES", "SPAN_PREFIX", "TRAIN_STEP",
           "span", "step_span", "scope"]

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
    "mlp",             # feed-forward (dense or mixture of experts)
    "head",            # final norm, logits, cross entropy
    "optimizer",       # the local optimizer update
    "gossip",          # a mixing program's apply
    "fused_update",    # the fused Pallas update + first gossip round
    "norms",           # DBench per-leaf parameter norms
    "probe",           # consensus distance
)


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
