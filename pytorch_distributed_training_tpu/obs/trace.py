"""Semantic phase names for xprof timelines, one vocabulary for the repo.

``jax.profiler`` traces show fusion names; chasing a pipeline bubble or an
exposed DCN transfer needs the *semantic* phase — which tier of the
gradient sync, which engine program, which tick.  This module owns the
canonical names (so the README table, the annotations, and any trace
tooling agree) and re-exports the compat-shimmed entry points:

- :func:`phase_span` — host-side span (``TraceAnnotation``, plus a
  recorded span when a ``SpanRecorder`` is passed): brackets dispatch +
  wait of host code.  Used around the serve engine's compiled calls and
  the trainer's input pull and host syncs.
- :func:`step_annotation` — ``StepTraceAnnotation``: xprof's step marker,
  giving the per-step row grouping in the trace viewer.
- :func:`scope` — trace-time ``named_scope``: ops traced under it carry
  the phase in HLO metadata, so *compiled* timelines (and HLO dumps) show
  the step's phases, grad-sync tiers and pipeline ticks by name.

The host-side two land in the capture's ``/host:CPU`` plane, on the clock
the device planes use, so an idle gap on the device can be laid against
what the host was doing.  All three do nothing outside an active capture;
what they cost inside one is measured on the chip (PERF.md section 6).

The span layer (obs/spans.py) is the *recorded* counterpart of the same
vocabulary: :func:`phase_span` brackets host-side phases with BOTH an
xprof annotation and a ``SpanRecorder`` span, so the exported timeline
(``tools/trace_export.py``) and a live xprof capture name the same work
the same way.  Only the HOST-side phases promote — trace-time
:func:`scope` names (the loss, the optimizer, grad-sync tiers, grad-accum
microbatches, pipeline ticks) live inside ONE compiled program, where a
host clock would record trace time once and bake it in; graftcheck's
``host-clock-in-trace`` rule makes that class a lint finding, and their
measured timelines stay xprof's job.  The host span for such a step
instead carries the anatomy as attributes (microbatch count, sync tiers,
pipeline ticks).
"""

from __future__ import annotations

from contextlib import contextmanager

from ..compat import named_scope, step_trace_annotation, trace_annotation

# The canonical annotation vocabulary (README "Observability" documents it;
# tests pin membership so renames are deliberate).
PHASES = (
    "train/step",            # one optimizer step (host span + step marker)
    "train/input_wait",      # the loop's batch pull: loader next, shard, H2D enqueue
    "train/host_sync",       # a loss fetch: the host waits for the device
    "train/loss",            # forward + loss of one microbatch (in the program)
    "train/optimizer",       # the update gate: optimizer + apply (in the program)
    "train/head",            # the head's product (a classifier's too) and the cross entropy
    "train/noise",           # block diffusion: the step's noise and its 2L input
    "block/norm",            # the residual stream's norms and adds (not a head's q/k norms)
    "block/mlp",             # a dense MLP: up / gate product, activation, down product
    "attn/proj",             # q/k/v/o projections, rotation, per-head norms (outside MLA)
    "attn/core",             # the attention product itself, flash or XLA, under any mask
    "attn/block_diffusion",  # attention under the block-diffusion mask
    "attn/mla",              # latent attention: projections, norms, rotation, output gate, W_o
    "moe/route",             # top-k router: scores, top-k, grouping by expert
    "moe/experts",           # the held experts: row gather, grouped products, combine
    "moe/shared",            # the shared experts' MLP, every token's
    "train/mtp",             # the multi-token-prediction module and its loss
    "ssm/conv",              # Mamba-2 mixer: the causal depthwise convolution and its SiLU
    "ssm/scan",              # Mamba-2 mixer: steps, decays, the chunked recurrence, the D skip
    "ssm/gate",              # Mamba-2 mixer: the output gate and the grouped RMS norm
    "ssm/proj",              # Mamba-2 mixer: the in- and the out-projection
    "gdn/proj",              # Gated DeltaNet: the qkvz, ba and out projections
    "gdn/conv",              # Gated DeltaNet: the causal depthwise convolution and its SiLU
    "gdn/delta",             # Gated DeltaNet: q/k L2 norms, beta, the decays, the chunked delta rule
    "gdn/gate",              # Gated DeltaNet: the gated RMS norm of the output
    "grad_accum/microbatch",  # fwd+bwd of one accumulation microbatch
    "grad_sync/rs_ici",      # tier 1: reduce-scatter over ICI
    "grad_sync/ar_dcn",      # tier 2: cross-slice all-reduce over DCN
    "grad_sync/ag_ici",      # tier 3: all-gather over ICI
    "grad_sync/stripe",      # multi-path lane rotation around the DCN hop
    "pipeline/tick",         # one pipeline schedule tick
    "serve/prefill",         # engine chunked-prefill program
    "serve/decode",          # engine decode program
    "serve/verify",          # engine speculative multi-token verify program
)


def step_annotation(step_num: int, name: str = "train"):
    """Per-step xprof marker (groups device activity under step rows)."""
    return step_trace_annotation(name, step_num=step_num)


def scope(name: str):
    """Trace-time scope: HLO metadata carries ``name`` for ops under it."""
    return named_scope(name)


@contextmanager
def phase_span(spans, name: str, *, corr=None, parent=None, **attrs):
    """One host-side phase, visible to BOTH timelines: an xprof
    annotation (live captures) and a recorded span on ``spans`` (a
    :class:`~.spans.SpanRecorder`, or None — then only the annotation).
    ``parent`` is the recorded span's parent where the lexical nesting of
    ``spans.span`` does not give it.  Use at dispatch boundaries only;
    inside compiled code it is a ``host-clock-in-trace`` lint finding."""
    if spans is None:
        with trace_annotation(name):
            yield None
        return
    with trace_annotation(name), \
            spans.span(name, corr=corr, parent=parent, **attrs) as s:
        yield s
