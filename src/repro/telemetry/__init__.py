"""Unified telemetry: host-side tracing, in-graph step metrics, latency
histograms (docs/telemetry.md).

This package top level is STDLIB-ONLY (tracer + histogram) so the hot
integration points — the loader worker, the checkpoint writer, the
failure log — can import it without pulling jax.  The jax-adjacent
pieces stay behind their submodules and import lazily:

* :mod:`repro.telemetry.metrics` — the replicated in-graph metrics
  vector threaded through the pipelined train step;
* :mod:`repro.telemetry.summarize` — offline trace analysis, also the
  ``python -m repro.telemetry summarize`` CLI.
"""

from repro.telemetry.hist import LatencyHistogram
from repro.telemetry.tracer import (
    Tracer,
    configure,
    counter,
    export,
    get_tracer,
    instant,
    set_track,
    span,
)

__all__ = [
    "LatencyHistogram",
    "Tracer",
    "configure",
    "counter",
    "export",
    "get_tracer",
    "instant",
    "set_track",
    "span",
]
