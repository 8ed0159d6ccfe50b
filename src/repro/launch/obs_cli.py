"""Shared observability CLI knobs (DESIGN.md §17).

Every launch entry point (``train.py``, ``fedsim.py``, ``serve.py``)
exposes the same two flags through ``add_observability_args``::

    --trace-dir DIR      record one jax.profiler trace into DIR: the host
                         spans and the device's ops on one clock
    --metrics-jsonl F    stream schema-versioned metric events to F

``make_observability`` builds the (tracer, sink) pair from parsed args and
starts the trace; ``finish_observability`` stops the trace and
drains/closes the sink — call it in a ``finally``.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.fed.telemetry import make_tracer
from repro.obs import JSONLMetricsSink


def add_observability_args(ap):
    g = ap.add_argument_group("observability")
    g.add_argument("--trace-dir", default=None,
                   help="record a jax.profiler trace here: host spans and "
                        "device ops on one clock (a perfetto_trace.json.gz "
                        "for ui.perfetto.dev lands beside the .xplane.pb)")
    g.add_argument("--metrics-jsonl", default=None,
                   help="stream schema-versioned metric events (JSONL) "
                        "to this file")
    return ap


def make_observability(args, *, run: Optional[str] = None):
    """(tracer, sink) from parsed args — NULL_TRACER / None when the
    flags are off, so call sites pass them through unconditionally.
    With ``--trace-dir`` the profiler trace starts here."""
    trace_dir = getattr(args, "trace_dir", None)
    tracer = make_tracer(trace_dir)
    if trace_dir:
        # the engine's spans are the host events: the Python function
        # tracer would bury them under every interpreter call
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, create_perfetto_trace=True,
                                 profiler_options=opts)
    metrics = getattr(args, "metrics_jsonl", None)
    sink = JSONLMetricsSink(metrics, run=run) if metrics else None
    return tracer, sink


def finish_observability(sink, args) -> Optional[str]:
    """Stop the profiler trace (returns its directory), drain + close the
    sink.  Safe to call with observability off."""
    trace_dir = getattr(args, "trace_dir", None)
    if trace_dir:
        jax.profiler.stop_trace()
    if sink is not None:
        sink.close()
    return trace_dir
