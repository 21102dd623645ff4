"""Traces of the solve's per-iteration step per fit: the ``step_trace``
spans of the window over the fits. The executor's step body enters that
span, and its Python runs only while JAX traces the step, so this counts
re-traces. Layer: the solve driver."""

import harness


def read(run):
    si = harness.bench_module("span_idle")
    if not run.fits or not si.spans(run, si.SWEEP):
        return None
    return len(si.spans(run, si.TRACE)) / len(run.fits)
