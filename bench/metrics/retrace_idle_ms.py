"""Device idle time per fit, in ms, inside the ``sweep`` spans that
enclose a ``step_trace``: the sweeps in which the host traced and lowered
the step before it could enqueue it. Layer: the solve driver."""

import harness


def read(run):
    si = harness.bench_module("span_idle")
    if not si.instrumented(run):
        return None
    retrace, _ = si.split_sweeps(run)
    return si.idle_ns(run, retrace) / 1e6 / len(run.fits)
