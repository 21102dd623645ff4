"""Device idle time per iteration, in ms, inside the ``x_solve`` spans
and the ``sweep`` spans that enclose no ``step_trace``: the host
enqueueing the x-update and the step, with nothing to trace. Layer: the
solve driver."""

import harness


def read(run):
    si = harness.bench_module("span_idle")
    if not si.instrumented(run) or not si.iterations(run):
        return None
    _, plain = si.split_sweeps(run)
    idle = si.idle_ns(run, si.spans(run, si.XSOLVE) + plain)
    return idle / 1e6 / si.iterations(run)
