"""Device idle time per iteration, in ms, inside the ``stop_rule`` spans:
the host reading the sweep's scalars back, one blocking read at a time,
for Boyd's stopping test. Layer: the solve driver."""

import harness


def read(run):
    si = harness.bench_module("span_idle")
    if not si.instrumented(run) or not si.iterations(run):
        return None
    return si.idle_ns(run, si.spans(run, si.STOP)) / 1e6 / si.iterations(run)
