"""Device idle time per fit, in ms, inside the ``gram_setup`` spans: the
executor's setup (the Gram pass) and the Cholesky factor, while the host
enqueues them. Layer: the solve driver."""

import harness


def read(run):
    si = harness.bench_module("span_idle")
    if not si.instrumented(run) or not si.spans(run, si.SETUP):
        return None
    return si.idle_ns(run, si.spans(run, si.SETUP)) / 1e6 / len(run.fits)
