"""Device idle time split by the solve driver's spans (``span_idle`` and
the five readers built on it), on events made by hand whose split is
known to the nanosecond and on two fits recorded on a TPU v5e."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness

tr = harness.bench_module("trace_reduce")
si = harness.bench_module("span_idle")
work = harness.bench_module("work")

SPLIT = ("step_traces_per_fit", "retrace_idle_ms", "setup_idle_ms",
         "dispatch_idle_ms", "readback_idle_ms")


def _run(events, fits, devices=("0",), cfg=None):
    run = SimpleNamespace(fits=fits, events=events, devices=list(devices),
                          trace=tr, cfg=cfg, work=work,
                          peak=work.peaks("TPU v5 lite"),
                          rows_per_chip=cfg and cfg["rows"])
    run.window = tr.window(events)
    run.ops_by_device = lambda: [tr.clip_ops(events, d, *run.window)
                                 for d in run.devices]
    run.metric = lambda name: harness.bench_module(
        f"metrics.{name}").read(run)
    return run


def _host(*spans):
    return [[name, s, e - s, "python"] for name, s, e in spans]


# One fit of two iterations in [0, 20000). The device's gaps, and where
# each one falls:
#   [0, 300)        bench_fit [0, 100), gram_setup [100, 300)
#   [1000, 1250)    gram_setup [1000, 1100), x_solve [1100, 1250)
#   [1350, 6000)    the first sweep, which encloses the step's trace
#   [8000, 8500)    stop_rule
#   [8600, 9100)    stop_rule [8600, 9000), x_solve [9000, 9100)
#   [9300, 9400)    the second sweep
#   [14000, 20000)  stop_rule [14000, 15000), then outside the solve loop
HAND = {
    "host": _host((tr.WINDOW, 0, 20000), (tr.FIT, 0, 20000),
                  (si.SOLVE, 100, 19900), (si.SETUP, 100, 1100),
                  (si.XSOLVE, 1100, 1300), (si.SWEEP, 1300, 6300),
                  (si.TRACE, 1400, 2400), (si.STOP, 6300, 9000),
                  (si.XSOLVE, 9000, 9200), (si.SWEEP, 9200, 9500),
                  (si.STOP, 9500, 15000)),
    "devices": {"0": {"ops": [
        ["custom-call.1", "jit_gram", 300, 700],
        ["triangular-solve", "jit__solve_triangular", 1250, 100],
        ["custom-call.7", "jit_step", 6000, 2000],
        ["sqrt", "jit_sqrt", 8500, 100],
        ["triangular-solve", "jit__solve_triangular", 9100, 200],
        ["custom-call.7", "jit_step", 9400, 4600],
    ]}},
}


def test_overlap_of_interval_lists():
    assert si.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert si.overlap_ns([(0, 10)], [(10, 20)]) == 0
    assert si.overlap_ns([], [(0, 5)]) == 0


def test_gap_straddling_two_spans_is_split():
    run = _run(HAND, [{"iters": 2}])
    setup = si.spans(run, si.SETUP)
    xsolve = si.spans(run, si.XSOLVE)
    assert si.idle_ns(run, setup) == 200 + 100
    assert si.idle_ns(run, xsolve) == 150 + 100
    # the same gaps, charged to both spans at once, count once
    assert si.idle_ns(run, setup + xsolve) == 300 + 250


def test_nested_spans_count_once():
    run = _run(HAND, [{"iters": 2}])
    retrace, plain = si.split_sweeps(run)
    assert retrace == [(1300, 6300)] and plain == [(9200, 9500)]
    assert si.idle_ns(run, retrace + si.spans(run, si.TRACE)) == 4650
    solve = si.spans(run, si.SOLVE)
    # everything inside admm_solve: all gaps but [0, 100) and
    # [19900, 20000)
    assert si.idle_ns(run, solve + retrace + plain) == (
        200 + 250 + 4650 + 500 + 500 + 100 + 5900)


def test_split_readers_on_hand_events():
    run = _run(HAND, [{"iters": 2}])
    assert run.metric("step_traces_per_fit") == 1.0
    assert run.metric("retrace_idle_ms") == pytest.approx(4650 / 1e6)
    assert run.metric("setup_idle_ms") == pytest.approx(300 / 1e6)
    assert run.metric("dispatch_idle_ms") == pytest.approx(
        (150 + 100 + 100) / 1e6 / 2)
    assert run.metric("readback_idle_ms") == pytest.approx(
        (500 + 400 + 1000) / 1e6 / 2)


def test_idle_is_averaged_over_chips():
    two = {"host": HAND["host"],
           "devices": {**HAND["devices"], "1": {"ops": [
               ["custom-call.9", "jit_gram", 0, 20000]]}}}
    run = _run(two, [{"iters": 2}], devices=("0", "1"))
    assert run.metric("retrace_idle_ms") == pytest.approx(4650 / 2 / 1e6)


def test_without_the_solve_spans_readers_find_nothing():
    """A program that puts no spans on the profiler's timeline (the
    benchmark laid over an older checkout) leaves every reader None."""
    bare = {"host": _host((tr.WINDOW, 0, 20000), (tr.FIT, 0, 20000)),
            "devices": HAND["devices"]}
    run = _run(bare, [{"iters": 2}])
    for name in SPLIT:
        assert run.metric(name) is None, name


def test_a_step_traced_before_the_window_reads_zero():
    """Spans but no ``step_trace``: the step was traced before the window
    (a jitted step kept across fits), so no sweep is a re-trace."""
    kept = {"host": [h for h in HAND["host"] if h[0] != si.TRACE],
            "devices": HAND["devices"]}
    run = _run(kept, [{"iters": 2}])
    assert run.metric("step_traces_per_fit") == 0.0
    assert run.metric("retrace_idle_ms") == 0.0
    assert run.metric("dispatch_idle_ms") == pytest.approx(
        (150 + 100 + 4650 + 100) / 1e6 / 2)


# Two fits of star.logistic on a TPU v5e with the solve loop's spans, cut from
# a traced run of the benchmark (device ops keep their program and the
# head of their name; host events keep the benchmark's annotations, the
# solve loop's spans and the step's lowering).
RECORDED = tr.load(Path(__file__).parent / "data"
                   / "v5e_star_two_fits_spans.json.gz")
STAR = {"rows": 3_712_000, "features": 307, "dtype": "float32"}


def test_split_on_recorded_v5e_trace():
    run = _run(RECORDED, [{"iters": 30}, {"iters": 30}], cfg=STAR)
    assert run.metric("step_traces_per_fit") == 1.0
    retrace = run.metric("retrace_idle_ms")
    setup = run.metric("setup_idle_ms")
    per_iter = run.metric("dispatch_idle_ms") + run.metric(
        "readback_idle_ms")
    assert retrace == pytest.approx(344.463284)
    assert setup == pytest.approx(0.205585)
    assert run.metric("dispatch_idle_ms") == pytest.approx(0.5447465667)
    assert run.metric("readback_idle_ms") == pytest.approx(5.7462225833)
    # coverage: the four metrics account for the device's idle time
    # inside the fits, and without the re-trace for what remains
    fit_idle = si.idle_ns(run, si.spans(run, tr.FIT)) / 1e6 / 2
    covered = setup + retrace + 30 * per_iter
    assert covered >= 0.9 * fit_idle
    assert covered - retrace >= 0.75 * (fit_idle - retrace)
    # the spans leave the readers of the program's modules as they were
    assert run.metric("sweep_ms") == pytest.approx(8.2205253)
    assert run.metric("gram_ms") == pytest.approx(27.3406765)
    assert run.metric("xsolve_ms") == pytest.approx(0.0732722333)
