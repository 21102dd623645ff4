"""The exchange layer's readers (``allreduce_ms``, ``allreduces_per_iter``)
on events made by hand, and every reader of the four-chip cell on a slice
of a trace recorded on four TPU v5e chips."""
from pathlib import Path

import pytest

import harness
from test_trace import _run

tr = harness.bench_module("trace_reduce")

# Two chips, two iterations. The step program's collectives: an async
# all-reduce (start and done, 300 ns together) and a plain one (200 ns)
# on chip 0, slower on chip 1; the Gram's all-reduce is not the step's.
HAND = {
    "host": [[tr.WINDOW, 0, 10000, "python"]],
    "devices": {
        "0": {"ops": [
            ["all-reduce.1", "jit_gram_body", 100, 400],
            ["fusion.2", "jit_step_body", 1000, 1000],
            ["all-reduce-start.3", "jit_step_body", 2000, 100],
            ["all-reduce-done.3", "jit_step_body", 2500, 200],
            ["all-reduce.4", "jit_step_body", 3000, 200],
            ["fusion.2", "jit_step_body", 5000, 1000],
            ["all-reduce-start.3", "jit_step_body", 6000, 100],
            ["all-reduce-done.3", "jit_step_body", 6500, 200],
            ["all-reduce.4", "jit_step_body", 7000, 200],
        ]},
        "1": {"ops": [
            ["fusion.2", "jit_step_body", 1000, 1000],
            ["all-reduce-start.3", "jit_step_body", 2000, 900],
            ["all-reduce-done.3", "jit_step_body", 3000, 100],
            ["fusion.2", "jit_step_body", 5000, 1000],
            ["all-reduce-start.3", "jit_step_body", 6000, 900],
            ["all-reduce-done.3", "jit_step_body", 7000, 100],
        ]},
    },
}
CFG = {"rows": 2000, "features": 10, "dtype": "float32"}


def test_exchange_readers_on_hand_events():
    run = _run(HAND, [{"iters": 2}], CFG, devices=("0", "1"))
    # chip 1 is the slower: (900 + 100) ns an iteration
    assert run.metric("allreduce_ms") == pytest.approx(1000 / 1e6)
    # chip 0: one async pair and one plain all-reduce an iteration
    assert run.metric("allreduces_per_iter") == 2.0
    # the step program's whole time still belongs to the sweep layer
    assert run.metric("sweep_ms") == pytest.approx(2000 / 1e6)


def test_exchange_readers_find_nothing_on_one_chip_programs():
    one_chip = {"host": HAND["host"], "devices": {"0": {"ops": [
        ["custom-call.7", "jit_step", 1000, 2000],
        ["all-reduce.1", "jit_gram_body", 3000, 100]]}}}
    run = _run(one_chip, [{"iters": 2}], CFG)
    assert run.metric("allreduce_ms") is None
    assert run.metric("allreduces_per_iter") is None


# Two fits of star_x4.logistic on four TPU v5e chips, cut from a traced
# run of the benchmark as the one-chip slices are (device ops keep their
# program and the head of their name; host events keep the benchmark's
# annotations and the solve loop's spans).
RECORDED = tr.load(Path(__file__).parent / "data" / "v5e_x4_two_fits.json.gz")
X4 = {"rows": 14_848_000, "features": 307, "dtype": "float32"}


def test_readers_on_recorded_v5e_four_chip_trace():
    run = _run(RECORDED, [{"iters": 32}, {"iters": 32}], X4,
               devices=("0", "1", "2", "3"))
    # the step's seven psums went out as one all-reduce an iteration
    assert run.metric("allreduces_per_iter") == 1.0
    assert run.metric("allreduce_ms") == pytest.approx(0.005139359375)
    # the one-chip readers find the shard_map programs (jit_step_body,
    # jit_gram_body) and read what star.logistic's per-chip share does
    assert run.metric("sweep_ms") == pytest.approx(8.22596396875)
    assert run.metric("sweep_roofline_pct") == pytest.approx(68.762530128)
    assert run.metric("gram_ms") == pytest.approx(27.3556685)
    assert run.metric("gram_roofline_pct") == pytest.approx(20.347498122)
    assert run.metric("xsolve_ms") == pytest.approx(0.073279421875)
    assert run.metric("iters_per_fit") == 32.0
    assert run.metric("idle_pct") == pytest.approx(61.360944828)
    assert run.metric("step_traces_per_fit") == 0.0
    assert run.metric("retrace_idle_ms") == 0.0
    assert run.metric("setup_idle_ms") == pytest.approx(0.5877045)
    assert run.metric("dispatch_idle_ms") == pytest.approx(2.37038646875)
    assert run.metric("readback_idle_ms") == pytest.approx(7.4867382109375)
