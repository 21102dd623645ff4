"""Observability layer (DESIGN.md §12): registry thread-safety,
histogram percentile accuracy, span nesting/attrs in the exported
Chrome trace, the solve loop's spans on the JAX profiler's timeline,
telemetry-JSONL parity with ADMMHistory on a lasso solve, and cluster
snapshot merging (registry + legacy ByteCounter)."""
import importlib.util
import json
import math
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.cluster.transport import ByteCounter
from repro.core.prox import StackedProx, make_l1, make_least_squares
from repro.core.unwrapped import UnwrappedADMM
from repro.data.synthetic import lasso_problem
from repro.exec import fit_on_executor, make_problem, synth_data
from repro.obs import (
    METRICS_FILE,
    TELEMETRY_FILE,
    TRACE_FILE,
    Histogram,
    MetricsRegistry,
    Observability,
    Tracer,
    load_trace,
    read_jsonl,
    span_hotspots,
    summarize_histogram,
)

jax.config.update("jax_platform_name", "cpu")


# -- registry ---------------------------------------------------------------

def test_registry_thread_safety():
    """Concurrent incs/observes from many threads lose no updates."""
    reg = MetricsRegistry()
    threads, per_thread = 8, 2000

    def worker(tid):
        for i in range(per_thread):
            reg.inc("ops", 1, kind="a" if i % 2 else "b")
            reg.observe("lat_s", 1e-3 * (1 + (i % 7)))

    ts = [threading.Thread(target=worker, args=(t,)) for t in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    total = sum(reg.labeled("ops", "kind").values())
    assert total == threads * per_thread
    snap = reg.histogram_snapshot("lat_s")
    assert snap["count"] == threads * per_thread


def test_histogram_percentiles_vs_numpy():
    """Log-bucket quantile estimates stay within one bucket width
    (factor 10^(1/32) ~ 7.5%) of numpy's exact percentiles."""
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-6.0, sigma=1.0, size=20000)  # ~ms latencies
    h = Histogram()
    for v in vals:
        h.observe(float(v))
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(vals, q))
        est = h.quantile(q)
        assert abs(est - exact) / exact < 0.08, (q, est, exact)
    assert abs(h.mean - vals.mean()) / vals.mean() < 1e-6
    assert h.min == vals.min() and h.max == vals.max()


def test_histogram_snapshot_roundtrip_and_summary():
    h = Histogram()
    for v in (0.001, 0.002, 0.004, 0.008):
        h.observe(v)
    snap = h.to_snapshot()
    # snapshot is plain JSON (string bucket keys) and round-trips
    h2 = Histogram.from_snapshot(json.loads(json.dumps(snap)))
    assert h2.count == 4 and h2.sum == h.sum
    s = summarize_histogram(snap, scale=1e3)   # seconds -> ms
    assert s["count"] == 4
    assert s["min"] == 1.0 and s["max"] == 8.0
    assert 1.0 <= s["p50"] <= 8.0


def test_registry_merge_with_extra_labels():
    """Coordinator folding two worker snapshots keeps per-worker series
    apart while counters/buckets add."""
    coord, w0, w1 = MetricsRegistry(), MetricsRegistry(), MetricsRegistry()
    for _ in range(3):
        w0.inc("worker.iters")
        w0.observe("worker.iter_s", 0.010)
    for _ in range(5):
        w1.inc("worker.iters")
        w1.observe("worker.iter_s", 0.020)
    coord.merge(w0.snapshot(), extra_labels={"worker": "0"})
    coord.merge(w1.snapshot(), extra_labels={"worker": "1"})
    # merging the same worker twice ADDS (heartbeat-then-bye is diffed by
    # callers; merge itself is additive)
    assert coord.counter_value("worker.iters", worker="0") == 3
    assert coord.counter_value("worker.iters", worker="1") == 5
    assert coord.labeled("worker.iters", "worker") == {"0": 3, "1": 5}
    h0 = coord.histogram_snapshot("worker.iter_s", worker="0")
    h1 = coord.histogram_snapshot("worker.iter_s", worker="1")
    assert h0["count"] == 3 and h1["count"] == 5
    assert abs(h0["sum"] - 0.030) < 1e-12


def test_bytecounter_legacy_snapshot_and_merge():
    """ByteCounter rides the registry but keeps its legacy dict shape
    (coordinator _telemetry and cluster_bench consume it)."""
    a, b = ByteCounter(), ByteCounter()
    a.add("tx", "contrib", 100)
    a.add("tx", "contrib", 50)
    a.add("rx", "x", 24)
    b.add("tx", "hello", 7)
    snap = a.snapshot()
    assert snap["sent_bytes"] == {"contrib": 150}
    assert snap["sent_msgs"] == {"contrib": 2}
    assert snap["received_bytes"] == {"x": 24}
    a.merge(b.snapshot())
    assert a.snapshot()["sent_bytes"] == {"contrib": 150, "hello": 7}
    assert a.total("tx") == 157


# -- tracer -----------------------------------------------------------------

def test_span_nesting_and_attrs(tmp_path):
    tr = Tracer(enabled=True, process_name="test-proc")
    with tr.span("outer", k=1):
        with tr.span("inner", block="b3", k=2):
            pass
    path = str(tmp_path / "trace.json")
    tr.export(path)
    events = load_trace(path)
    xs = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert set(xs) == {"outer", "inner"}
    assert xs["inner"]["args"] == {"block": "b3", "k": 2}
    assert xs["outer"]["args"] == {"k": 1}
    # nesting: inner starts no earlier and ends no later than outer
    # (ts is integer µs, so allow the 1 µs truncation)
    o, i = xs["outer"], xs["inner"]
    assert i["ts"] >= o["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1.0
    # metadata rows: process name + a thread name for the emitting tid
    metas = [e for e in events if e.get("ph") == "M"]
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "test-proc" for e in metas)
    assert any(e["name"] == "thread_name"
               and e["tid"] == o["tid"] for e in metas)
    hot = span_hotspots(events)
    assert hot[0]["name"] == "outer" and hot[0]["count"] == 1


def test_tracer_merges_worker_events():
    """Coordinator folds a worker's shipped event list under its own
    timeline with a process_name track for the worker pid."""
    coord = Tracer(enabled=True, process_name="coordinator")
    worker = Tracer(enabled=True)        # no process meta of its own
    with worker.span("block_step", block=0):
        pass
    shipped = worker.events()
    coord.add_events(shipped, process_name="worker-0", pid=4242)
    events = coord.events()
    assert any(e.get("ph") == "M" and e.get("name") == "process_name"
               and e.get("pid") == 4242
               and e["args"]["name"] == "worker-0" for e in events)
    assert any(e.get("ph") == "X" and e["name"] == "block_step"
               for e in events)


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False, process_name="off")
    with tr.span("x", a=1):
        pass
    tr.instant("i")
    assert tr.events() == []
    obs = Observability(dir=None, enabled=False)
    obs.inc("n")
    obs.observe("h", 1.0)
    obs.record(iter=1)
    with obs.span("s"):
        pass
    assert obs.registry.snapshot()["counters"] == []
    obs.finish()   # no dir: must not write anything / raise


# -- spans on the profiler's timeline ---------------------------------------

def _trace_reduce():
    """The benchmark's trace reader (bench/trace_reduce.py)."""
    path = Path(__file__).resolve().parents[1] / "bench" / "trace_reduce.py"
    spec = importlib.util.spec_from_file_location("_trace_reduce", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _profiled(log_dir, fn):
    """Run ``fn`` under the JAX profiler; return its result and the
    trace's plain events."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0         # annotations only, as the bench
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, _trace_reduce().extract(str(log_dir))


def test_enabled_span_is_also_a_profiler_event(tmp_path):
    """An enabled span records its JSON event and, under the same name,
    an event on the profiler's host plane."""
    tr = Tracer(enabled=True)

    def spans():
        with tr.span("outer", k=1):
            with tr.span("inner"):
                pass

    _, events = _profiled(tmp_path, spans)
    host = {e[0] for e in events["host"]}
    assert {"outer", "inner"} <= host
    assert {e["name"] for e in tr.events() if e.get("ph") == "X"} == {
        "outer", "inner"}


@pytest.mark.parametrize("executor", ["local", "shard_map"])
def test_solve_spans_on_profiler_timeline(tmp_path, executor):
    """A fresh logistic solve, with observability off, leaves the
    solve loop's spans on the profiler's host plane: one solve and one setup,
    an x-update, a sweep and a stopping test per iteration inside the
    solve, and one trace of the executor's step, inside the first sweep.
    The disabled Tracer records nothing."""
    prob = make_problem("logistic")
    D, aux = synth_data(prob, m=2000, n=16, seed=3)
    opts = ({"mesh": Mesh(np.array(jax.devices()[:1]), ("data",))}
            if executor == "shard_map" else {})
    obs = Observability(dir=None, enabled=False)

    def solve():
        res = fit_on_executor(prob, executor, D, aux, obs=obs, **opts)
        return jax.block_until_ready(res)

    res, events = _profiled(tmp_path, solve)
    assert obs.tracer.events() == []
    host = lambda name: _trace_reduce().host_spans(events, name)
    (solve_span,) = host("admm_solve")
    assert len(host("gram_setup")) == 1
    iters = int(res.iters)
    assert 1 < iters < 300
    inside = lambda s, e: solve_span[0] <= s and e <= solve_span[1]
    for name in ("gram_setup", "x_solve", "sweep", "stop_rule"):
        got = host(name)
        assert len(got) == (1 if name == "gram_setup" else iters), name
        assert all(inside(s, e) for s, e in got), name
    (step_trace,) = host("step_trace")
    first_sweep = host("sweep")[0]
    assert first_sweep[0] <= step_trace[0]
    assert step_trace[1] <= first_sweep[1]


# -- telemetry parity with ADMMHistory --------------------------------------

def test_telemetry_matches_admm_history_lasso(tmp_path):
    """A small lasso solve with --obs-dir-style telemetry: the JSONL
    stream reproduces ADMMHistory (objective / primal / dual residuals)
    to float tolerance."""
    prob = lasso_problem(jax.random.PRNGKey(5), N=2, m_per_node=100, n=16)
    Dflat = prob.D.reshape(-1, 16)
    bflat = prob.b.reshape(-1)
    mu = float(prob.mu)
    m = Dflat.shape[0]
    D_hat = jnp.concatenate([jnp.eye(16), Dflat], axis=0)[None]
    sp = StackedProx(blocks=(make_l1(mu), make_least_squares()),
                     sizes=(16, m))
    aux = jnp.concatenate([jnp.zeros(16), bflat])[None]
    solver = UnwrappedADMM(loss=sp.as_loss(), tau=0.01 * m)

    rundir = str(tmp_path / "obs")
    obs = Observability(dir=rundir, process_name="test")
    res = solver.run(D_hat, aux, iters=25, obs=obs)
    obs.finish()

    recs = [r for r in read_jsonl(str(tmp_path / "obs" / TELEMETRY_FILE))
            if "iter" in r]
    hist = res.history
    assert len(recs) == 25
    np.testing.assert_allclose(
        [r["objective"] for r in recs], np.asarray(hist.objective),
        rtol=1e-6)
    np.testing.assert_allclose(
        [r["primal_res"] for r in recs], np.asarray(hist.primal_res),
        rtol=1e-6)
    np.testing.assert_allclose(
        [r["dual_res"] for r in recs], np.asarray(hist.dual_res),
        rtol=1e-6)
    assert all(r["tau"] == solver.tau for r in recs)

    # the run directory holds all three artifacts and the metrics
    # snapshot counted the run
    with open(tmp_path / "obs" / METRICS_FILE) as f:
        snap = json.load(f)
    assert any(e["name"] == "admm.runs" and e["value"] == 1
               for e in snap["counters"])
    trace = load_trace(str(tmp_path / "obs" / TRACE_FILE))
    assert any(e.get("ph") == "X" and e["name"] == "admm_run"
               for e in trace)


def test_obs_disabled_run_identical(tmp_path):
    """obs=None and an enabled obs produce bit-identical solver output
    (instrumentation reads, never perturbs)."""
    prob = lasso_problem(jax.random.PRNGKey(6), N=1, m_per_node=80, n=16)
    Dflat = prob.D.reshape(-1, 16)
    m = Dflat.shape[0]
    D_hat = jnp.concatenate([jnp.eye(16), Dflat], axis=0)[None]
    sp = StackedProx(blocks=(make_l1(float(prob.mu)), make_least_squares()),
                     sizes=(16, m))
    aux = jnp.concatenate([jnp.zeros(16), prob.b.reshape(-1)])[None]
    solver = UnwrappedADMM(loss=sp.as_loss(), tau=0.01 * m)
    r0 = solver.run(D_hat, aux, iters=10)
    obs = Observability(dir=str(tmp_path / "o"), process_name="t")
    r1 = solver.run(D_hat, aux, iters=10, obs=obs)
    obs.finish()
    np.testing.assert_array_equal(np.asarray(r0.x), np.asarray(r1.x))
