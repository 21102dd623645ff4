"""The executors' jitted steps are built once per engine config: a
second solve on an equal engine (the same loss object, tau, backend,
block size and residency) neither traces nor lowers the step again,
while an engine that differs gets a step of its own."""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core.prox import make_logistic
from repro.core.unwrapped import UnwrappedADMM
from repro.engine import IterationEngine
from repro.exec import make_problem, solve_with_executor, synth_data
from repro.exec import local as local_mod
from repro.exec import shard_map as shard_map_mod
from repro.exec.shard_map import ShardMapExecutor

STEP_CACHE_SIZE = 64


def _count_step_traces(monkeypatch, module):
    """Count the ``step_trace`` annotations ``module``'s step bodies
    enter: the body runs, and so enters one, only while jit traces."""
    count = [0]
    real = module.annotate

    def counting(name):
        count[0] += name == "step_trace"
        return real(name)

    monkeypatch.setattr(module, "annotate", counting)
    return count


@pytest.fixture(scope="module")
def data():
    D, aux = synth_data(make_problem("logistic"), m=400, n=8, seed=5)
    return D[None], aux[None]


def _solve(loss, tau, data):
    D, aux = data
    return UnwrappedADMM(loss=loss, tau=tau).solve(D, aux, max_iters=40)


@pytest.mark.parametrize("second, traces", [
    ("same_loss", 1),          # the benchmark job's pattern
    ("other_tau", 2),
    ("other_loss_object", 2),  # a separately built make_logistic()
])
def test_local_solves_share_the_step(monkeypatch, data, second, traces):
    """Two solves by new UnwrappedADMM instances: the step is traced once
    when their engines are equal, and again when tau or the loss object
    differ (no false sharing). A shared step gives x bit for bit."""
    count = _count_step_traces(monkeypatch, local_mod)
    loss = make_logistic()
    first = _solve(loss, 0.1, data)
    res = {"same_loss": lambda: _solve(loss, 0.1, data),
           "other_tau": lambda: _solve(loss, 0.2, data),
           "other_loss_object": lambda: _solve(make_logistic(), 0.1, data),
           }[second]()
    assert count[0] == traces
    if second != "other_tau":
        np.testing.assert_array_equal(np.asarray(res.x), np.asarray(first.x))


@pytest.mark.parametrize("builder", ["local", "shard_map"])
def test_step_cache_is_bounded(builder):
    """The step caches are LRUs of a stated size: one engine more than
    the bound evicts the least recently used, so a process that builds a
    fresh loss per request keeps at most that many steps alive."""
    cached = {"local": local_mod._fused_step,
              "shard_map": shard_map_mod._shard_fns}[builder]
    args = (() if builder == "local" else
            (("data",), Mesh(np.array(jax.devices()[:1]), ("data",)),
             True, False))
    build = lambda e: cached(e, *args)
    assert cached.cache_info().maxsize == STEP_CACHE_SIZE
    loss = make_logistic()
    engines = [IterationEngine(loss=loss, tau=1.0 + i)
               for i in range(STEP_CACHE_SIZE + 1)]
    first = build(engines[0])
    assert build(IterationEngine(loss=loss, tau=1.0)) is first
    for e in engines[1:]:
        build(e)
    assert cached.cache_info().currsize == STEP_CACHE_SIZE
    assert build(engines[0]) is not first    # evicted, built anew


def test_shard_map_solves_share_the_step(monkeypatch, data):
    """Two ShardMapExecutors on a one-device mesh with one loss object
    trace the shard_map step once."""
    count = _count_step_traces(monkeypatch, shard_map_mod)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    D, aux = data[0][0], data[1][0]
    loss = make_logistic()
    xs = []
    for _ in range(2):
        ex = ShardMapExecutor(IterationEngine(loss=loss, tau=0.1), D,
                              aux=aux, mesh=mesh)
        res = solve_with_executor(ex, loss=loss, tau=0.1, max_iters=40)
        xs.append(np.asarray(res.x))
    assert count[0] == 1
    np.testing.assert_array_equal(xs[0], xs[1])
