"""Iteration-engine backend parity (ISSUE 2 acceptance): the same
(x, history) to tolerance across reference / chunked / pallas-interpret for
lasso, logistic and svm, including bf16 residency and the fused Gram+RHS
kernel, plus the engine-adjacent satellites (solve() warm start, history
without per-iteration x stacking, stats ingest through the engine)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gram as gram_lib
from repro.core.fasta import transpose_reduction_lasso
from repro.core.prox import (
    StackedProx,
    make_hinge,
    make_huber,
    make_l1,
    make_least_squares,
    make_logistic,
)
from repro.core.unwrapped import UnwrappedADMM
from repro.data.synthetic import classification_problem, lasso_problem
from repro.engine import IterationEngine, autotune, gram_stats
from repro.service.stats import SufficientStats

jax.config.update("jax_platform_name", "cpu")

BACKENDS = ("reference", "chunked", "pallas_interpret")


@pytest.fixture(scope="module")
def classif():
    return classification_problem(jax.random.PRNGKey(0), N=4,
                                  m_per_node=250, n=20)


def _rand_state(m, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    D = jax.random.normal(ks[0], (m, n))
    aux = jnp.sign(jax.random.normal(ks[1], (m,)))
    y = jax.random.normal(ks[2], (m,))
    lam = jax.random.normal(ks[3], (m,))
    x = jax.random.normal(ks[4], (n,)) * 0.1
    return D, aux, y, lam, x


# ---------------------------------------------------------------------------
# iterate(): single fused step, all backends, all kernel kinds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["chunked", "pallas_interpret"])
@pytest.mark.parametrize("loss,tau", [
    (make_logistic(), 0.5), (make_hinge(0.7), 1.0),
    (make_l1(0.3), 1.0), (make_least_squares(), 2.0),
])
def test_iterate_backend_parity(backend, loss, tau):
    m, n = 1234, 40
    D, aux, y, lam, x = _rand_state(m, n)
    a = None if loss.name == "l1" else aux
    ref = IterationEngine(loss=loss, tau=tau, backend="reference").iterate(
        D, a, y, lam, x)
    st = IterationEngine(loss=loss, tau=tau, backend=backend).iterate(
        D, a, y, lam, x)
    scale = float(jnp.max(jnp.abs(ref.d)))
    np.testing.assert_allclose(np.asarray(st.y), np.asarray(ref.y),
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(st.lam), np.asarray(ref.lam),
                               atol=3e-5)
    for got, want in [(st.d, ref.d), (st.w, ref.w), (st.v, ref.v)]:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-3 * max(scale, 1.0))


@pytest.mark.parametrize("backend", ["chunked", "pallas_interpret"])
def test_iterate_bf16_residency_parity(backend):
    m, n = 2048, 64
    D, aux, y, lam, x = _rand_state(m, n, seed=1)
    loss = make_logistic()
    ref = IterationEngine(loss=loss, tau=0.5, backend="reference").iterate(
        D, aux, y, lam, x)
    eng = IterationEngine(loss=loss, tau=0.5, backend=backend,
                          residency="bf16")
    Dres = eng.prepare(D)
    assert Dres.dtype == jnp.bfloat16
    st = eng.iterate(Dres, aux, y, lam, x)
    assert st.d.dtype == jnp.float32          # f32 in-register accumulation
    np.testing.assert_allclose(np.asarray(st.y), np.asarray(ref.y),
                               atol=5e-2)
    np.testing.assert_allclose(
        np.asarray(st.d), np.asarray(ref.d),
        atol=2e-2 * float(jnp.max(jnp.abs(ref.d))))


def test_backend_capability_fallbacks():
    # only "auto" chooses: huber has no Pallas prox kind, so an explicit
    # pallas request raises while auto lands on chunked; StackedProx is
    # not coordinatewise -> reference (DESIGN.md §8 selection rules).
    for be in ("pallas", "pallas_interpret"):
        with pytest.raises(ValueError, match="cannot run loss"):
            IterationEngine(loss=make_huber(1.0), tau=1.0,
                            backend=be).resolve()
        with pytest.raises(ValueError, match="cannot run loss"):
            IterationEngine(loss=make_logistic(), tau=1.0,
                            backend=be).resolve(jnp.float64)
    assert IterationEngine(loss=make_huber(1.0), tau=1.0,
                           backend="auto").resolve() == "chunked"
    sp = StackedProx(blocks=(make_l1(0.1), make_logistic()), sizes=(4, 8))
    assert IterationEngine(loss=sp.as_loss(), tau=1.0,
                           backend="chunked").resolve() == "reference"
    with pytest.raises(ValueError):
        IterationEngine(loss=make_logistic(), tau=1.0, backend="cuda")


# ---------------------------------------------------------------------------
# Full solver parity: same (x, history) across backends
# ---------------------------------------------------------------------------

def _run_parity(solver_kw, D, aux, iters, x_rtol=2e-4, obj_rtol=1e-4):
    results = {
        be: UnwrappedADMM(backend=be, **solver_kw).run(D, aux, iters=iters)
        for be in BACKENDS
    }
    ref = results["reference"]
    for be in ("chunked", "pallas_interpret"):
        r = results[be]
        nx = float(jnp.linalg.norm(r.x - ref.x) / jnp.linalg.norm(ref.x))
        assert nx < x_rtol, (be, nx)
        rel = np.max(np.abs(np.asarray(r.history.objective)
                            - np.asarray(ref.history.objective))
                     / np.abs(np.asarray(ref.history.objective)))
        assert rel < obj_rtol, (be, rel)
        np.testing.assert_allclose(np.asarray(r.history.primal_res),
                                   np.asarray(ref.history.primal_res),
                                   atol=1e-3)
    return results


def test_run_backend_parity_logistic(classif):
    _run_parity(dict(loss=make_logistic(), tau=0.1),
                classif.D, classif.labels, iters=60)


def test_run_backend_parity_svm(classif):
    _run_parity(dict(loss=make_hinge(1.0), tau=0.5, rho=1.0),
                classif.D, classif.labels, iters=80)


def test_run_backend_parity_bf16_residency(classif):
    ref = UnwrappedADMM(loss=make_logistic(), tau=0.1,
                        backend="reference").run(
        classif.D, classif.labels, iters=60)
    r = UnwrappedADMM(loss=make_logistic(), tau=0.1, backend="chunked",
                      residency="bf16").run(
        classif.D, classif.labels, iters=60)
    nx = float(jnp.linalg.norm(r.x - ref.x) / jnp.linalg.norm(ref.x))
    assert nx < 5e-3, nx


def test_lasso_gram_backend_parity():
    """lasso rides the engine's Gram path: identical stats -> identical
    FASTA solution across backends."""
    prob = lasso_problem(jax.random.PRNGKey(1), N=2, m_per_node=400, n=48)
    Dflat = prob.D.reshape(-1, 48)
    bflat = prob.b.reshape(-1)
    sols = {}
    for be in BACKENDS:
        G, c = gram_stats(Dflat, bflat, backend=be)
        sols[be] = np.asarray(
            transpose_reduction_lasso(G, c, float(prob.mu), iters=1500).x)
    for be in ("chunked", "pallas_interpret"):
        np.testing.assert_allclose(sols[be], sols["reference"],
                                   rtol=1e-3, atol=1e-5)


def test_fused_gram_rhs_kernel_multi_rhs():
    """Fused Gram+RHS Pallas kernel vs gram_and_rhs_chunked, (m,) and
    (m, r) right-hand sides, f32 and bf16 row streams."""
    for (m, n, r, dt) in [(700, 96, 0, jnp.float32), (513, 33, 5,
                                                      jnp.float32),
                          (256, 140, 2, jnp.bfloat16)]:
        D = jax.random.normal(jax.random.PRNGKey(2), (m, n), dt)
        b = jax.random.normal(jax.random.PRNGKey(3),
                              (m, r) if r else (m,))
        G1, c1 = gram_stats(D, b, backend="pallas_interpret")
        G2, c2 = gram_stats(D, b, backend="chunked")
        tol = dict(rtol=2e-2, atol=1e-2) if dt == jnp.bfloat16 else dict(
            rtol=3e-5, atol=1e-3)
        np.testing.assert_allclose(np.asarray(G1), np.asarray(G2), **tol)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), **tol)
        assert c1.shape == ((n, r) if r else (n,))


# ---------------------------------------------------------------------------
# Padding edges: m not divisible by the block size (chunked + pallas).
# The prox of a padded zero row may be nonzero (e.g. logistic at z=0 has
# curvature) but its D row is zero, so NOTHING may leak into the d/w/v
# reductions.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["chunked", "pallas_interpret"])
@pytest.mark.parametrize("loss,tau", [(make_logistic(), 0.5),
                                      (make_hinge(0.7), 1.0)])
@pytest.mark.parametrize("m", [1000, 1023, 1025])
def test_padding_edges_no_leak(backend, loss, tau, m):
    n = 32
    block = 256                       # never divides any of the m values
    assert m % block != 0
    D, aux, y, lam, x = _rand_state(m, n, seed=m)
    ref = IterationEngine(loss=loss, tau=tau, backend="reference").iterate(
        D, aux, y, lam, x)
    st = IterationEngine(loss=loss, tau=tau, backend=backend,
                         block_m=block).iterate(D, aux, y, lam, x)
    scale = max(float(jnp.max(jnp.abs(ref.d))), 1.0)
    np.testing.assert_allclose(np.asarray(st.y), np.asarray(ref.y),
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(st.lam), np.asarray(ref.lam),
                               atol=3e-5)
    for got, want in [(st.d, ref.d), (st.w, ref.w), (st.v, ref.v)]:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-3 * scale)
    # iterates keep exactly m rows (padding never escapes the engine)
    assert st.y.shape == (m,) and st.lam.shape == (m,)


def test_transpose_d_streams_without_dense_copy(monkeypatch):
    """transpose_d routes through the backend-dispatched streaming pass:
    the chunked/pallas engines must NOT call the dense gram_rhs (which
    materializes a full accumulation-precision copy of D)."""
    m, n = 700, 24
    D, _, y, lam, _ = _rand_state(m, n, seed=7)
    want = np.asarray(gram_lib.gram_rhs(D, y - lam))
    for backend in ("chunked", "pallas_interpret"):
        eng = IterationEngine(loss=make_logistic(), tau=1.0,
                              backend=backend)
        np.testing.assert_allclose(np.asarray(eng.transpose_d(D, y, lam)),
                                   want, rtol=1e-5, atol=1e-4)
    from repro.engine import engine as engine_mod

    def boom(*a, **k):
        raise AssertionError("dense gram_rhs called from a streaming "
                             "backend")

    monkeypatch.setattr(engine_mod.gram_lib, "gram_rhs", boom)
    eng = IterationEngine(loss=make_logistic(), tau=1.0, backend="chunked")
    eng.transpose_d(D, y, lam)        # streams: must not hit the dense path
    with pytest.raises(AssertionError, match="dense gram_rhs"):
        IterationEngine(loss=make_logistic(), tau=1.0,
                        backend="reference").transpose_d(D, y, lam)


# ---------------------------------------------------------------------------
# Satellites
# ---------------------------------------------------------------------------

def test_solve_honors_warm_start(classif):
    solver = UnwrappedADMM(loss=make_logistic(), tau=0.1)
    cold = solver.solve(classif.D, classif.labels, max_iters=300)
    warm = solver.solve(classif.D, classif.labels, max_iters=300, x0=cold.x)
    # the warm start threads through: converged, and to the same optimum
    assert int(warm.iters) < 300
    nx = float(jnp.linalg.norm(warm.x - cold.x)
               / jnp.linalg.norm(cold.x))
    assert nx < 5e-3, nx
    # and x0 actually changes the trajectory (first x-update starts at x0):
    # a one-iteration warm solve must differ from a one-iteration cold one.
    w1 = solver.run(classif.D, classif.labels, iters=1, x0=cold.x)
    c1 = solver.run(classif.D, classif.labels, iters=1)
    assert float(jnp.linalg.norm(w1.x - c1.x)) > 1e-3


def test_history_final_x_from_carry(classif):
    """History carries scalars only — no (iters, n) x stacking — while the
    final x still matches the recorded trajectory's endpoint."""
    solver = UnwrappedADMM(loss=make_logistic(), tau=0.1)
    res = solver.run(classif.D, classif.labels, iters=40)
    assert res.x.shape == (20,)
    assert set(res.history._fields) == {
        "objective", "primal_res", "dual_res", "grad_sq", "converged_at"}
    for field in ("objective", "primal_res", "dual_res", "grad_sq"):
        assert getattr(res.history, field).shape == (40,)
    # final objective consistent with the returned x
    obj_from_x = float(solver._objective(
        res.x,
        jnp.einsum("imn,n->im", classif.D, res.x).reshape(-1),
        classif.labels.reshape(-1)))
    assert abs(obj_from_x - float(res.history.objective[-1])) \
        < 1e-3 * abs(obj_from_x)
    assert solver.run(classif.D, classif.labels, iters=5,
                      record=False).history is None


def test_stats_ingest_backend_parity():
    D = jax.random.normal(jax.random.PRNGKey(4), (600, 32))
    b = jax.random.normal(jax.random.PRNGKey(5), (600,))
    s_chunked = SufficientStats.from_data(D, b, backend="chunked")
    s_pallas = SufficientStats.from_data(D, b, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(s_pallas.G),
                               np.asarray(s_chunked.G),
                               rtol=3e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s_pallas.c),
                               np.asarray(s_chunked.c),
                               rtol=3e-5, atol=1e-3)
    assert s_pallas.fingerprint == s_chunked.fingerprint
    # streaming update still goes through the engine path
    s2 = s_chunked.update(D[:100], b[:100])
    ref = np.asarray(s_chunked.G) + np.asarray(D[:100].T @ D[:100])
    np.testing.assert_allclose(np.asarray(s2.G), ref, rtol=1e-5, atol=1e-3)


def test_autotune_blocks_are_sane():
    bm = autotune.iter_block_m(1 << 20, 512, jnp.float32)
    assert 128 <= bm <= 4096 and bm % 8 == 0
    # never taller than the (padded) row count
    assert autotune.iter_block_m(300, 64, jnp.float32) <= 304
    gm, gn = autotune.gram_blocks(1 << 20, 512, jnp.bfloat16)
    assert gn % 128 == 0 and gm % 16 == 0
    assert autotune.chunked_block_rows(1 << 20, 512, jnp.float32) % 8 == 0
    # memoized: same key -> same object
    key = ("iter", 1 << 20, 512, "float32")
    assert key in autotune.CACHE
