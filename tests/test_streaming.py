"""Out-of-core streaming path (ISSUE 3 tentpole): the ShardedMatrixStore
contract (blocks, padding, fingerprints, mmap round-trip), stats ingestion
reusing store fingerprints, and solve_streaming parity with the in-memory
engine across backends on a dataset whose D exceeds the configured
per-block device budget."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.prox import make_hinge, make_logistic
from repro.core.unwrapped import UnwrappedADMM
from repro.data.store import ShardedMatrixStore, fingerprint_array
from repro.engine import IterationEngine, StreamingEngine, autotune
from repro.service.stats import SufficientStats

from exec_fixtures import classification_fixture

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def classif():
    return classification_fixture(N=4, m_per_node=300, n=24)


def _flat(classif):
    D = np.asarray(classif.D.reshape(-1, 24))
    a = np.asarray(classif.labels.reshape(-1))
    return D, a


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

def test_store_blocks_and_padding(classif):
    D, a = _flat(classif)                      # m = 1200
    store = ShardedMatrixStore.from_arrays(D, a, block_rows=512)
    assert (store.m, store.n, store.nblocks) == (1200, 24, 3)
    # tail block is logically short, padded on request
    Dt, at = store.block(2, padded=False)
    assert Dt.shape == (176, 24) and at.shape == (176,)
    Dp, ap = store.block(2, padded=True)
    assert Dp.shape == (512, 24) and ap.shape == (512,)
    assert np.all(Dp[176:] == 0) and np.all(ap[176:] == 0)
    np.testing.assert_array_equal(Dp[:176], Dt)
    # logical slices tile [0, m)
    sls = [store.block_slice(k) for k in range(store.nblocks)]
    assert sls[0] == slice(0, 512) and sls[2] == slice(1024, 1200)
    # reassembly is exact
    np.testing.assert_array_equal(
        np.concatenate([store.block(k)[0] for k in range(3)]), D)


def test_store_fingerprints_match_service_hashing(classif):
    """Store write-time fingerprints == hashing the blocks the service
    way, and the folded store fingerprint == ingest-order-independent."""
    D, a = _flat(classif)
    store = ShardedMatrixStore.from_arrays(D, a, block_rows=500)
    for k in range(store.nblocks):
        Db, ab = store.block(k, padded=False)
        assert store.fingerprints[k] == fingerprint_array(Db, ab)
    s = SufficientStats.from_store(store)
    assert s.fingerprint == store.fingerprint
    assert s.rows == store.m and s.labeled_rows == store.m
    # same stats as a direct streaming ingest of the raw arrays
    ref = SufficientStats.from_data(jnp.asarray(D), jnp.asarray(a),
                                    backend="chunked")
    np.testing.assert_allclose(np.asarray(s.G), np.asarray(ref.G),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s.c), np.asarray(ref.c),
                               rtol=1e-5, atol=1e-3)


def test_store_mmap_roundtrip(tmp_path, classif):
    D, a = _flat(classif)
    ram = ShardedMatrixStore.from_arrays(D, a, block_rows=256)
    disk = ShardedMatrixStore.open(ram.save(str(tmp_path / "store")))
    assert disk.path is not None
    assert (disk.m, disk.n, disk.block_rows) == (ram.m, ram.n, 256)
    assert disk.fingerprints == ram.fingerprints
    for k in range(ram.nblocks):
        np.testing.assert_array_equal(disk.block(k)[0], ram.block(k)[0])
        np.testing.assert_array_equal(disk.block(k)[1], ram.block(k)[1])


def test_streaming_block_rows_budget():
    br = autotune.streaming_block_rows(1 << 18, 512, jnp.float32,
                                       budget_bytes=8 << 20)
    # worst-case in-flight set (compute + 2 queued + 1 staging at the
    # default prefetch depth) of (br, 512) f32 blocks fits the budget
    assert 4 * br * 512 * 4 <= 8 << 20
    assert br % 8 == 0 and br >= 128
    # never taller than the dataset
    assert autotune.streaming_block_rows(100, 8, jnp.float32) <= 104


# ---------------------------------------------------------------------------
# solve_streaming parity (all backends), D larger than the device budget
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "chunked",
                                     "pallas_interpret"])
def test_solve_streaming_matches_in_memory(classif, backend):
    D, a = _flat(classif)
    # per-block device budget far below D's 115 KB: 8 blocks in flight
    br = autotune.streaming_block_rows(D.shape[0], D.shape[1], np.float32,
                                       budget_bytes=16 << 10)
    assert br * D.shape[1] * 4 < D.nbytes          # genuinely out-of-core
    store = ShardedMatrixStore.from_arrays(D, a, block_rows=br)
    solver = UnwrappedADMM(loss=make_logistic(), tau=0.1, backend=backend)
    mem = solver.solve(classif.D, classif.labels, max_iters=250)
    stream = solver.solve_streaming(store, max_iters=250, record=True)
    nx = float(jnp.linalg.norm(stream.x - mem.x) / jnp.linalg.norm(mem.x))
    assert nx < 2e-4, (backend, nx)
    # host-resident iterates come back (1, m) and match the in-memory ones
    assert stream.y.shape == (1, D.shape[0])
    np.testing.assert_allclose(np.asarray(stream.y).ravel(),
                               np.asarray(mem.y).ravel(), atol=2e-3)


def test_solve_streaming_overlap_parity(classif):
    """Double-buffered and naive-synchronous sweeps are bit-equivalent in
    results (same blocks, same jitted body, different scheduling)."""
    D, a = _flat(classif)
    store = ShardedMatrixStore.from_arrays(D, a, block_rows=301)
    solver = UnwrappedADMM(loss=make_logistic(), tau=0.1)
    db = solver.solve_streaming(store, max_iters=40, record=True)
    sync = solver.solve_streaming(store, max_iters=40, record=True,
                                  overlap=False)
    assert int(db.iters) == int(sync.iters)
    np.testing.assert_array_equal(np.asarray(db.x), np.asarray(sync.x))
    np.testing.assert_array_equal(np.asarray(db.history.objective),
                                  np.asarray(sync.history.objective))


def test_solve_streaming_objective_matches_reference_history(classif):
    """Streamed telemetry == the in-memory recorded history, including the
    pad-objective correction (m % block_rows != 0)."""
    D, a = _flat(classif)
    store = ShardedMatrixStore.from_arrays(D, a, block_rows=352)  # pad 208
    solver = UnwrappedADMM(loss=make_logistic(), tau=0.1)
    iters = 30
    ref = solver.run(classif.D, classif.labels, iters=iters)
    stream = solver.solve_streaming(store, max_iters=iters, record=True)
    k = int(stream.iters)
    np.testing.assert_allclose(
        np.asarray(stream.history.objective)[:k],
        np.asarray(ref.history.objective)[:k], rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(stream.history.primal_res)[:k],
        np.asarray(ref.history.primal_res)[:k], atol=1e-3)


def test_solve_streaming_warm_start(classif):
    D, a = _flat(classif)
    store = ShardedMatrixStore.from_arrays(D, a, block_rows=256)
    solver = UnwrappedADMM(loss=make_logistic(), tau=0.1)
    cold = solver.solve_streaming(store, max_iters=300)
    warm = solver.solve_streaming(store, max_iters=300, x0=cold.x)
    assert int(warm.iters) < int(cold.iters)
    nx = float(jnp.linalg.norm(warm.x - cold.x) / jnp.linalg.norm(cold.x))
    assert nx < 5e-3, nx


def test_solve_streaming_hinge_ragged_tail(classif):
    """hinge parity holds with a ragged tail block (pad-row value is 1,
    not 0 — exercises the pad-objective correction for a second loss)."""
    D, a = _flat(classif)
    store = ShardedMatrixStore.from_arrays(D, a, block_rows=389)
    solver = UnwrappedADMM(loss=make_hinge(1.0), tau=0.5, rho=1.0)
    mem = solver.solve(classif.D, classif.labels, max_iters=200)
    stream = solver.solve_streaming(store, max_iters=200)
    nx = float(jnp.linalg.norm(stream.x - mem.x) / jnp.linalg.norm(mem.x))
    assert nx < 1e-3, nx


def test_solve_streaming_unlabeled_store(classif):
    """A store built WITHOUT aux streams through every has_aux=False
    branch (staging, step, pad objective) — l1 loss needs no labels."""
    from repro.core.prox import make_l1
    D, _ = _flat(classif)
    store = ShardedMatrixStore.from_arrays(D, block_rows=389)  # no aux
    assert not store.has_aux
    assert store.block(0)[1] is None
    solver = UnwrappedADMM(loss=make_l1(0.5), tau=1.0)
    mem = solver.solve(D[None], None, max_iters=120)
    stream = solver.solve_streaming(store, max_iters=120, record=True)
    # l1-on-Dx drives x to ~0; compare absolutely, scaled by the data
    tol = 1e-4 * max(float(jnp.linalg.norm(mem.x)), 1.0)
    assert float(jnp.linalg.norm(stream.x - mem.x)) < tol
    assert np.all(np.isfinite(np.asarray(stream.history.objective)))
    # unlabeled ingest works too and folds the same fingerprints
    s = SufficientStats.from_store(store)
    assert s.rows == store.m and s.labeled_rows == 0
    assert s.fingerprint == store.fingerprint


def test_streaming_device_dtype_residency(classif):
    """An f64 host store with f32 device residency: blocks are cast at
    staging time, results match the f32 solve."""
    D, a = _flat(classif)
    store64 = ShardedMatrixStore.from_arrays(D.astype(np.float64),
                                             a.astype(np.float64),
                                             block_rows=256)
    solver = UnwrappedADMM(loss=make_logistic(), tau=0.1)
    eng = StreamingEngine(engine=solver.engine, device_dtype="float32")
    assert eng.residency_dtype(store64) == jnp.float32
    res64 = solver.solve_streaming(store64, max_iters=150,
                                   device_dtype="float32")
    store32 = ShardedMatrixStore.from_arrays(D, a, block_rows=256)
    res32 = solver.solve_streaming(store32, max_iters=150)
    assert res64.x.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(res64.x), np.asarray(res32.x),
                               atol=1e-5)


def test_staged_tuple_payloads_and_abandonment(classif):
    """staged() must (a) pass through 2-tuple payloads whose first element
    is an array (sentinel detection is by identity, not ==, which numpy
    arrays refuse), and (b) unblock its producer thread when the consumer
    abandons the generator mid-stream."""
    import threading
    import time
    from repro.engine.streaming import staged
    D, a = _flat(classif)
    store = ShardedMatrixStore.from_arrays(D, a, block_rows=128)
    items = list(staged(range(store.nblocks),
                        lambda k: store.block(k, padded=True), 2))
    assert len(items) == store.nblocks
    np.testing.assert_array_equal(items[0][0], store.block(0, True)[0])
    before = threading.active_count()
    gen = staged(range(store.nblocks),
                 lambda k: store.block(k, padded=True), 2)
    next(gen)
    gen.close()                       # consumer walks away mid-stream
    time.sleep(0.3)
    assert threading.active_count() <= before


def test_sweep_padded_rows_do_not_leak(classif):
    """Zero pad rows of the tail block contribute nothing to d and the
    stopping-rule scalars (the zero-row argument,
    streaming edition)."""
    D, a = _flat(classif)
    eng = IterationEngine(loss=make_logistic(), tau=0.1,
                          backend="chunked")
    seng = StreamingEngine(engine=eng)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(24),
                    jnp.float32) * 0.1
    results = {}
    for br in (500, 1200):    # ragged tail (pad 300) vs single block
        store = ShardedMatrixStore.from_arrays(D, a, block_rows=br)
        y = np.zeros((store.m,), np.float32)
        lam = np.zeros((store.m,), np.float32)
        sw = seng.sweep(store, x, y, lam)
        results[br] = (np.asarray(sw.d), float(sw.r_sq), float(sw.dx_sq),
                       y.copy(), lam.copy())
    d_r, r_r, dx_r, y_r, lam_r = results[1200]
    d_p, r_p, dx_p, y_p, lam_p = results[500]
    np.testing.assert_allclose(d_p, d_r, rtol=1e-5, atol=1e-4)
    assert abs(r_p - r_r) < 1e-3 * max(abs(r_r), 1.0)
    assert abs(dx_p - dx_r) < 1e-3 * max(abs(dx_r), 1.0)
    np.testing.assert_allclose(y_p, y_r, atol=1e-5)
    np.testing.assert_allclose(lam_p, lam_r, atol=1e-5)
