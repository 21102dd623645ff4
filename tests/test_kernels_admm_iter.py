"""Fused ADMM-iteration Pallas kernel vs jnp oracle (§Perf Iter C3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.admm_iter.ops import admm_iter_full
from repro.kernels.admm_iter.ref import admm_iter_ref

jax.config.update("jax_platform_name", "cpu")

CASES = [
    (2048, 128, jnp.float32, "logistic"),
    (3000, 307, jnp.float32, "logistic"),   # star-cell feature count, ragged m
    (2048, 256, jnp.bfloat16, "logistic"),
    (1500, 64, jnp.float32, "hinge"),
    (777, 33, jnp.float32, "l1"),
]


@pytest.mark.parametrize("feature_major", [False, True])
@pytest.mark.parametrize("m,n,dt,kind", CASES)
def test_fused_iter_matches_ref(m, n, dt, kind, feature_major):
    """Both panel orientations (row panels of D, feature panels of D^T),
    with a ragged last block where 512 does not divide m: pad rows add
    nothing to d, w or v."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    D = jax.random.normal(ks[0], (m, n), dt)
    aux = jnp.sign(jax.random.normal(ks[1], (m,)))
    y = jax.random.normal(ks[2], (m,))
    lam = jax.random.normal(ks[3], (m,))
    x = jax.random.normal(ks[4], (n,)) * 0.1
    y1, l1, d1, w1, v1 = admm_iter_full(
        D, aux, y, lam, x, kind=kind, delta=2.0, block_m=512,
        interpret=True, feature_major=feature_major)
    y2, l2, d2 = admm_iter_ref(D, aux, y, lam, x, kind=kind, delta=2.0)
    Df = D.astype(jnp.float32)
    w2 = jnp.dot(y2 - y, Df, precision="highest")
    v2 = jnp.dot(l2, Df, precision="highest")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=2e-5)
    for got, want in ((d1, d2), (w1, w2), (v1, v2)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5,
            atol=2e-3 * float(jnp.max(jnp.abs(want))))


def test_fused_iter_advances_admm_exactly():
    """One kernel call must equal one UnwrappedADMM.step (same y/lam/d)."""
    from repro.core import gram as gram_lib
    from repro.core.prox import make_logistic
    from repro.core.unwrapped import UnwrappedADMM
    key = jax.random.PRNGKey(1)
    m, n = 1024, 32
    D = jax.random.normal(key, (m, n))
    labels = jnp.sign(jax.random.normal(jax.random.PRNGKey(2), (m,)))
    tau = 0.1
    solver = UnwrappedADMM(loss=make_logistic(), tau=tau)
    L = solver.setup(D[None])
    y = jnp.zeros((1, m))
    lam = jnp.zeros((1, m))
    # reference step
    x_ref, Dx, y_ref, lam_ref = solver.step(L, D[None], labels[None], y, lam)
    # kernel path: x from the same solve, then the fused body
    d0 = jnp.einsum("mn,m->n", D, (y - lam)[0])
    x_k = gram_lib.gram_solve(L, d0)
    yk, lk, dk, _, _ = admm_iter_full(D, labels, y[0], lam[0], x_k,
                                      kind="logistic", delta=1.0 / tau,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(y_ref[0]),
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lam_ref[0]),
                               atol=3e-5)
    # and d feeds the NEXT x-update identically
    d_ref = jnp.einsum("mn,m->n", D, (y_ref - lam_ref)[0])
    np.testing.assert_allclose(np.asarray(dk), np.asarray(d_ref), rtol=1e-4,
                               atol=1e-3)
