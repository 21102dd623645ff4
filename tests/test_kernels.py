"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attn.ops import chunked_attention_xla, flash_attention
from repro.kernels.flash_attn.ref import mha_ref
from repro.kernels.gram.ops import gram, gram_and_rhs
from repro.kernels.gram.ref import gram_ref, gram_with_rhs_ref
from repro.kernels.prox.ops import prox_update
from repro.kernels.prox.ref import prox_update_ref

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Gram kernel (the transpose-reduction hot-spot)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n", [(256, 128), (1000, 130), (512, 64),
                                 (2048, 512), (77, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("feature_major", [False, True])
def test_gram_matches_ref(m, n, dtype, feature_major):
    """Both panel orientations; ragged blocks along m (masked) and n."""
    D = jax.random.normal(jax.random.PRNGKey(0), (m, n), dtype)
    G1 = gram(D, block_m=256, block_n=128, interpret=True,
              feature_major=feature_major)
    G2 = gram_ref(D)
    tol = 5e-6 * m if dtype == jnp.bfloat16 else 2e-6 * m
    np.testing.assert_allclose(np.asarray(G1), np.asarray(G2),
                               atol=tol * float(jnp.max(jnp.abs(G2))) / m,
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_gram_symmetric_skip_equals_full():
    D = jax.random.normal(jax.random.PRNGKey(1), (512, 256))
    G1 = gram(D, symmetric_skip=True, interpret=True)
    G2 = gram(D, symmetric_skip=False, interpret=True)
    np.testing.assert_allclose(np.asarray(G1), np.asarray(G2), rtol=1e-6)


def test_gram_output_is_psd_and_symmetric():
    D = jax.random.normal(jax.random.PRNGKey(2), (300, 60))
    G = np.asarray(gram(D, interpret=True))
    np.testing.assert_allclose(G, G.T, rtol=1e-6)
    w = np.linalg.eigvalsh(G)
    assert w.min() > -1e-3


@pytest.mark.parametrize("m,n", [(512, 100), (999, 65)])
@pytest.mark.parametrize("feature_major", [False, True])
def test_gram_with_rhs(m, n, feature_major):
    key = jax.random.PRNGKey(3)
    D = jax.random.normal(key, (m, n))
    b = jax.random.normal(jax.random.PRNGKey(4), (m,))
    G1, c1 = gram_and_rhs(D, b, block_m=256, interpret=True,
                          feature_major=feature_major)
    G2, c2 = gram_with_rhs_ref(D, b)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=3e-5,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(G1), np.asarray(G2), rtol=3e-5,
                               atol=1e-3)


# ---------------------------------------------------------------------------
# Fused prox/lambda kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [1000, 262144, 300001])
@pytest.mark.parametrize("kind,delta", [("logistic", 10.0), ("hinge", 0.7),
                                        ("l1", 0.3), ("least_squares", 2.0)])
def test_prox_kernel_matches_ref(m, kind, delta):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    Dx = jax.random.normal(k1, (m,)) * 3
    lam = jax.random.normal(k2, (m,))
    aux = jnp.sign(jax.random.normal(k3, (m,))) if kind != "l1" else None
    y1, l1 = prox_update(Dx, lam, aux, kind=kind, delta=delta,
                         interpret=True, block_rows=64)
    aux_ref = aux if aux is not None else jnp.zeros_like(Dx)
    y2, l2 = prox_update_ref(kind, Dx, lam, aux_ref, delta)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-6)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=2e-6)


def test_prox_kernel_fusion_identity():
    """lam' + y == Dx + lam (conservation of the ADMM update)."""
    m = 4096
    Dx = jax.random.normal(jax.random.PRNGKey(5), (m,))
    lam = jax.random.normal(jax.random.PRNGKey(6), (m,))
    labels = jnp.sign(jax.random.normal(jax.random.PRNGKey(7), (m,)))
    y, lam_new = prox_update(Dx, lam, labels, kind="logistic", delta=1.0,
                             interpret=True, block_rows=64)
    np.testing.assert_allclose(np.asarray(y + lam_new),
                               np.asarray(Dx + lam), atol=2e-6)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

CASES = [
    (2, 4, 2, 256, 256, 64, jnp.float32, True),
    (1, 8, 1, 512, 512, 128, jnp.float32, True),
    (2, 4, 4, 256, 256, 64, jnp.bfloat16, True),
    (1, 2, 2, 256, 512, 64, jnp.float32, False),
]


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D,dt,causal", CASES)
def test_flash_attention_matches_ref(B, Hq, Hkv, Sq, Skv, D, dt, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), dt)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D), dt)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D), dt)
    ref = mha_ref(q, k, v, causal=causal).astype(jnp.float32)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    for impl in ("pallas_interpret", "xla"):
        o = flash_attention(q, k, v, causal=causal, impl=impl,
                            block_q=128, block_k=128).astype(jnp.float32)
        assert float(jnp.max(jnp.abs(o - ref))) < tol, impl


def test_windowed_attention_matches_dense_mask():
    """Local (banded) attention vs explicit dense masking."""
    B, H, S, D, W = 1, 2, 96, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    o = chunked_attention_xla(q, k, v, causal=True, window=W, chunk_q=32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(1.0 * D)
    qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = (qi >= ki) & (ki > qi - W)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)


def test_chunked_attention_unroll_matches_scan():
    B, H, S, D = 1, 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    o1 = chunked_attention_xla(q, k, v, causal=True, chunk_q=32, unroll=False)
    o2 = chunked_attention_xla(q, k, v, causal=True, chunk_q=32, unroll=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)
