"""Synthetic GLM data generators — paper §10.1 and §10.2 analogue.

All generators emit the node-stacked layout (N, m_i, n) used by the solvers,
with deterministic per-node seeding (node i derives its own fold of the key,
so generation is reproducible shard-by-shard without materializing the global
matrix anywhere — the same discipline the distributed pipeline uses).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


class LassoProblem(NamedTuple):
    D: Array          # (N, m_i, n)
    b: Array          # (N, m_i)
    x_true: Array     # (n,)
    mu: Array         # scalar: the paper's 10% rule


class ClassifProblem(NamedTuple):
    D: Array          # (N, m_i, n)
    labels: Array     # (N, m_i) in {-1, +1}


def _hetero_shift(key, N: int, scale: float) -> Array:
    """Paper: 'one random Gaussian scalar for each node, added to D_i'."""
    return scale * jax.random.normal(key, (N, 1, 1))


def lasso_problem(
    key,
    N: int,
    m_per_node: int,
    n: int,
    active: int = 10,
    heterogeneity: float = 0.0,
    noise_sigma: float = 1.0,
    dtype=jnp.float32,
) -> LassoProblem:
    """Boyd-style lasso test problem (paper §10.1 'Lasso problems').

    D random Gaussian; x_true has ``active`` unit-magnitude entries;
    b = D x_true + sigma*eta; mu = 10% of mu_max = ||D^T b||_inf.
    """
    kD, kx, keta, kh, ksgn = jax.random.split(key, 5)
    D = jax.random.normal(kD, (N, m_per_node, n), dtype)
    if heterogeneity:
        D = D + _hetero_shift(kh, N, heterogeneity).astype(dtype)
    idx = jax.random.permutation(kx, n)[:active]
    signs = jnp.sign(jax.random.normal(ksgn, (active,))) .astype(dtype)
    x_true = jnp.zeros((n,), dtype).at[idx].set(signs)
    b = jnp.einsum("imn,n->im", D, x_true) + noise_sigma * jax.random.normal(
        keta, (N, m_per_node), dtype
    )
    Dt_b = jnp.einsum("imn,im->n", D.astype(jnp.float32), b.astype(jnp.float32))
    mu = 0.1 * jnp.max(jnp.abs(Dt_b))
    return LassoProblem(D, b, x_true, mu)


def classification_problem(
    key,
    N: int,
    m_per_node: int,
    n: int,
    informative: int = 5,
    mean_shift: float = 1.0,
    heterogeneity: float = 0.0,
    dtype=jnp.float32,
) -> ClassifProblem:
    """Paper §10.1 'Classification problems'.

    Two Gaussian classes; class 2 has mean ``mean_shift`` in its first
    ``informative`` columns (classes are NOT perfectly separable). Rows of the
    two classes are interleaved evenly per node; optional per-node scalar
    shift creates heterogeneity.
    """
    kD, kh, kperm = jax.random.split(key, 3)
    m_half = m_per_node // 2
    labels = jnp.concatenate(
        [
            -jnp.ones((N, m_per_node - m_half), dtype),
            jnp.ones((N, m_half), dtype),
        ],
        axis=1,
    )
    # Shuffle the labels within each node so classes are interleaved, then
    # draw the rows elementwise from them: shuffling D's rows instead would
    # gather a second D-sized array (the peak that matters at the paper's
    # 4.56 GB-per-chip share).
    perm = jax.vmap(lambda k: jax.random.permutation(k, m_per_node))(
        jax.random.split(kperm, N)
    )
    labels = jnp.take_along_axis(labels, perm, axis=1)
    shift = jnp.zeros((n,), dtype).at[:informative].set(mean_shift)
    D = jax.random.normal(kD, (N, m_per_node, n), dtype) + jnp.where(
        labels[..., None] > 0, shift, 0.0)
    if heterogeneity:
        D = D + _hetero_shift(kh, N, heterogeneity).astype(dtype)
    return ClassifProblem(D, labels)


def star_catalog_problem(
    key,
    N: int,
    m_per_node: int,
    base_features: int = 17,
    dtype=jnp.float32,
) -> ClassifProblem:
    """GSC-II analogue (paper §10.2): 17 base measurements + ALL second-order
    products (17x17 = 289) + bias = 307 features, matching the paper.

    Base features are drawn from a node-dependent (heterogeneous) Gaussian —
    empirical sky-survey data is not iid across shards — and the label is a
    noisy sparse logistic teacher over the interaction features, mimicking
    'star / not-a-star' structure. Features are normalized as in the paper.
    """
    kD, kh, kw, kn = jax.random.split(key, 4)
    base = jax.random.normal(kD, (N, m_per_node, base_features), dtype)
    base = base + 0.5 * _hetero_shift(kh, N, 1.0).astype(dtype)
    # ALL second-order products (full 17x17 grid, as the paper's 307 needs).
    inter = (base[..., :, None] * base[..., None, :]).reshape(
        N, m_per_node, base_features * base_features)
    ones = jnp.ones((N, m_per_node, 1), dtype)
    D = jnp.concatenate([base, inter, ones], axis=-1)
    # Normalize features (global scale; per-feature std over a sample).
    std = jnp.maximum(jnp.std(D.reshape(-1, D.shape[-1]), axis=0), 1e-6)
    D = D / std
    n = D.shape[-1]
    w = jax.random.normal(kw, (n,), dtype) * (
        jax.random.bernoulli(kw, 0.1, (n,))
    )
    logits = jnp.einsum("imn,n->im", D, w)
    noise = 0.5 * jax.random.normal(kn, logits.shape, dtype)
    labels = jnp.sign(logits + noise)
    labels = jnp.where(labels == 0, 1.0, labels).astype(dtype)
    return ClassifProblem(D, labels)
