"""Multi-request coalescing: one cached factor, many solves.

The asymmetry the serving layer exploits: after the O(m n^2) Gram reduction,
every additional solve against the same dataset is O(n^2) — so requests that
share a dataset fingerprint should share one factor and run as a *stacked*
solve. Three coalescing shapes:

  * ``batched_gram_solve``   — k right-hand sides through one Cholesky
                               factor (64 ridge probes = one (n, 64) solve);
  * ``batched_quad_prox``    — vmapped FASTA over stacked (c_j, mu_j) lanes
                               sharing one G (lasso mu-path, elastic-net
                               grids, NNLS probe banks);
  * ``rhs_chunked``          — the fused one-pass D^T B for a whole
                               micro-batch of label vectors (one data pass
                               for k requests, not k passes).

All are jit-compiled with static batch shape; the server buckets requests
so recompilation only happens per (problem, n, k) shape class.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import gram as gram_lib
from repro.service import registry

Array = jax.Array


@jax.jit
def batched_gram_solve(L: Array, rhs_stack: Array) -> Array:
    """Solve (L L^T) X = rhs for k stacked right-hand sides.

    ``rhs_stack`` is (k, n); returns (k, n). One triangular solve pair over
    an (n, k) block — the BLAS-3 path, not k separate BLAS-2 solves.
    """
    return gram_lib.gram_solve(L, rhs_stack.T).T


@partial(jax.jit, static_argnames=("block_rows",))
def rhs_chunked(D: Array, B: Array, block_rows: int = 1024) -> Array:
    """Streaming D^T B over row blocks: (m, n), (m, k) -> (n, k).

    The micro-batch analogue of gram_and_rhs_chunked's rhs pass — k label
    vectors share one pass over the data (and skip the Gram term, which the
    caller already has cached).
    """
    m, n = D.shape
    acc = gram_lib._acc_dtype(D.dtype)

    def body(C, blk):
        Db, Bb = blk
        return C + gram_lib.t_dot(Db.astype(acc), Bb.astype(acc)), None

    C0 = jnp.zeros((n, B.shape[1]), acc)
    C, _ = gram_lib.scan_row_blocks(body, C0, (D, B), block_rows)
    return C


@partial(jax.jit, static_argnames=("kind", "iters"))
def batched_quad_prox(G: Array, c_stack: Array, mu_stack: Array,
                      kind: str = "lasso", l2: float = 0.0,
                      iters: int = 1000) -> Tuple[Array, Array]:
    """vmapped stats-path solve over stacked (c_j, mu_j) lanes sharing G.

    ``kind`` is any problem with a registered gram solver
    (registry.GRAM_SOLVERS — lasso / elastic_net / nnls / ridge / future
    registrations). Returns (X, iters_used) with X of shape (k, n). A lasso
    regularization path is the degenerate case c_stack = tile(c),
    mu_stack = the mu grid.
    """
    try:
        solver = registry.GRAM_SOLVERS[kind]
    except KeyError:
        raise ValueError(
            f"no gram solver registered for {kind!r}; "
            f"available: {sorted(registry.GRAM_SOLVERS)}") from None

    def one(c, mu):
        x, it, _ = solver(G, c, mu=mu, l2=l2, iters=iters)
        return x, jnp.asarray(it)

    return jax.vmap(one)(c_stack, mu_stack)


def lasso_mu_path(G: Array, c: Array, mus: Array,
                  iters: int = 1000) -> Array:
    """Full regularization path from ONE cached Gram: (len(mus), n)."""
    k = mus.shape[0]
    c_stack = jnp.broadcast_to(c, (k,) + c.shape)
    X, _ = batched_quad_prox(G, c_stack, jnp.asarray(mus), kind="lasso",
                             iters=iters)
    return X
