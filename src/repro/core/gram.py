"""Transpose reduction: Gram-matrix computation (paper §4).

The enabling observation of the paper: for tall D (m >> n),
``D^T D = sum_i D_i^T D_i`` is only n x n. Each node builds its local Gram
matrix by streaming row blocks; one all-reduce produces the global Gram.

Three implementations with identical semantics:
  * ``gram``            — one-shot jnp (oracle / small inputs).
  * ``gram_chunked``    — lax.scan over row blocks (``scan_row_blocks``, which
                          slices D in place rather than padding a copy);
                          bounds live memory to one block, mirrors the
                          HBM->VMEM streaming the Pallas kernel performs.
  * ``repro.kernels.gram.ops.gram`` — the Pallas TPU kernel (VMEM accumulator).

Accumulation is always f32 (or f64 if inputs are f64): the Gram sum is a long
reduction over up to ~1e9 rows, so bf16 inputs are up-cast per block.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def _acc_dtype(dtype) -> jnp.dtype:
    return jnp.float64 if dtype == jnp.float64 else jnp.float32


def t_dot(a: Array, b: Array) -> Array:
    """a^T b at full f32 precision: a TPU's default runs f32 operands as a
    single bf16 pass, which a long row reduction cannot afford."""
    return jnp.matmul(a.T, b, precision=jax.lax.Precision.HIGHEST)


def gram(D: Array) -> Array:
    """D^T D in accumulation precision."""
    Dc = D.astype(_acc_dtype(D.dtype))
    return Dc.T @ Dc


def gram_rhs(D: Array, b: Array) -> Array:
    """D^T b in accumulation precision (the lasso RHS, paper §4)."""
    acc = _acc_dtype(D.dtype)
    return D.astype(acc).T @ b.astype(acc)


def scan_row_blocks(body, init, arrays, block_rows: int):
    """Fold ``body(carry, blocks) -> (carry, out)`` over consecutive
    ``block_rows``-row blocks of ``arrays`` (all with the same m rows).

    The shared scaffold of every streaming row-block reduction here. It
    never pads or copies the arrays: each full block is a dynamic slice of
    the original, and a ragged tail is one static slice of fewer than
    ``block_rows`` rows. ``out`` (a pytree of
    per-row arrays, or None) comes back concatenated to m rows.
    """
    m = arrays[0].shape[0]
    nfull, tail = divmod(m, block_rows)
    outs = []
    carry = init
    if nfull:
        def step(c, i):
            return body(c, tuple(
                jax.lax.dynamic_slice_in_dim(a, i * block_rows, block_rows)
                for a in arrays))

        carry, full = jax.lax.scan(step, carry, jnp.arange(nfull))
        outs.append(jax.tree.map(
            lambda o: o.reshape((nfull * block_rows,) + o.shape[2:]), full))
    if tail:
        carry, last = body(carry, tuple(a[nfull * block_rows:]
                                        for a in arrays))
        outs.append(last)
    if len(outs) == 1:
        return carry, outs[0]
    return carry, jax.tree.map(lambda *o: jnp.concatenate(o), *outs)


@partial(jax.jit, static_argnames=("block_rows",))
def gram_chunked(D: Array, block_rows: int = 1024) -> Array:
    """Streaming D^T D over row blocks of size ``block_rows``."""
    m, n = D.shape
    acc = _acc_dtype(D.dtype)

    def body(G, blk):
        Db = blk[0].astype(acc)
        return G + t_dot(Db, Db), None

    G, _ = scan_row_blocks(body, jnp.zeros((n, n), acc), (D,), block_rows)
    return G


@partial(jax.jit, static_argnames=("block_rows",))
def gram_and_rhs_chunked(
    D: Array, b: Array, block_rows: int = 1024
) -> Tuple[Array, Array]:
    """Fused streaming (D^T D, D^T b) — one pass over the data.

    ``b`` may be (m,) — the classic lasso rhs — or (m, r) stacked
    right-hand sides (multi-probe serving); c comes back (n,) or (n, r).
    """
    m, n = D.shape
    acc = _acc_dtype(D.dtype)

    def body(carry, blk):
        G, c = carry
        Db, bb = blk
        Db = Db.astype(acc)
        return (G + t_dot(Db, Db), c + t_dot(Db, bb.astype(acc))), None

    init = (jnp.zeros((n, n), acc), jnp.zeros((n,) + b.shape[1:], acc))
    (G, c), _ = scan_row_blocks(body, init, (D, b), block_rows)
    return G, c


@partial(jax.jit, static_argnames=("block_rows",))
def gram_rhs_chunked(D: Array, b: Array, block_rows: int = 1024) -> Array:
    """Streaming D^T b over row blocks — the rhs-only companion of
    ``gram_chunked``. Unlike the dense ``gram_rhs`` it never materializes
    a full accumulation-precision copy of D: each block is up-cast alone,
    so live memory is one block (the warm-start ``transpose_d`` path of
    the iteration engine)."""
    m, n = D.shape
    acc = _acc_dtype(D.dtype)

    def body(c, blk):
        Db, bb = blk
        return c + t_dot(Db.astype(acc), bb.astype(acc)), None

    c0 = jnp.zeros((n,) + b.shape[1:], acc)
    c, _ = scan_row_blocks(body, c0, (D, b), block_rows)
    return c


def gram_factor(G: Array, ridge: float = 0.0) -> Array:
    """Cholesky factor of (G + ridge*I).

    The paper stores the explicit inverse W = (sum_i D_i^T D_i)^{-1}; we keep
    the Cholesky factorization instead (DESIGN.md §3) — same asymptotic cost,
    better conditioning. ``ridge`` carries the (rho/tau) term for ridge-
    regularized x-updates (SVM) and the +I block of the sparse stacking.
    """
    n = G.shape[0]
    A = G + ridge * jnp.eye(n, dtype=G.dtype) if ridge else G
    return jnp.linalg.cholesky(A)

def gram_solve(L: Array, rhs: Array) -> Array:
    """Solve (L L^T) x = rhs given the Cholesky factor L."""
    z = jax.scipy.linalg.solve_triangular(L, rhs, lower=True)
    return jax.scipy.linalg.solve_triangular(L.T, z, lower=False)
