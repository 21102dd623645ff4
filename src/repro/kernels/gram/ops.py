"""Jitted public wrappers for the Gram kernels: block clamping, symmetry
restore, and the fused Gram+RHS kernel (``gram_and_rhs`` — D^T D and D^T B
accumulated in the same row stream; the engine's setup path). Nothing here
copies D: ragged blocks are handled inside the kernels, and the panel
orientation follows D's layout in HBM (kernels/tiling.py)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import tiling
from repro.kernels.gram.gram import gram_pallas, gram_rhs_pallas


def _blocks(D, block_m, block_n, feature_major):
    """Clamp blocks to the array (a block at or above a dimension covers
    all of it) and resolve the panel orientation."""
    m, n = D.shape
    if feature_major is None:
        feature_major = tiling.feature_major(m, n, D.dtype)
    return min(block_m, m), min(block_n, n), feature_major


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "symmetric_skip",
                              "interpret", "feature_major"))
def gram(
    D: jax.Array,
    *,
    block_m: int = 512,
    block_n: int = 256,
    symmetric_skip: bool = True,
    interpret: bool = False,
    feature_major=None,
) -> jax.Array:
    """D^T D, f32, any (m, n)."""
    bm, bn, fm = _blocks(D, block_m, block_n, feature_major)
    G = gram_pallas(D, block_m=bm, block_n=bn, feature_major=fm,
                    symmetric_skip=symmetric_skip, interpret=interpret)
    if symmetric_skip:
        G = _mirror_upper(G, bn)
    return G


def _mirror_upper(G: jax.Array, block_n: int) -> jax.Array:
    """Rebuild the strictly-lower blocks skipped by ``symmetric_skip``."""
    bi = jnp.arange(G.shape[0]) // block_n
    upper = bi[:, None] <= bi[None, :]             # block-upper mask
    return jnp.where(upper, G, G.T)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "interpret",
                              "feature_major"))
def gram_and_rhs(
    D: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 512,
    block_n: int = 256,
    interpret: bool = False,
    feature_major=None,
):
    """Fused (D^T D, D^T b) — ONE row stream over D, any (m, n).

    ``b`` may be (m,) — the classic lasso rhs — or (m, r) stacked
    right-hand sides (multi-probe serving); c comes back (n,) or (n, r).
    The right-hand sides enter as lane-dense (r, m) rows: for r = 1 that
    is a bitcast of b, for r > 1 a transpose of the (small) B.
    """
    squeeze = b.ndim == 1
    Bt = (b[None] if squeeze else b.T).astype(jnp.float32)
    bm, bn, fm = _blocks(D, block_m, block_n, feature_major)
    G, Ct = gram_rhs_pallas(D, Bt, block_m=bm, block_n=bn, feature_major=fm,
                            symmetric_skip=True, interpret=interpret)
    G = _mirror_upper(G, bn)
    return G, (Ct[0] if squeeze else Ct.T)
