"""Jitted wrappers for the fused ADMM-iteration kernel. Nothing here copies
D: a ragged last row block is masked inside the kernel, and the panel
orientation follows D's layout in HBM (kernels/tiling.py)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import tiling
from repro.kernels.admm_iter.admm_iter import admm_iter_pallas


@functools.partial(
    jax.jit, static_argnames=("kind", "delta", "block_m", "interpret",
                              "param", "feature_major"))
def admm_iter_full(D, aux, y, lam, x, *, kind: str, delta: float,
                   block_m: int = 1024, interpret: bool = False,
                   param: float = 0.0, feature_major=None):
    """Fused iteration body returning (y', lam', d, w, v).

    d = D^T(y' - lam') feeds the next x-update (paper Alg. 2 line 6);
    w = D^T(y' - y) and v = D^T lam' feed Boyd's dual residual and
    tolerance without a second pass over D (the engine's one-pass
    telemetry — DESIGN.md §8). Differences are formed in-register before
    the reduction, so the residuals keep full f32 accuracy near
    convergence. ``block_m`` at or above m means one block of all rows;
    ``feature_major`` None follows the device's default layout of D.
    """
    m, n = D.shape
    if feature_major is None:
        feature_major = tiling.feature_major(m, n, D.dtype)
    return admm_iter_pallas(
        D, aux, y, lam, x, kind=kind, delta=delta,
        block_m=min(block_m, m), feature_major=feature_major,
        interpret=interpret, param=param)

