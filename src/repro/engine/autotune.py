"""Block-size autotuner for the iteration engine (DESIGN.md §8).

Model-driven, not search-driven: block shapes are picked from the VMEM /
cache budget math below and memoized per ``(m, n, dtype)`` so every caller
of the engine (solvers, service ingest, benchmarks) agrees on the shapes
without re-deriving them. The cache is a plain dict — inspectable in tests
and overridable by pinning an entry before the first resolve.

Budget math (see DESIGN.md §7 for the kernel-side derivation):

  * Pallas kernels: each kernel module states the tiled VMEM footprint of
    one grid step (``vmem_bytes``: lanes rounded to 128, sublanes to 8/16,
    double-buffered panels and the in-register f32 work), calibrated
    against what the v5e compiler asks for. The row block is the tallest
    multiple of 128 rows whose footprint fits ``VMEM_BUDGET``, preferring
    one that divides m so the last panel needs no mask. The kernels pass
    the footprint to the compiler as ``vmem_limit_bytes`` when it exceeds
    the 16 MiB default scoped limit.
  * chunked (lax.scan) backend: the same streaming shape on CPU/GPU; the
    budget stands in for the last-level-cache slice a core can keep hot,
    so one block of D plus its vectors stays resident between the Dx and
    D^T passes of the fused body.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp

from repro.kernels import tiling
from repro.kernels.admm_iter import admm_iter as admm_iter_kernel
from repro.kernels.gram import gram as gram_kernel

# Working set one Pallas kernel may plan for. The v5e compiler reports
# 128 MiB of VMEM per core; 32 MiB leaves room for the pipeline's own
# scratch and keeps blocks in the range where per-step overhead is small.
VMEM_BUDGET = 32 * 1024 * 1024
# Tallest Pallas row block: beyond this a taller panel only adds VMEM.
MAX_BLOCK_M = 4096
# Last-level cache slice assumed hot per chunked-backend stream on CPU/GPU.
CACHE_BUDGET = 2 * 1024 * 1024
# Default per-block DEVICE-memory budget for the out-of-core streaming
# path (DESIGN.md §9): covers the two in-flight D blocks (double buffer).
STREAM_BUDGET = 256 * 1024 * 1024

# (kind, m, n, dtype_name) -> chosen block size(s); pin to override.
CACHE: Dict[Tuple, Tuple] = {}


def _dsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def _clamp_multiple(value: int, mult: int, lo: int, hi: int) -> int:
    v = max(lo, min(hi, value))
    return max(mult, (v // mult) * mult)


def _row_cap(m: int, mult: int) -> int:
    """Never pick a row block taller than m rounded up to the tile size —
    taller blocks only add zero-padding work."""
    return -(-m // mult) * mult


def _pallas_block_m(m: int, footprint) -> int:
    """Tallest lane-aligned row block whose ``footprint(bm)`` fits the
    budget; all of m when that fits, else a divisor of m when one is at
    least half as tall (so the last panel needs no mask)."""
    lane = tiling.LANE
    bm = lane
    while bm + lane <= MAX_BLOCK_M and footprint(bm + lane) <= VMEM_BUDGET:
        bm += lane
    if m <= bm:
        return m
    for cand in range(bm, bm // 2 - 1, -lane):
        if m % cand == 0:
            return cand
    return bm


def iter_block_m(m: int, n: int, dtype) -> int:
    """Row-panel height for the fused Pallas iteration kernel."""
    key = ("iter", int(m), int(n), jnp.dtype(dtype).name)
    if key not in CACHE:
        fm = tiling.feature_major(m, n, dtype)
        CACHE[key] = (_pallas_block_m(
            m, lambda bm: admm_iter_kernel.vmem_bytes(bm, n, dtype, fm)),)
    return CACHE[key][0]


def gram_blocks(m: int, n: int, dtype, rhs: int = 0) -> Tuple[int, int]:
    """(block_m, block_n) for the Gram / fused Gram+RHS kernels.

    ``rhs`` is the stacked right-hand-side count (0 = Gram only); its
    (r, bm) B stream and resident C block are budgeted so wide multi-RHS
    ingests shrink bm instead of blowing the VMEM budget.
    """
    key = ("gram", int(m), int(n), jnp.dtype(dtype).name, int(rhs))
    if key not in CACHE:
        # Output tile first: all of n up to 512 (one tile, no partial
        # block), else 512 — bn >= 256 keeps the kernel MXU-bound
        # (arithmetic intensity ~ bn FLOP/byte).
        bn = n if n <= 512 else 512
        fm = tiling.feature_major(m, n, dtype)
        bm = _pallas_block_m(
            m, lambda bm: gram_kernel.vmem_bytes(bm, bn, dtype, fm, rhs=rhs))
        CACHE[key] = (bm, bn)
    return CACHE[key]


def streaming_block_rows(m: int, n: int, dtype,
                         budget_bytes: int = None) -> int:
    """Store block height for the out-of-core streaming path (DESIGN.md
    §9): the tallest block whose worst-case in-flight set fits the
    device-memory budget. At the default prefetch depth of 2 the
    pipeline can hold FOUR D blocks at once (one computing, two staged
    in the queue, one mid-``device_put`` in the producer), plus the
    per-row vector traffic."""
    budget = int(budget_bytes) if budget_bytes else STREAM_BUDGET
    key = ("stream", int(m), int(n), jnp.dtype(dtype).name, budget)
    if key not in CACHE:
        dsize = _dsize(dtype)
        rows = budget // max(1, 4 * n * dsize + 6 * 4)
        cap = _row_cap(m, 8)
        # prefer >= 128-row blocks, but honor a tight budget (huge n /
        # small budget) down to the 8-row tile floor rather than
        # silently overshooting the caller's device memory
        lo = min(128, cap) if rows >= 128 else 8
        CACHE[key] = (_clamp_multiple(rows, 8, lo, cap),)
    return CACHE[key][0]


def sparse_block_m(m: int, n: int, kp: int, dtype) -> int:
    """Row-block height for the padded block-CSR path (DESIGN.md §10).

    nnz-budgeted, not (m x n)-budgeted: a block's live bytes are its CSR
    slice ``bm * kp * (4 + dsize)`` plus the same nonzeros again in the
    local-CSC companion (padding slack rides in the 2x), plus the five
    (bm,) iterate vectors — so the block height scales with 1/density
    and the cache budget covers ~1/density more rows than the dense
    chunked stream. Tall floor (1024): the local CSC pads each column to
    the block's max per-column count, and that Poisson slack shrinks as
    blocks grow.
    """
    kp = max(int(kp), 1)
    key = ("sparse", int(m), int(n), kp, jnp.dtype(dtype).name)
    if key not in CACHE:
        dsize = _dsize(dtype)
        rows = CACHE_BUDGET // max(1, 2 * kp * (4 + dsize) + 20)
        cap = _row_cap(m, 8)
        CACHE[key] = (_clamp_multiple(rows, 8, min(1024, cap),
                                      min(16384, cap)),)
    return CACHE[key][0]


def chunked_block_rows(m: int, n: int, dtype) -> int:
    """Row-block length for the lax.scan streaming backend (CPU/GPU)."""
    key = ("chunked", int(m), int(n), jnp.dtype(dtype).name)
    if key not in CACHE:
        dsize = _dsize(dtype)
        rows = CACHE_BUDGET // max(1, n * dsize)
        cap = _row_cap(m, 8)
        CACHE[key] = (_clamp_multiple(rows, 8, min(128, cap), min(8192, cap)),)
    return CACHE[key][0]
