"""Pallas TPU kernel for the transpose reduction G = D^T D (paper §4/§5).

TPU-native design (DESIGN.md §7) — this is a *streaming* Gram accumulation,
not a CUDA tile port:

  * D is tall (m >> n): the (bn, bn) output tile lives resident in VMEM
    while row panels of D stream HBM->VMEM. Arithmetic intensity per output
    tile approaches 2*bm*bn_i*bn_j / (bm*(bn_i+bn_j)) ~ bn FLOP/byte, so for
    bn >= 256 the kernel is MXU-bound, exactly like the paper's m >> n
    regime wants.
  * Grid = (n/bn_i, n/bn_j, m/bm) with the *reduction innermost*: TPU grids
    execute sequentially with the last dimension fastest, so the output
    BlockSpec (constant in k) keeps one accumulator tile in VMEM across the
    entire row stream — no HBM round-trips for partials.
  * Symmetry: G is symmetric, so blocks with i > j skip the dot (the mirror
    is reconstructed in ops.py) — a ~2x FLOP cut the straight jnp lowering
    does not get.
  * Accumulation is always f32 (bf16 inputs are up-cast in-register via
    preferred_element_type), because the row stream is a very long
    reduction; f32 products ask for full f32 MXU precision (HIGHEST).
  * Layout (kernels/tiling.py): panels are read the way D sits in HBM —
    (bm, bn) pieces of a row-major D, or (bn, bm) pieces of D^T when the
    runtime stores D column-major. Nothing pads D. A partial block along n
    needs no mask: its garbage lands only in rows and columns of G past n,
    which the output write drops. A partial last block along m is masked
    to zero in-register.

Block shapes: bn is n itself or a multiple of 128 (it is the lane width of
the output tile), bm is m itself or a multiple of 128. The tiled VMEM
footprint is :func:`vmem_bytes`; the engine's autotuner sizes bm from it
and the kernel raises the compiler's scoped-VMEM limit when it needs to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


def _dot(a, b, contract, interpret: bool = False):
    if interpret:
        # the CPU's dot thunk has no bf16 x bf16 -> f32 (exact either way)
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    prec = (jax.lax.Precision.HIGHEST
            if a.dtype == jnp.float32 and b.dtype == jnp.float32 else None)
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


def _row_mask(refs_axes, k, *, m: int, block_m: int):
    """Load each (ref, row axis) pair, zeroing rows past m when the last
    block along m is partial."""
    vals = [r[...] for r, _ in refs_axes]
    if m % block_m == 0:
        return vals
    start = k * block_m
    out = []
    for v, (_, ax) in zip(vals, refs_axes):
        ok = start + jax.lax.broadcasted_iota(jnp.int32, v.shape, ax) < m
        out.append(jnp.where(ok, v, jnp.zeros((), v.dtype)))
    return out


def _gram_kernel(d_i_ref, d_j_ref, out_ref, *, symmetric_skip: bool,
                 m: int, block_m: int, row_axis: int, interpret: bool):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def _accum():
        a, b = _row_mask([(d_i_ref, row_axis), (d_j_ref, row_axis)], k,
                         m=m, block_m=block_m)
        out_ref[...] += _dot(a, b, ((row_axis,), (row_axis,)),  # D_i^T D_j
                             interpret)

    if symmetric_skip:
        pl.when(i <= j)(_accum)
    else:
        _accum()


def _gram_rhs_kernel(d_i_ref, d_j_ref, b_ref, g_ref, c_ref, *,
                     symmetric_skip: bool, m: int, block_m: int,
                     row_axis: int, interpret: bool):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init_g():
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when((k == 0) & (j == 0))
    def _init_c():
        c_ref[...] = jnp.zeros_like(c_ref)

    def _accum_g():
        a, b = _row_mask([(d_i_ref, row_axis), (d_j_ref, row_axis)], k,
                         m=m, block_m=block_m)
        g_ref[...] += _dot(a, b, ((row_axis,), (row_axis,)), interpret)

    if symmetric_skip:
        pl.when(i <= j)(_accum_g)
    else:
        _accum_g()

    # c_i += B^T D_i, once per (i, k) — the j == 0 sweep reuses the D_i
    # panel already resident in VMEM, so the RHS costs no extra reads of D
    # (and B's own index_map parks on block 0 for j > 0, so B streams only
    # on the sweeps that consume it). B stays f32 even when D streams as
    # bf16 (the rhs is tiny; quantizing it would cost accuracy for no
    # bandwidth win), hence the in-register upcast of the D panel for this
    # dot only.
    @pl.when(j == 0)
    def _accum_c():
        a, bt = _row_mask([(d_i_ref, row_axis), (b_ref, 1)], k,
                          m=m, block_m=block_m)
        c_ref[...] += _dot(bt, a.astype(jnp.float32), ((1,), (row_axis,)))


def vmem_bytes(block_m: int, block_n: int, dtype, feature_major: bool,
               rhs: int = 0) -> int:
    """Tiled VMEM working set of one grid step: two double-buffered D
    panels, four f32 panels of in-register work (masked or upcast panels
    and the operand splits of the full-precision product), the
    double-buffered (bn, bn) G tile, and for the fused RHS the (r, bm) B
    stream and the (r, bn) C tile. The v5e compiler needed 0.6-1.0x this
    at the star and Fig-1 shares."""
    shape = (block_n, block_m) if feature_major else (block_m, block_n)
    panel = tiling.tiled_bytes(*shape, dtype)
    f32_panel = tiling.tiled_bytes(*shape, jnp.float32)
    total = 4 * panel + 4 * f32_panel + 2 * tiling.tiled_bytes(
        block_n, block_n, jnp.float32)
    if rhs:
        total += (3 * tiling.tiled_bytes(rhs, block_m, jnp.float32)
                  + 2 * tiling.tiled_bytes(rhs, block_n, jnp.float32))
    return total


def _specs(m, n, block_m, block_n, feature_major):
    """(panel operand view, BlockSpec for stripe i, for stripe j, row axis)."""
    if feature_major:
        return (lambda D: D.T,
                pl.BlockSpec((block_n, block_m), lambda i, j, k: (i, k)),
                pl.BlockSpec((block_n, block_m), lambda i, j, k: (j, k)), 1)
    return (lambda D: D,
            pl.BlockSpec((block_m, block_n), lambda i, j, k: (k, i)),
            pl.BlockSpec((block_m, block_n), lambda i, j, k: (k, j)), 0)


def gram_rhs_pallas(
    D: jax.Array,
    Bt: jax.Array,
    *,
    block_m: int,
    block_n: int,
    feature_major: bool,
    symmetric_skip: bool = True,
    interpret: bool = False,
):
    """(G, C^T) = (D^T D, B^T D) in ONE row stream over D (paper §4 setup).

    D: (m, n); Bt: (r, m) stacked right-hand sides as lane-dense rows.
    The C accumulator block (r, block_n) has a j/k-constant index_map so it
    stays VMEM-resident across the whole (j, k) sweep of each stripe i,
    exactly like the G tiles — the RHS rides the same HBM pass for free.
    """
    m, n = D.shape
    r = Bt.shape[0]
    view, spec_i, spec_j, row_axis = _specs(m, n, block_m, block_n,
                                            feature_major)
    kernel = functools.partial(_gram_rhs_kernel,
                               symmetric_skip=symmetric_skip, m=m,
                               block_m=block_m, row_axis=row_axis,
                               interpret=interpret)
    nb = pl.cdiv(n, block_n)
    return pl.pallas_call(
        kernel,
        grid=(nb, nb, pl.cdiv(m, block_m)),
        in_specs=[
            spec_i, spec_j,
            # B is consumed only on the j == 0 sweeps; park its index on
            # block 0 for j > 0 so the revisit skips the DMA instead of
            # re-streaming the whole rhs once per column stripe.
            pl.BlockSpec((r, block_m),
                         lambda i, j, k: (0, jnp.where(j == 0, k, 0))),
        ],
        out_specs=[
            pl.BlockSpec((block_n, block_n), lambda i, j, k: (i, j)),
            pl.BlockSpec((r, block_n), lambda i, j, k: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, n), jnp.float32),
            jax.ShapeDtypeStruct((r, n), jnp.float32),
        ],
        compiler_params=tiling.compiler_params(
            vmem_bytes(block_m, block_n, D.dtype, feature_major, rhs=r),
            ("arbitrary",) * 3),
        interpret=interpret,
    )(view(D), view(D), Bt)


def gram_pallas(
    D: jax.Array,
    *,
    block_m: int,
    block_n: int,
    feature_major: bool,
    symmetric_skip: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """G = D^T D via Pallas. D: (m, n); returns (n, n) f32.

    When ``symmetric_skip`` the strictly-lower blocks are left zero and
    ops.py mirrors the upper triangle.
    """
    m, n = D.shape
    view, spec_i, spec_j, row_axis = _specs(m, n, block_m, block_n,
                                            feature_major)
    kernel = functools.partial(_gram_kernel, symmetric_skip=symmetric_skip,
                               m=m, block_m=block_m, row_axis=row_axis,
                               interpret=interpret)
    nb = pl.cdiv(n, block_n)
    return pl.pallas_call(
        kernel,
        grid=(nb, nb, pl.cdiv(m, block_m)),
        in_specs=[spec_i, spec_j],
        out_specs=pl.BlockSpec((block_n, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        compiler_params=tiling.compiler_params(
            vmem_bytes(block_m, block_n, D.dtype, feature_major),
            ("arbitrary",) * 3),
        interpret=interpret,
    )(view(D), view(D))
