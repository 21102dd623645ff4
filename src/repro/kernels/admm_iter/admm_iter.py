"""Pallas TPU kernel: FUSED unwrapped-ADMM iteration (§Perf, beyond-paper).

The paper's per-iteration body touches D twice when written as separate ops
(Dx, then D^T(y-lam)) and XLA's per-operand accounting cannot merge the
reads. This kernel streams each bm-row panel of D HBM->VMEM ONCE and does
everything with it while it is resident:

    Dx_b   = D_b x              (MXU; x stays in VMEM)
    y_b    = prox_f(Dx_b + lam_b)   (VPU, in-register Newton/bisection)
    lam_b' = lam_b + Dx_b - y_b
    d     += D_b^T (y_b - lam_b')   (MXU, f32 VMEM accumulator)
    w     += D_b^T (y_b - y_b_old)  (Boyd dual residual, same stream)
    v     += D_b^T lam_b'           (dual tolerance, same stream)

Per-iteration HBM traffic drops from 2 x bytes(D) + small to
1 x bytes(D) + small. The d/w/v accumulators are rows of one (8, n) output
block with a constant index_map, so they stay resident across the row grid
and are psum'd outside per paper Alg. 2 line 6.

Layout (kernels/tiling.py): the panel is read the way D sits in HBM —
(bm, n) row panels of a row-major D, or (n, bm) feature panels of D^T when
the runtime stores D column-major (it does for n = 307). The m-vectors
y, lam, aux, y', lam' travel as lane-dense (1, m) rows in (1, bm) blocks,
and both products with the panel produce or consume such rows, so no
vector is ever lane-padded. When bm does not divide m the last panel is
partial: its rows past m are masked to zero in-register, so they add
nothing to d, w or v and nothing outside the kernel copies D to pad it.

Every f32 product asks the MXU for full f32 precision (HIGHEST): the
default may run f32 operands as a single bf16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling
from repro.kernels.prox.prox import _prox_body

_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(x_ref, d_in_ref, y_ref, lam_ref, aux_ref, y_out_ref, lam_out_ref,
            dwv_ref, u_ref, *, kind: str, delta: float, param: float,
            m: int, block_m: int, row_axis: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dwv_ref[...] = jnp.zeros_like(dwv_ref)
        u_ref[...] = jnp.zeros_like(u_ref)

    Db = d_in_ref[...].astype(jnp.float32)       # (bm, n) or (n, bm)
    x = x_ref[...]                               # (1, n)
    y_old = y_ref[...]                           # (1, bm)
    lam = lam_ref[...]
    aux = aux_ref[...]
    if m % block_m:
        # partial last panel: whatever lies past row m is garbage (NaN in
        # interpret mode) — zero it before it can reach a product
        start = i * block_m
        ok = start + jax.lax.broadcasted_iota(jnp.int32, y_old.shape, 1) < m
        rows = start + jax.lax.broadcasted_iota(jnp.int32, Db.shape,
                                                row_axis) < m
        Db = jnp.where(rows, Db, 0.0)
        y_old = jnp.where(ok, y_old, 0.0)
        lam = jnp.where(ok, lam, 0.0)
        aux = jnp.where(ok, aux, 0.0)
    Dx = jax.lax.dot_general(                    # x D_b^T -> (1, bm)
        x, Db, (((1,), (1 - row_axis,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)
    z = Dx + lam
    y = _prox_body(kind, z, delta, aux, newton_iters=3, param=param)
    lam_new = lam + Dx - y
    y_out_ref[...] = y
    lam_out_ref[...] = lam_new

    # Three transpose reductions in the SAME row stream, as one (8, bm) x
    # panel product (rows 3-7 of u stay zero):
    #   d = D^T(y' - lam')  — next x-update RHS (Alg. 2 line 6)
    #   w = D^T(y' - y)     — Boyd dual residual s = tau ||w||; the y-space
    #                         difference is taken in-register BEFORE the
    #                         reduction, avoiding the catastrophic
    #                         cancellation of differencing two accumulated
    #                         D^T y vectors across iterations
    #   v = D^T lam'        — dual tolerance eps_dual needs tau ||v||
    u_ref[0:1, :] = y - lam_new
    u_ref[1:2, :] = y - y_old
    u_ref[2:3, :] = lam_new
    dwv_ref[...] += jax.lax.dot_general(
        u_ref[...], Db, (((1,), (row_axis,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def vmem_bytes(block_m: int, n: int, dtype, feature_major: bool) -> int:
    """Tiled VMEM working set of one grid step: the double-buffered panel,
    four f32 panels of in-register work (the upcast or masked panel and
    the operand splits of the two full-precision products), five
    double-buffered (1, bm) vector blocks, the (8, bm) u scratch, and x +
    the (8, n) accumulator. The v5e compiler needed 0.9-1.0x this at the
    star and Fig-1 shares (f32 and bf16, bm 512-5120)."""
    shape = (n, block_m) if feature_major else (block_m, n)
    panel = tiling.tiled_bytes(*shape, dtype)
    f32_panel = tiling.tiled_bytes(*shape, jnp.float32)
    vec = tiling.tiled_bytes(1, block_m, jnp.float32)
    small = 2 * tiling.tiled_bytes(8, n, jnp.float32)
    return 2 * panel + 4 * f32_panel + 10 * vec + 8 * block_m * 4 + small


def admm_iter_pallas(D, aux, y, lam, x, *, kind: str, delta: float,
                     block_m: int, feature_major: bool,
                     interpret: bool = False, param: float = 0.0):
    """D: (m, n); aux/y/lam: (m,); x: (n,). ``block_m`` is m itself or a
    multiple of 128. Returns (y', lam', d, w, v) with d = D^T(y'-lam'),
    w = D^T(y'-y) and v = D^T lam' accumulated in f32 in the same row
    stream. ``feature_major`` streams D^T (a bitcast of a column-major D)."""
    m, n = D.shape
    assert block_m == m or block_m % tiling.LANE == 0, (m, block_m)
    row_axis = 1 if feature_major else 0
    if feature_major:
        panels = D.T
        d_spec = pl.BlockSpec((n, block_m), lambda i: (0, i))
    else:
        panels = D
        d_spec = pl.BlockSpec((block_m, n), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, block_m), lambda i: (0, i))
    acc_spec = pl.BlockSpec((8, n), lambda i: (0, 0))
    row = lambda v: v.astype(jnp.float32).reshape(1, m)
    kernel = functools.partial(_kernel, kind=kind, delta=float(delta),
                               param=float(param), m=m, block_m=block_m,
                               row_axis=row_axis)
    y_new, lam_new, dwv = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(m, block_m),),
        in_specs=[pl.BlockSpec((1, n), lambda i: (0, 0)),   # x (replicated)
                  d_spec, vec_spec, vec_spec, vec_spec],    # D, y, lam, aux
        out_specs=[vec_spec, vec_spec, acc_spec],           # y', lam', d/w/v
        out_shape=[
            jax.ShapeDtypeStruct((1, m), jnp.float32),
            jax.ShapeDtypeStruct((1, m), jnp.float32),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((8, block_m), jnp.float32)],
        compiler_params=tiling.compiler_params(
            vmem_bytes(block_m, n, D.dtype, feature_major), ("arbitrary",)),
        interpret=interpret,
    )(x.astype(jnp.float32).reshape(1, n), panels, row(y), row(lam),
      row(aux))
    return y_new.reshape(m), lam_new.reshape(m), dwv[0], dwv[1], dwv[2]
