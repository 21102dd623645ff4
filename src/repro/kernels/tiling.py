"""TPU tiling facts shared by the dense kernels and their block autotuner.

Two things decide whether a kernel over D fits the chip without copying D:

  * **Which way D is laid out in HBM.** The TPU runtime picks the default
    layout of a 2-D array to waste the least tile padding. An f32 array is
    tiled (8, 128): the minor dimension is padded to 128 lanes and the
    major one to 8 sublanes. For D = (3,712,000, 307) row-major would pad
    307 -> 384 lanes (+25%), so the runtime stores it column-major: the
    bytes of D are those of a row-major D^T (n, m). A kernel that asks for
    (bm, n) row panels of such a D makes XLA transpose all of D first.
    :func:`feature_major` predicts the layout, and the kernels then read
    either D (row panels) or D^T (feature panels), whichever is the
    bitcast of what sits in HBM.
  * **What a block really occupies in VMEM.** A (r, c) block occupies
    round_up(r, sublane) x round_up(c, 128) elements; :func:`tiled_bytes`
    is the footprint the autotuner budgets.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# The compiler's default scoped-VMEM limit for one kernel. Kernels whose
# working set needs more ask for it through ``vmem_limit_bytes``.
DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024


def sublane(dtype) -> int:
    """Second-minor tile of a 2-D array (f32: 8, bf16: 16, int8: 32)."""
    return {4: 8, 2: 16, 1: 32}.get(jnp.dtype(dtype).itemsize, 8)


def round_up(x: int, mult: int) -> int:
    return -(-int(x) // mult) * mult


def tiled_bytes(rows: int, cols: int, dtype) -> int:
    """Bytes a (rows, cols) block occupies once tiled on the TPU."""
    return (round_up(rows, sublane(dtype)) * round_up(cols, LANE)
            * jnp.dtype(dtype).itemsize)


def _padding_rule(m: int, n: int, dtype) -> bool:
    sub = sublane(dtype)
    rows_major = round_up(m, sub) * round_up(n, LANE)
    cols_major = round_up(n, sub) * round_up(m, LANE)
    return cols_major < rows_major


def feature_major(m: int, n: int, dtype) -> bool:
    """True when the device stores an (m, n) array column-major, so that
    D^T (n, m) — not D — is the free, row-major view of its bytes.

    On a TPU this asks the runtime for its default layout of the shape.
    Elsewhere (interpret mode on the CPU, or a compile for a described
    chip) it applies the runtime's padding rule: column-major exactly when
    that pads less. tests/test_tpu_compile.py holds the rule to the v5e
    runtime's answer at the shapes the repository runs.
    """
    if jax.default_backend() != "tpu":
        return _padding_rule(m, n, dtype)
    dev = jax.devices()[0]
    layout = str(dev.client.get_default_layout(jnp.dtype(dtype), (m, n),
                                               dev))
    minor_to_major = layout.strip("{}").split(":")[0]
    if minor_to_major not in ("0,1", "1,0"):
        raise ValueError(f"unexpected TPU layout {layout} for {(m, n)}")
    return minor_to_major == "0,1"


def compiler_params(vmem_bytes: int, semantics):
    """Mosaic parameters: the grid's dimension semantics, plus a scoped
    VMEM limit above the default when the working set needs it (with 25%
    headroom for Mosaic's own scratch)."""
    limit = None
    if vmem_bytes > DEFAULT_SCOPED_VMEM * 3 // 4:
        limit = round_up(vmem_bytes * 5 // 4, 1 << 20)
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics),
                                vmem_limit_bytes=limit)
