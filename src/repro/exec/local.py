"""LocalExecutor — device-resident data, the paper's single-node Alg. 1.

Replaces the two hand-rolled ``lax.while_loop`` drivers that used to
live in ``core/unwrapped.py`` (``_solve_dense`` / ``_solve_sparse``):
one jitted fused step per iteration, the loop itself in the shared
driver. Accepts node-stacked dense (N, m_i, n) arrays, a flat dense
(m, n) matrix (one node), or a flat :class:`~repro.data.sparse.BlockCSR`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import gram as gram_lib
from repro.core.unwrapped import node_shape
from repro.data.sparse import BlockCSR
from repro.engine.streaming import SweepResult
from repro.exec.base import SolveExecutor

Array = jax.Array


class LocalExecutor(SolveExecutor):
    name = "local"
    checkpoint_kind = "local_solve"
    kind_label = "local"

    def __init__(self, engine, D, aux: Optional[Array] = None,
                 gram_block_rows: Optional[int] = None):
        self.engine = engine
        self.sparse = isinstance(D, BlockCSR)
        if self.sparse:
            self.m, self.n = D.m, D.n
            self._stack = None               # y comes back as (1, m)
            self._Dflat = D
        else:
            N, mi, n = node_shape(D)
            self.m, self.n = N * mi, n
            self._stack = (N, mi)
            self._Dflat = D.reshape(self.m, n)
        self.acc = gram_lib._acc_dtype(D.dtype)
        self.ycols = getattr(engine.loss, "ycols", 1)
        self.backend = "sparse" if self.sparse else engine.resolve(D.dtype)
        self._aux = aux.reshape(self.m) if aux is not None else None
        self._gbr = gram_block_rows
        self._Dres = None
        self._y = None
        self._lam = None
        self._step = _fused_step(engine)

    def _yshape(self):
        return (self.m,) if self.ycols == 1 else (self.m, self.ycols)

    def setup(self, obs) -> Array:
        G, _ = self.engine.gram(self._Dflat, block_rows=self._gbr)
        self._Dres = self.engine.prepare(self._Dflat)
        return G

    def init(self, x0: Optional[Array]) -> Array:
        if x0 is None:
            self._y = jnp.zeros(self._yshape(), self.acc)
            self._lam = jnp.zeros(self._yshape(), self.acc)
            return self.zero_x()
        # warm start: y = D x0, lam = 0, d = D^T(y - lam) — one extra
        # setup-time pass (same semantics the jitted drivers had)
        x0 = jnp.asarray(x0)
        if self.sparse:
            from repro.kernels.spgram import ops as spgram_ops
            y = spgram_ops.matvec(self._Dflat, x0.astype(self.acc))
        else:
            y = self._Dflat.astype(self.acc) @ x0.astype(self.acc)
        self._y = y
        self._lam = jnp.zeros_like(y)
        return self.engine.transpose_d(self._Dflat, y, self._lam)

    def sweep(self, x: Array, k: int) -> SweepResult:
        self._y, self._lam, sw = self._step(
            self._Dres, self._aux, self._y, self._lam, x)
        return sw

    # -- checkpointing (driver-owned cadence) -------------------------------
    def state_arrays(self, k: int) -> dict:
        return {"y": self._y, "lam": self._lam}

    def restore_state(self, k: int, tree: dict) -> Array:
        self._y = jnp.asarray(tree["y"], self.acc)
        self._lam = jnp.asarray(tree["lam"], self.acc)
        return tree["d"]

    def final_iterates(self):
        if self._stack is None:
            return self._y[None], self._lam[None]
        N, mi = self._stack
        shape = (N, mi) + tuple(self._y.shape[1:])
        return self._y.reshape(shape), self._lam.reshape(shape)


def _fused_step(engine):
    """Jitted ``(D, aux, y, lam, x) -> (y', lam', SweepResult)``: the
    engine's fused body plus the stopping-rule scalars in one dispatch.
    Shared across LocalExecutor instances of the same engine config via
    jit's own cache (the engine is a frozen dataclass)."""
    loss = engine.loss

    @jax.jit
    def step(D, aux, y, lam, x):
        st = engine.iterate(D, aux, y, lam, x, want_dual=True)
        Dx = st.lam - lam + st.y
        sw = SweepResult(
            st.d, st.w, st.v,
            jnp.sum((st.lam - lam) ** 2), jnp.sum(Dx * Dx),
            jnp.sum(st.y * st.y), loss.value(Dx, aux))
        return st.y, st.lam, sw

    return step
