"""Unwrapped ADMM with transpose reduction — paper Algorithms 1 & 2.

Solves ``min_x rho/2 ||x||^2 + f(Dx)`` (rho=0 for plain ``min f(Dx)``) by
splitting ``y = Dx``:

    x^{k+1} = argmin_x rho/2||x||^2 + tau/2 ||Dx - y^k + lam^k||^2
            = (D^T D + (rho/tau) I)^{-1} D^T (y^k - lam^k)          (global LS)
    y^{k+1} = prox_f(D x^{k+1} + lam^k, 1/tau)                      (separable)
    lam^{k+1} = lam^k + D x^{k+1} - y^{k+1}

The x-update is the transpose-reduction step: only ``d = sum_i D_i^T(y_i -
lam_i)`` crosses the network (an n-vector), and the n x n Gram factor is
computed once at setup from ``sum_i D_i^T D_i`` (paper Alg. 2 lines 2-3).

The per-iteration body lives in :mod:`repro.engine` (DESIGN.md §8): the
drivers here carry ``(y, lam, d = D^T(y-lam), x)`` and call
``engine.iterate`` once per iteration — ONE streaming pass over D instead
of the textbook two (d-reduction pass + Dx pass). The engine accumulates
the stopping-rule reductions w = D^T(y^{k+1}-y^k) and v = D^T lam^{k+1}
in the same stream, and the remaining residual quantities are elementwise:

    Dx  = lam^{k+1} - lam^k + y^{k+1}
    r   = ||Dx - y^{k+1}|| = ||lam^{k+1} - lam^k||
    s   = tau ||w||,   eps_dual ~ tau ||v||

Data layout: ``D`` is ``(N, m_i, n)`` — N nodes, m_i rows each. N=1 recovers
the single-node Alg. 1, and a flat ``(m, n)`` matrix counts as one node (on
a TPU the flat form is the one whose default layout the kernels read
without a copy — kernels/tiling.py). This module is the *reference
semantics*; the multi-device version (``repro.core.distributed``) runs the same engine body
per shard under ``shard_map`` with a psum where this module sums over rows.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import gram as gram_lib
from repro.core.prox import ProxLoss
from repro.data.sparse import BlockCSR

Array = jax.Array


def node_shape(D) -> Tuple[int, int, int]:
    """(N, m_i, n) of node-stacked data; a flat (m, n) matrix is one node."""
    return (1,) + tuple(D.shape) if D.ndim == 2 else tuple(D.shape)


class ADMMHistory(NamedTuple):
    """Per-iteration telemetry (paper Fig. 2 curves + Theorems 1/2 checks)."""

    objective: Array      # f(Dx^k) (+ rho/2||x||^2)
    primal_res: Array     # ||D x^k - y^k||
    dual_res: Array       # tau * ||D^T (y^k - y^{k-1})||  (Boyd dual residual)
    grad_sq: Array        # ||D^T grad f(D x^k)||^2 if f smooth else nan
    converged_at: Array   # first iteration k meeting Boyd's stopping rule


class ADMMResult(NamedTuple):
    x: Array
    y: Array
    lam: Array
    iters: Array                 # iterations actually informative (stop point)
    history: Optional[ADMMHistory]


@dataclasses.dataclass(frozen=True)
class UnwrappedADMM:
    """Configured solver. ``loss`` acts on y with per-row aux (labels / b).

    ``backend`` / ``residency`` select the engine hot path (DESIGN.md §8):
    "auto" picks the fused Pallas kernel on TPU and the chunked lax.scan
    stream elsewhere; ``residency="bf16"`` keeps the iteration copy of D in
    bf16 (f32 accumulation) to halve the per-iteration HBM bytes again.
    """

    loss: ProxLoss
    tau: float = 1.0
    rho: float = 0.0              # ridge g(x) = rho/2 ||x||^2 (SVM: rho=1)
    eps_rel: float = 1e-3         # paper §9 stopping constants
    eps_abs: float = 1e-6
    gram_block_rows: Optional[int] = None   # None -> engine autotune;
                                            # set to bound setup memory
    backend: str = "auto"         # engine backend (reference | chunked |
                                  # pallas | pallas_interpret | auto)
    residency: Optional[str] = None   # None | "bf16" iteration data dtype

    @property
    def engine(self):
        # Imported lazily: repro.engine imports repro.core.gram, whose
        # package __init__ imports this module — a module-level import
        # here would be circular.
        from repro.engine import IterationEngine
        return IterationEngine(loss=self.loss, tau=self.tau,
                               backend=self.backend,
                               residency=self.residency)

    # -- setup (Alg. 2 lines 2-3): one Gram all-reduce + one factorization --
    def setup(self, D: Array) -> Array:
        N, mi, n = node_shape(D)
        G, _ = self.engine.gram(D.reshape(N * mi, n),
                                block_rows=self.gram_block_rows)
        ridge = self.rho / self.tau
        return gram_lib.gram_factor(G, ridge=ridge)

    # -- one iteration (Alg. 2 lines 5-8), reference-shaped API -------------
    def step(self, L: Array, D: Array, aux: Optional[Array], y: Array,
             lam: Array):
        """Single step on node-stacked arrays — the oracle surface kernel
        tests compare against; the drivers below inline the same engine
        body around a carried ``d`` instead of recomputing it."""
        N, mi, n = node_shape(D)
        eng = self.engine
        Dflat = D.reshape(N * mi, n)
        d = eng.transpose_d(Dflat, y.reshape(-1), lam.reshape(-1))
        x = gram_lib.gram_solve(L, d)
        st = eng.iterate(Dflat, aux.reshape(-1) if aux is not None else None,
                         y.reshape(-1), lam.reshape(-1), x, want_dual=False)
        Dx = st.lam - lam.reshape(-1) + st.y
        return (x, Dx.reshape(N, mi), st.y.reshape(N, mi),
                st.lam.reshape(N, mi))

    def _objective(self, x, Dx, aux_flat):
        obj = self.loss.value(Dx, aux_flat)
        if self.rho:
            obj = obj + 0.5 * self.rho * jnp.sum(x * x)
        return obj

    def _residuals_tolerances(self, st, lam, m, n):
        """All of Boyd's stopping quantities from the engine's same-pass
        reductions — no extra pass over D (module docstring identities)."""
        Dx = st.lam - lam + st.y
        r = jnp.linalg.norm(st.lam - lam)                 # ||Dx - y_new||
        s = self.tau * jnp.linalg.norm(st.w)
        eps_pri = jnp.sqrt(m) * self.eps_abs + self.eps_rel * jnp.maximum(
            jnp.linalg.norm(Dx), jnp.linalg.norm(st.y))
        eps_dual = jnp.sqrt(n * 1.0) * self.eps_abs + (
            self.eps_rel * self.tau * jnp.linalg.norm(st.v))
        return Dx, r, s, eps_pri, eps_dual

    def _init_state(self, Dflat, x0, m, n, acc):
        if x0 is not None:
            # Warm start (the serving layer's repeated solves): seed the
            # split variable at y = D x0, so the first x-update returns
            # (D^T D + rI)^{-1} D^T D x0 — exactly x0 when rho = 0. One
            # extra setup-time pass builds the carried reduction.
            y = Dflat.astype(acc) @ x0.astype(acc)
            lam = jnp.zeros((m,), acc)
            d = self.engine.transpose_d(Dflat, y, lam)
        else:
            y = jnp.zeros((m,), acc)
            lam = jnp.zeros((m,), acc)
            d = jnp.zeros((n,), acc)
        return y, lam, d

    # -- fixed-iteration driver with full telemetry (lax.scan) --
    def run(
        self,
        D,
        aux: Optional[Array],
        iters: int,
        x0: Optional[Array] = None,
        record: bool = True,
        obs=None,
    ) -> ADMMResult:
        """``D`` is node-stacked dense (N, m_i, n) or a flat
        :class:`BlockCSR` (sparse solves return y/lam as (1, m)).

        ``obs`` (:class:`repro.obs.Observability`) is handled entirely
        OUTSIDE the jitted driver: one span around the dispatch, then the
        recorded :class:`ADMMHistory` is streamed to the telemetry sink
        post-hoc — the scan body never sees a host callback."""
        if obs is None or not obs.enabled:
            if isinstance(D, BlockCSR):
                return self._run_sparse(D, aux, iters, x0=x0, record=record)
            return self._run_dense(D, aux, iters, x0, record)
        with obs.span("admm_run", iters=iters, sparse=isinstance(D, BlockCSR)):
            if isinstance(D, BlockCSR):
                res = self._run_sparse(D, aux, iters, x0=x0, record=record)
            else:
                res = self._run_dense(D, aux, iters, x0, record)
            jax.block_until_ready(res.x)
        obs.inc("admm.runs")
        if res.history is not None:
            obs.write_history(res.history, tau=self.tau, rho=self.rho)
        return res

    @partial(jax.jit, static_argnames=("self", "iters", "record"))
    def _run_dense(
        self,
        D: Array,
        aux: Optional[Array],
        iters: int,
        x0: Optional[Array] = None,
        record: bool = True,
    ) -> ADMMResult:
        N, mi, n = node_shape(D)
        m = N * mi
        acc = gram_lib._acc_dtype(D.dtype)
        eng = self.engine
        Dflat = D.reshape(m, n)
        L = self.setup(D)
        Dres = eng.prepare(Dflat)
        aux_f = aux.reshape(m) if aux is not None else None
        y, lam, d = self._init_state(Dflat, x0, m, n, acc)

        def body(carry, _):
            y, lam, d, _, k_conv, k = carry
            x = gram_lib.gram_solve(L, d)
            st = eng.iterate(Dres, aux_f, y, lam, x, want_dual=True)
            Dx, r, s, eps_pri, eps_dual = self._residuals_tolerances(
                st, lam, m, n)
            done = (r <= eps_pri) & (s <= eps_dual)
            k_conv = jnp.where((k_conv < 0) & done, k, k_conv)
            obj = self._objective(x, Dx, aux_f)
            if record and self.loss.grad is not None:
                # Theorem 2 diagnostic: ||d/dx f(Dx^k)||^2 = ||D^T grad f||^2.
                # The one telemetry quantity that is not derivable from the
                # carried n-vectors; costs an extra pass, so it only runs on
                # the recording driver (solve(), the hot path, never pays).
                # Routed through the engine's streaming rmatvec: the dense
                # ``Dflat.astype(acc).T @ g`` would materialize a full
                # accumulation-precision copy of D every iteration on
                # streaming-class backends.
                g = self.loss.grad(Dx, aux_f)
                gsq = jnp.sum(eng.rmatvec(Dflat, g) ** 2)
            else:
                gsq = jnp.asarray(jnp.nan, acc)
            hist = (obj, r, s, gsq)
            return (st.y, st.lam, st.d, x, k_conv, k + 1), hist

        init = (y, lam, d, jnp.zeros((n,), acc),
                jnp.asarray(-1, jnp.int32), jnp.asarray(0, jnp.int32))
        (y, lam, d, x, k_conv, _), hist = jax.lax.scan(
            body, init, None, length=iters)
        objs, rs, ss, gsqs = hist
        history = (
            ADMMHistory(objs, rs, ss, gsqs, k_conv) if record else None
        )
        iters_used = jnp.where(k_conv >= 0, k_conv + 1, iters)
        return ADMMResult(x, y.reshape(N, mi), lam.reshape(N, mi),
                          iters_used, history)

    # -- early-stopping driver, deployment path -----------------------------
    def solve(
        self, D, aux: Optional[Array], max_iters: int = 500,
        x0: Optional[Array] = None, record: bool = False,
        reg=None, checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0, resume: bool = False, obs=None,
    ) -> ADMMResult:
        """``D`` is node-stacked dense (N, m_i, n) or a flat
        :class:`BlockCSR`. Runs through the shared executor driver
        (DESIGN.md §14) on a :class:`repro.exec.LocalExecutor` — the
        same stopping rule / warm start / checkpoint code path every
        other topology uses. ``reg`` (a :class:`repro.exec.Regularizer`)
        switches the x-update to the composite prox-gradient."""
        from repro.exec import LocalExecutor, solve_with_executor
        ex = LocalExecutor(self.engine, D, aux=aux,
                           gram_block_rows=self.gram_block_rows)

        res = solve_with_executor(
            ex, loss=self.loss, tau=self.tau, rho=self.rho,
            eps_rel=self.eps_rel, eps_abs=self.eps_abs,
            max_iters=max_iters, x0=x0, record=record, reg=reg,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, resume=resume, obs=obs)
        if obs is not None and obs.enabled:
            jax.block_until_ready(res.x)
            obs.inc("admm.solves")
            obs.record(event="solve_done", iters=int(res.iters),
                       tau=self.tau, rho=self.rho)
        return res

    # -- sparse drivers: same semantics over a BlockCSR ---------------------
    # The Gram setup is a HOST pass for sparse data (the O(nnz) gram has
    # no fast XLA lowering — kernels/spgram/ops.py), so these drivers
    # factor L outside the jitted loop and hand it in; the per-iteration
    # body, stopping rule, telemetry and warm-start semantics are the
    # dense drivers' own, through the engine's sparse backend.

    def _sparse_setup(self, D: BlockCSR) -> Array:
        G, _ = self.engine.gram(D)
        return gram_lib.gram_factor(G, ridge=self.rho / self.tau)

    def _sparse_init(self, D: BlockCSR, x0, m, n, acc):
        from repro.kernels.spgram import ops as spgram_ops
        if x0 is not None:
            y = spgram_ops.matvec(D, x0.astype(acc))
            lam = jnp.zeros((m,), acc)
            d = self.engine.transpose_d(D, y, lam)
        else:
            y = jnp.zeros((m,), acc)
            lam = jnp.zeros((m,), acc)
            d = jnp.zeros((n,), acc)
        return y, lam, d

    def _run_sparse(self, D: BlockCSR, aux, iters, x0=None, record=True):
        L = self._sparse_setup(D)
        return self._run_sparse_jit(D, aux, L, iters, x0, record)

    @partial(jax.jit, static_argnames=("self", "iters", "record"))
    def _run_sparse_jit(self, D: BlockCSR, aux, L, iters, x0, record):
        m, n = D.m, D.n
        acc = gram_lib._acc_dtype(D.dtype)
        eng = self.engine
        Dres = eng.prepare(D)
        aux_f = aux.reshape(m) if aux is not None else None
        y, lam, d = self._sparse_init(D, x0, m, n, acc)

        def body(carry, _):
            y, lam, d, _, k_conv, k = carry
            x = gram_lib.gram_solve(L, d)
            st = eng.iterate(Dres, aux_f, y, lam, x, want_dual=True)
            Dx, r, s, eps_pri, eps_dual = self._residuals_tolerances(
                st, lam, m, n)
            done = (r <= eps_pri) & (s <= eps_dual)
            k_conv = jnp.where((k_conv < 0) & done, k, k_conv)
            obj = self._objective(x, Dx, aux_f)
            if record and self.loss.grad is not None:
                g = self.loss.grad(Dx, aux_f)
                gsq = jnp.sum(eng.rmatvec(D, g) ** 2)
            else:
                gsq = jnp.asarray(jnp.nan, acc)
            hist = (obj, r, s, gsq)
            return (st.y, st.lam, st.d, x, k_conv, k + 1), hist

        init = (y, lam, d, jnp.zeros((n,), acc),
                jnp.asarray(-1, jnp.int32), jnp.asarray(0, jnp.int32))
        (y, lam, d, x, k_conv, _), hist = jax.lax.scan(
            body, init, None, length=iters)
        objs, rs, ss, gsqs = hist
        history = (
            ADMMHistory(objs, rs, ss, gsqs, k_conv) if record else None
        )
        iters_used = jnp.where(k_conv >= 0, k_conv + 1, iters)
        return ADMMResult(x, y[None], lam[None], iters_used, history)

    # -- out-of-core driver: D streams from a host/disk block store --------
    def solve_streaming(
        self, store, max_iters: int = 500, x0: Optional[Array] = None,
        record: bool = False, overlap: bool = True, prefetch: int = 2,
        device_dtype: Optional[str] = None,
        checkpoint_dir: Optional[str] = None, checkpoint_every: int = 0,
        resume: bool = False, obs=None,
    ) -> ADMMResult:
        """``solve`` for data that does not fit device memory: ``store``
        is a :class:`repro.data.store.ShardedMatrixStore` (host RAM or
        memory-mapped) and every pass — Gram setup, each iteration's
        fused body — streams one row block at a time with double-buffered
        host→device transfers (DESIGN.md §9). The m-sized iterates
        (y, lam) persist to host per block, so device memory is bounded
        by one block regardless of m. Same stopping rule and warm-start
        semantics as ``solve``; ``overlap=False`` degrades to the naive
        synchronous transfer loop (the benchmark baseline).

        ``checkpoint_dir`` + ``checkpoint_every=K`` persist the loop
        state (x, y, lam, d, iter) every K iterations through
        :class:`repro.checkpoint.manager.CheckpointManager`;
        ``resume=True`` restores the newest step and continues
        bitwise-compatibly after a kill (the checkpoint refuses to
        resume against a store with a different content fingerprint).
        """
        from repro.engine.streaming import solve_streaming as _solve
        return _solve(self, store, max_iters=max_iters, x0=x0,
                      record=record, overlap=overlap, prefetch=prefetch,
                      device_dtype=device_dtype,
                      checkpoint_dir=checkpoint_dir,
                      checkpoint_every=checkpoint_every, resume=resume,
                      obs=obs)


# ---------------------------------------------------------------------------
# Sparse stacking helpers (paper §7): D_hat = [I; D]
# ---------------------------------------------------------------------------

def sparse_unwrapped_lasso_matrices(D: Array, b: Array, mu: float):
    """Build the stacked system for sparse fitting min mu|x| + f(Dx).

    Returns (D_hat, labels_hat) where D_hat = [I; D] with the identity block
    assigned to a dedicated "node" N+1 (paper eq. 15) and a StackedProx-ready
    layout. For the (N, m_i, n) layout we return flat 2-D arrays; callers
    embed the identity rows on the central node.
    """
    N, mi, n = D.shape
    Dflat = D.reshape(N * mi, n)
    D_hat = jnp.concatenate([jnp.eye(n, dtype=D.dtype), Dflat], axis=0)
    return D_hat


def flat_to_nodes(D2: Array, N: int) -> Array:
    """(m, n) -> (N, m/N, n); m must divide evenly (pad upstream)."""
    m, n = D2.shape
    assert m % N == 0, f"rows {m} not divisible by {N} nodes"
    return D2.reshape(N, m // N, n)
