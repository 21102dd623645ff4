"""Multi-device transpose-reduction ADMM (paper Alg. 2) under shard_map.

Mapping of the paper's cluster roles onto a TPU mesh (DESIGN.md §3):

  * "node i" = a mesh position along the data axes ('pod','data'). D rows are
    sharded there; y_i, lam_i live entirely on their shard and never move.
  * "send D_i^T(y_i-lam_i) to central server" = one psum of an n-vector per
    iteration (the paper's O(n)-per-node communication claim, C5).
  * "central node computes W = (sum_i W_i)^{-1}" = the n x n Gram psum at
    setup, then a *replicated* Cholesky on every device — on TPU a redundant
    n x n factorization is cheaper than a broadcast round-trip.
  * x-update options: plain LS, ridge (SVM), or composite g(x)=mu|x| solved
    by warm-started proximal-gradient *on the cached Gram factor* — the
    "global subproblem on a single node" idea of §4 applied per-iteration;
    adds zero communication.

Beyond-paper: optional int8 error-feedback compression of the per-iteration
reduction (quantize d_i, all_gather int8 + scales, dequant-sum locally) — a
4x wire-byte reduction; ADMM tolerates it as a perturbed RHS and the error
feedback makes the bias vanish (test_distributed.py asserts parity).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import gram as gram_lib
from repro.core.prox import ProxLoss
# One shared int8 error-feedback implementation for every wire: the
# shard_map psum here and the multi-process cluster transport
# (repro.cluster) quantize with the same blocks/scales —
# repro.cluster.compress is the single canonical module; import the
# quantizers from there, not from here.
from repro.cluster.compress import ef_compress

Array = jax.Array


def compressed_psum(v: Array, axis_names, err: Array) -> Tuple[Array, Array]:
    """Error-feedback int8 all-gather-sum over ``axis_names``.

    Returns (sum, new_error). Wire payload per hop: 1 byte/coord (+ scales)
    instead of 4.
    """
    n = v.shape[0]
    q, scale, new_err = ef_compress(v, err)
    # int8 all-gather over the innermost (largest) data axis...
    ax = axis_names[-1]
    qg = jax.lax.all_gather(q, ax)                # (Nax, nb, block) int8
    sg = jax.lax.all_gather(scale, ax)
    deq = (qg.astype(jnp.float32) * sg).reshape(qg.shape[0], -1)[:, :n]
    total = jnp.sum(deq, axis=0)
    # ...then a plain f32 psum across the remaining (outer/pod) axes.
    if len(axis_names) > 1:
        total = jax.lax.psum(total, tuple(axis_names[:-1]))
    return total, new_err


# ---------------------------------------------------------------------------
# The distributed solver
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistributedUnwrappedADMM:
    """Paper Alg. 2 under shard_map.

    Attributes:
      loss: separable ProxLoss on y (rows follow D's row sharding).
      tau: ADMM stepsize.
      rho: ridge weight on x (SVM).
      l1_mu: if > 0, composite x-update with g(x) = l1_mu * |x|.
      data_axes: mesh axis names the rows of D are sharded over.
      compress: int8 error-feedback compression of the per-iteration psum.
      inner_iters: prox-gradient iterations for the composite x-update.
      backend / residency: iteration-engine knobs (DESIGN.md §8); the
        engine body runs PER SHARD inside shard_map — the fused one-pass
        kernel streams the local rows, then only the n-vector d crosses
        the network, composing with the int8-compressed reduction.
    """

    loss: ProxLoss
    tau: float = 1.0
    rho: float = 0.0
    l1_mu: float = 0.0
    data_axes: Tuple[str, ...] = ("data",)
    compress: bool = False
    inner_iters: int = 25
    backend: str = "auto"
    residency: Optional[str] = None

    @property
    def engine(self):
        # Lazy for the same circular-import reason as UnwrappedADMM.engine.
        from repro.engine import IterationEngine
        return IterationEngine(loss=self.loss, tau=self.tau,
                               backend=self.backend,
                               residency=self.residency)

    # -- inner composite x-update: argmin mu|x| + tau/2 (x'Gx - 2 d'x) -------
    def _composite_x(self, G: Array, lmax: Array, d: Array, x_warm: Array):
        # one prox-gradient implementation for every topology
        # (repro.exec.base) — traceable, so it runs inside this shard_map
        # body unchanged
        from repro.core.prox import soft_threshold
        from repro.exec.base import composite_x_update
        return composite_x_update(
            G, lmax, d, x_warm, self.tau,
            lambda z, step: soft_threshold(z, step * self.l1_mu),
            self.inner_iters)

    def build(self, mesh: Mesh, m_global: int, n: int, iters: int,
              obs=None):
        """Returns a jitted ``solve(D_global, aux_global) -> (x, history)``.

        D_global: (m_global, n) sharded P(data_axes, None);
        aux_global: (m_global,) sharded P(data_axes).

        ``m_global`` need not divide the shard count: uneven datasets are
        zero-padded to a shard multiple inside the returned function
        (pass HOST arrays in that case — pre-sharding an uneven array
        with ``shard_rows`` would fail before the pad can happen).

        ``obs`` (:class:`repro.obs.Observability`) wraps the RETURNED
        function, never the shard_map body: one span around the whole
        solve, then the per-iteration (objective, primal-res) history is
        streamed to the telemetry sink after the device work completes.
        With ``obs`` disabled the raw jitted function comes back
        untouched — zero overhead.
        """
        axes = self.data_axes
        nshards = 1
        for a in axes:
            nshards *= mesh.shape[a]
        # Uneven datasets are zero-padded to a shard multiple rather than
        # rejected: zero rows are EXACT under the transpose reduction
        # (no Gram, d, or residual contribution), and
        # with zero aux their iterates stay at zero, so the only telemetry
        # they touch is the objective's constant f(0) term, subtracted in
        # the wrapper below.
        pad = -(-m_global // nshards) * nshards - m_global

        eng = self.engine

        def local_fn(D_loc: Array, aux_loc: Array):
            acc = gram_lib._acc_dtype(D_loc.dtype)
            # -- setup: Gram psum + replicated factor (Alg.2 lines 2-3) --
            G, _ = eng.gram(D_loc)
            G = jax.lax.psum(G, axes)
            ridge = self.rho / self.tau
            use_chol = self.l1_mu == 0.0
            if use_chol:
                L = gram_lib.gram_factor(G, ridge=ridge)
                lmax = jnp.asarray(0.0, acc)
            else:
                L = jnp.zeros((n, n), acc)
                # Power iteration for the inner prox-gradient stepsize.
                v = jnp.ones((n,), acc) / jnp.sqrt(n * 1.0)

                def piter(v, _):
                    w = G @ v
                    return w / jnp.maximum(jnp.linalg.norm(w), 1e-30), None

                v, _ = jax.lax.scan(piter, v, None, length=30)
                lmax = jnp.vdot(v, G @ v)

            m_loc = D_loc.shape[0]
            D_res = eng.prepare(D_loc)
            y = jnp.zeros((m_loc,), acc)
            lam = jnp.zeros((m_loc,), acc)
            err = jnp.zeros((n,), jnp.float32)
            x0 = jnp.zeros((n,), acc)
            # d_loc = D^T(y - lam) rides the carry: the engine's fused body
            # emits the NEXT iteration's reduction in the same data pass
            # that applies the prox (cold start: y = lam = 0 -> d_loc = 0).
            d0 = jnp.zeros((n,), acc)

            def body(carry, _):
                y, lam, err, x_prev, d_loc = carry
                if self.compress:
                    d, err = compressed_psum(d_loc, axes, err)
                else:
                    d = jax.lax.psum(d_loc, axes)
                if use_chol:
                    x = gram_lib.gram_solve(L, d)
                else:
                    x = self._composite_x(G, lmax, d, x_prev)
                # ONE streaming pass over the local shard (Alg. 2 lines 5-8
                # + line 6's reduction input, fused — DESIGN.md §8).
                st = eng.iterate(D_res, aux_loc, y, lam, x, want_dual=False)
                Dx = st.lam - lam + st.y
                # telemetry (global reductions of scalars). The objective
                # is f(Dx) — same as the reference solver's _objective —
                # NOT f(y): mid-run y != Dx (they only meet at
                # convergence), and history must be comparable across
                # solvers at every iteration.
                r_sq = jax.lax.psum(jnp.sum((Dx - st.y) ** 2), axes)
                obj_loc = self.loss.value(Dx, aux_loc)
                obj = jax.lax.psum(obj_loc, axes)
                if self.rho:
                    obj = obj + 0.5 * self.rho * jnp.sum(x * x)
                if self.l1_mu:
                    obj = obj + self.l1_mu * jnp.sum(jnp.abs(x))
                return (st.y, st.lam, err, x, st.d), (obj, jnp.sqrt(r_sq))

            (y, lam, err, x, _), hist = jax.lax.scan(
                body, (y, lam, err, x0, d0), None, length=iters
            )
            return x, hist[0], hist[1]

        in_specs = (P(axes, None), P(axes))
        out_specs = (P(), P(), P())
        from repro.sharding.compat import shard_map
        fn = shard_map(
            local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        if pad == 0:
            solve_fn = jax.jit(fn)
        else:
            # Pad-row objective: iterates of zero rows stay at zero, so
            # their per-iteration contribution is the CONSTANT f(0, aux=0).
            pad_obj = float(self.loss.value(jnp.zeros((pad,)),
                                            jnp.zeros((pad,))))

            @jax.jit
            def padded(D_global: Array, aux_global: Array):
                Dp = jnp.pad(D_global, ((0, pad), (0, 0)))
                ap = jnp.pad(aux_global, (0, pad))
                x, objs, rs = fn(Dp, ap)
                return x, objs - pad_obj, rs

            solve_fn = padded

        if obs is None or not obs.enabled:
            return solve_fn

        def observed(D_global: Array, aux_global: Array):
            with obs.span("distributed_solve", iters=iters,
                          shards=nshards):
                x, objs, rs = solve_fn(D_global, aux_global)
                jax.block_until_ready(x)
            obs.inc("distributed.solves")
            for i, (o, r) in enumerate(zip(jnp.asarray(objs),
                                           jnp.asarray(rs))):
                obs.record(iter=i + 1, objective=float(o),
                           primal_res=float(r), tau=self.tau,
                           rho=self.rho, shards=nshards)
            return x, objs, rs

        return observed


def shard_rows(mesh: Mesh, arr: Array, axes: Sequence[str]) -> Array:
    """Place a host array with rows sharded over the given mesh axes."""
    spec = P(tuple(axes), *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))
