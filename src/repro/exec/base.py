"""The SolveExecutor contract and the ONE unwrapped-ADMM solve driver.

The paper's thesis is that transpose reduction makes the global
sub-problem identical no matter where the data lives: every topology
produces the same three n-sized reductions d = D^T(y'-lam'),
w = D^T(y'-y), v = D^T lam' plus four scalars, and everything above that
line — the x-update, Boyd's stopping rule, warm starts, checkpoint/
resume, obs spans/telemetry, history assembly — is topology-independent.
This module owns that shared half exactly once (DESIGN.md §14).

A :class:`SolveExecutor` backend owns only the three topology-specific
primitives:

  * ``setup()`` — stage the data and produce the Gram matrix G = D^T D
    (one pass over D, however the topology stores it);
  * ``init(x0)`` — establish the iterate state (y, lam) it keeps between
    sweeps (host buffers, device shards, or worker processes) and return
    the warm-start reduction d = D^T(y - lam);
  * ``sweep(x, k)`` — run the fused per-block body over all rows once
    and reduce to a :class:`~repro.engine.streaming.SweepResult`
    (``None`` aborts the solve as ``degraded``).

plus small hooks for checkpoint state ownership. Backends must NOT
re-implement the stopping rule, residual formulas, history, or
checkpoint cadence — that is the driver's job, and having four copies of
it is the bug class this module deletes.
"""
from __future__ import annotations

import abc
import dataclasses
import time
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gram as gram_lib
from repro.engine.streaming import SweepResult

Array = jax.Array


# ---------------------------------------------------------------------------
# composite x-update: argmin g(x) + tau/2 (x'Gx - 2 d'x), prox-gradient
# ---------------------------------------------------------------------------

@jax.jit
def power_lmax(G: Array) -> Array:
    """Largest eigenvalue of G by 30 power iterations — the inner
    prox-gradient stepsize for composite x-updates."""
    n = G.shape[0]
    v = jnp.ones((n,), G.dtype) / jnp.sqrt(n * 1.0)

    def piter(v, _):
        w = G @ v
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30), None

    v, _ = jax.lax.scan(piter, v, None, length=30)
    return jnp.vdot(v, G @ v)


def composite_x_update(G: Array, lmax: Array, d: Array, x_warm: Array,
                       tau: float, prox: Callable[[Array, Array], Array],
                       inner_iters: int = 25) -> Array:
    """Warm-started proximal gradient on the cached Gram: minimizes
    g(x) + tau/2 (x'Gx - 2 d'x) where ``prox(z, step)`` is the prox of
    ``step * g``. Shared by the driver (group lasso / l1 regularizers)
    and ``DistributedUnwrappedADMM``'s in-jit composite x-update —
    traceable (pure jnp), so it works inside shard_map bodies too."""
    step = 1.0 / (tau * lmax)

    def body(x, _):
        grad = tau * (G @ x - d)
        return prox(x - step * grad, step), None

    x, _ = jax.lax.scan(body, x_warm, None, length=inner_iters)
    return x


@dataclasses.dataclass(frozen=True)
class Regularizer:
    """A separable penalty g(x) on the SOLUTION (not on y = Dx): the
    x-update becomes the composite prox-gradient above instead of a
    Cholesky solve. ``prox(z, step)`` is the prox of ``step * g``."""

    name: str
    value: Callable[[Array], Array]
    prox: Callable[[Array, Array], Array]
    inner_iters: int = 25


def make_l1_reg(mu: float, inner_iters: int = 25) -> Regularizer:
    from repro.core.prox import soft_threshold
    return Regularizer("l1", lambda x: mu * jnp.sum(jnp.abs(x)),
                       lambda z, step: soft_threshold(z, step * mu),
                       inner_iters)


def make_group_lasso_reg(mu: float, groups, num_groups: int,
                         inner_iters: int = 40) -> Regularizer:
    """Group-lasso penalty mu * sum_g ||x_g||_2 over the coordinate
    partition ``groups`` (int array mapping coordinate -> group id)."""
    from repro.core.prox import group_soft_threshold
    g = jnp.asarray(groups, jnp.int32)

    def value(x):
        sq = jax.ops.segment_sum(x * x, g, num_segments=num_groups)
        return mu * jnp.sum(jnp.sqrt(sq))

    return Regularizer(
        "group_lasso", value,
        lambda z, step: group_soft_threshold(z, step * mu, g, num_groups),
        inner_iters)


# ---------------------------------------------------------------------------
# the executor contract
# ---------------------------------------------------------------------------

class SolveExecutor(abc.ABC):
    """One solve topology reduced to its three primitives (module
    docstring). Concrete backends: local / streaming / shard_map /
    cluster. Instances are single-solve: the driver assumes exclusive
    ownership of the iterate state between ``init`` and the last
    ``sweep``."""

    name: str = "?"                  # stamped into telemetry / BENCH json
    backend: str = "?"               # resolved engine backend, ditto
    checkpoint_kind: str = "solve"   # checkpoint `extra["kind"]` tag
    kind_label: str = "executor"     # human label in restore errors
    restore_fallback: bool = False   # CheckpointManager fallback scan
    error_cls = ValueError           # restore-refusal exception type
    status: str = "ok"               # backends may set "degraded"

    m: int
    n: int
    ycols: int = 1
    acc = jnp.float32                # accumulation dtype of x/d

    # -- the three topology primitives --------------------------------------
    @abc.abstractmethod
    def setup(self, obs) -> Array:
        """Stage the data; return the Gram matrix G = D^T D (n, n)."""

    @abc.abstractmethod
    def init(self, x0: Optional[Array]) -> Array:
        """Establish iterate state; return d = D^T(y0 - lam0). ``x0``
        None is the cold start (y = lam = 0 without touching D)."""

    @abc.abstractmethod
    def sweep(self, x: Array, k: int) -> Optional[SweepResult]:
        """One fused pass over all rows for iteration ``k`` (1-based):
        update the backend's (y, lam), return the reductions. ``None``
        stops the solve with ``status='degraded'``."""

    # -- shared-driver hooks (defaults fit most backends) -------------------
    def zero_x(self) -> Array:
        shape = (self.n,) if self.ycols == 1 else (self.n, self.ycols)
        return jnp.zeros(shape, self.acc)

    def pad_objective(self) -> float:
        return 0.0

    def extra_record(self) -> dict:
        """Backend-specific keys merged into each telemetry record."""
        return {}

    def finish(self, iters: int, converged: bool):
        """Post-loop bookkeeping (cluster status accounting)."""

    # -- checkpoint ownership: backend owns SHAPES, driver owns CADENCE -----
    def state_like(self) -> dict:
        yshape = ((self.m,) if self.ycols == 1 else (self.m, self.ycols))
        z = partial(jnp.zeros, dtype=self.acc)
        return {"x": self.zero_x(), "y": z(yshape), "lam": z(yshape),
                "d": self.zero_x()}

    def checkpoint_extra(self) -> dict:
        return {}

    def verify_checkpoint(self, extra: dict):
        """Raise ``error_cls`` when the checkpoint belongs elsewhere."""

    def restore_state(self, k: int, tree: dict) -> Array:
        """Adopt restored (y, lam); return the restored d."""
        raise self.error_cls(
            f"{self.name} executor does not support resume")

    def state_arrays(self, k: int) -> Optional[dict]:
        """{"y": ..., "lam": ...} at iteration k, or None to skip this
        checkpoint round (cluster mid-recovery)."""
        return None

    def on_checkpointed(self, k: int, state: dict):
        """A checkpoint at k was committed (cluster: new replay base)."""

    @abc.abstractmethod
    def final_iterates(self) -> Tuple[Array, Array]:
        """(y, lam) in the node-stacked ADMMResult convention."""


# ---------------------------------------------------------------------------
# THE driver
# ---------------------------------------------------------------------------

def solve_with_executor(ex: SolveExecutor, *, loss, tau: float,
                        rho: float = 0.0, eps_rel: float = 1e-3,
                        eps_abs: float = 1e-6, max_iters: int = 500,
                        x0: Optional[Array] = None, record: bool = False,
                        reg: Optional[Regularizer] = None,
                        checkpoint_dir: Optional[str] = None,
                        checkpoint_every: int = 0, resume: bool = False,
                        obs=None):
    """Unwrapped ADMM (paper Alg. 1/2) over any :class:`SolveExecutor`.

    Owns, exactly once, everything the four topologies used to
    duplicate: the x-update (Cholesky on the cached Gram, or the
    composite prox-gradient when ``reg`` is given), Boyd's stopping rule
    with its eps_pri/eps_dual tolerances, warm starts, checkpoint
    cadence + resume validation, obs spans and per-iteration telemetry
    (stamped with executor name + backend), and history assembly.
    Returns an :class:`~repro.core.unwrapped.ADMMResult`.
    """
    from repro.core.unwrapped import ADMMHistory, ADMMResult
    from repro.obs import NOOP

    obs = obs if obs is not None else NOOP
    with obs.span("admm_solve", executor=ex.name, max_iters=max_iters):
        m, n, K = ex.m, ex.n, ex.ycols
        m_eff, n_eff = m * K, n * K

        with obs.span("gram_setup", executor=ex.name):
            G = ex.setup(obs)
            if reg is None:
                L = gram_lib.gram_factor(G, ridge=rho / tau)
                lmax = None
            else:
                L = None
                lmax = power_lmax(G)

        manager = None
        if checkpoint_dir is not None:
            from repro.checkpoint.manager import CheckpointManager
            manager = CheckpointManager(checkpoint_dir)

        k = 0
        ex.resume_iter = 0
        if (manager is not None and resume
                and manager.latest_step() is not None):
            tree, extra = manager.restore(ex.state_like(),
                                          fallback=ex.restore_fallback)
            if extra.get("kind") != ex.checkpoint_kind:
                raise ex.error_cls(
                    f"not a {ex.kind_label} checkpoint: {extra}")
            ex.verify_checkpoint(extra)
            k = int(extra["iter"])
            ex.resume_iter = k
            d = ex.restore_state(k, tree)
            x = tree["x"]            # returned as-is if no iterations remain
        elif x0 is not None:
            with obs.span("init_from_x0", executor=ex.name):
                d = ex.init(x0)
            x = ex.zero_x()
        else:
            d = ex.init(None)
            x = ex.zero_x()

        pad_obj = ex.pad_objective()
        objs, rs, ss = [], [], []
        k_conv = -1
        while k < max_iters:
            t_it = time.perf_counter()
            with obs.span("x_solve", k=k + 1):
                if reg is None:
                    x = gram_lib.gram_solve(L, jnp.asarray(d))
                else:
                    x = composite_x_update(G, lmax, jnp.asarray(d),
                                           jnp.asarray(x), tau, reg.prox,
                                           reg.inner_iters)
            with obs.span("sweep", k=k + 1):
                sw = ex.sweep(x, k + 1)
            if sw is None:           # degraded stop: best-so-far x
                break
            # the sweep was only enqueued: the host waits for it here
            with obs.span("stop_rule", k=k + 1):
                d = sw.d
                r = float(jnp.sqrt(sw.r_sq))
                s = tau * float(jnp.linalg.norm(sw.w))
                eps_pri = np.sqrt(m_eff) * eps_abs + eps_rel * max(
                    float(jnp.sqrt(sw.dx_sq)), float(jnp.sqrt(sw.y_sq)))
                eps_dual = np.sqrt(n_eff) * eps_abs + (
                    eps_rel * tau * float(jnp.linalg.norm(sw.v)))
            k += 1
            if record or obs.enabled:
                obj = float(sw.obj) - pad_obj
                if rho:
                    obj += 0.5 * rho * float(jnp.sum(jnp.asarray(x) ** 2))
                if reg is not None:
                    obj += float(reg.value(jnp.asarray(x)))
                if record:
                    objs.append(obj)
                    rs.append(r)
                    ss.append(s)
                if obs.enabled:
                    dt = time.perf_counter() - t_it
                    obs.observe(f"{ex.name}.iter_s", dt)
                    obs.record(iter=k, objective=obj, primal_res=r,
                               dual_res=s, eps_pri=float(eps_pri),
                               eps_dual=float(eps_dual), tau=tau, rho=rho,
                               iter_s=round(dt, 6),
                               executor=ex.name, backend=ex.backend,
                               **ex.extra_record())
            if manager is not None and checkpoint_every \
                    and k % checkpoint_every == 0:
                state = ex.state_arrays(k)
                if state is not None:
                    manager.save(k, {"x": x, "y": state["y"],
                                     "lam": state["lam"], "d": d},
                                 extra={"kind": ex.checkpoint_kind, "iter": k,
                                        **ex.checkpoint_extra()})
                    ex.on_checkpointed(k, state)
            if r <= eps_pri and s <= eps_dual:
                k_conv = k - 1
                break

        converged = k_conv >= 0
        ex.finish(k, converged)
        history = None
        if record:
            acc = ex.acc
            nan = jnp.full((len(objs),), jnp.nan, acc)
            history = ADMMHistory(jnp.asarray(objs, acc), jnp.asarray(rs, acc),
                                  jnp.asarray(ss, acc), nan,
                                  jnp.asarray(k_conv, jnp.int32))
        y, lam = ex.final_iterates()
    return ADMMResult(jnp.asarray(x), y, lam, jnp.asarray(k, jnp.int32),
                      history)
