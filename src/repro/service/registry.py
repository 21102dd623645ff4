"""Problem registry — one entry point for every solvable problem.

Replaces the if-chain dispatch that used to live in ``repro.core.fit``:
solvers self-register under ``(problem, method)`` with
:func:`register_problem`, and :func:`solve` is the single dispatch point
that ``repro.core.fit.fit`` (and every call site behind it) routes through.

Two solver surfaces per problem:

  * the *data path*  — ``fn(D, aux, **params) -> FitResult`` on node-stacked
    (N, m_i, n) data, exactly the old ``fit()`` semantics;
  * the *stats path* — for problems whose data term is quadratic
    (lasso / ridge / elastic net / NNLS), ``GRAM_SOLVERS[problem](G, c,
    **params)`` solves straight from cached sufficient statistics. This is
    what the serving layer (repro.service.server) batches and caches: a
    warm request never touches the raw data again.

Registered problems (>= 7 through the one entry point):
  lasso, logistic, svm, sparse_logistic   (seed solvers, relocated here)
  ridge, elastic_net, huber, nnls         (new in the serving layer)
  quantile, group_lasso, multinomial      (executor-backed, DESIGN.md §14)
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import consensus as cons
from repro.core import fasta as fasta_lib
from repro.core import gram as gram_lib
from repro.core import prox as prox_lib
from repro.core.oracles import default_tau
from repro.core.unwrapped import UnwrappedADMM, node_shape
from repro.engine import gram_stats

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class RegisteredSolver:
    problem: str
    method: str
    fn: Callable[..., "FitResult"]
    gram_path: bool = False       # solvable from (G, c) sufficient stats


_REGISTRY: Dict[Tuple[str, str], RegisteredSolver] = {}

# problem -> fn(G, c, **params) -> (x, iters, objective_history|None)
GRAM_SOLVERS: Dict[str, Callable] = {}


def register_problem(problem: str, method: str = "transpose",
                     gram_path: bool = False, aliases: Tuple[str, ...] = ()):
    """Decorator registering ``fn(D, aux, **params) -> FitResult``."""

    def deco(fn):
        for meth in (method,) + tuple(aliases):
            _REGISTRY[(problem, meth)] = RegisteredSolver(
                problem=problem, method=meth, fn=fn, gram_path=gram_path)
        return fn

    return deco


def register_gram_solver(problem: str):
    def deco(fn):
        GRAM_SOLVERS[problem] = fn
        return fn

    return deco


def problems() -> Tuple[str, ...]:
    return tuple(sorted({p for p, _ in _REGISTRY}))


def methods(problem: str) -> Tuple[str, ...]:
    return tuple(sorted(m for p, m in _REGISTRY if p == problem))


def get_solver(problem: str, method: str) -> RegisteredSolver:
    try:
        return _REGISTRY[(problem, method)]
    except KeyError:
        raise ValueError(
            f"unsupported (problem={problem}, method={method}); "
            f"registered problems: {problems()}; "
            f"methods for {problem!r}: {methods(problem) or 'none'}"
        ) from None


def solve(problem: str, D: Array, aux: Array, method: str = "transpose",
          **params) -> "FitResult":
    """The single dispatch point behind ``repro.core.fit.fit``."""
    spec = get_solver(problem, method)
    if params.get("tau") is None and problem in (
            "lasso", "logistic", "svm", "sparse_logistic", "huber"):
        N, mi, n = node_shape(D)
        base = {"sparse_logistic": "logistic", "huber": "svm"}.get(
            problem, problem)
        params["tau"] = default_tau(base, N * mi)
    return spec.fn(D, aux, **params)


def _result(x, iters, history, method, problem):
    from repro.core.fit import FitResult
    return FitResult(x, iters, history, method, problem)


# ---------------------------------------------------------------------------
# Stats-path solvers: x from (G, c) alone — the serving layer's hot path.
# ---------------------------------------------------------------------------

@register_gram_solver("ridge")
def ridge_from_stats(G: Array, c: Array, mu: float = 1.0, iters: int = 0,
                     **_):
    """min 0.5||Dx-b||^2 + mu/2||x||^2  ==  (G + mu I)^{-1} c, closed form.

    The ridge term is added explicitly (not via gram_factor's ridge kwarg)
    so ``mu`` may be a traced scalar — batching vmaps over mu lanes.
    """
    n = G.shape[0]
    A = G + jnp.asarray(mu, G.dtype) * jnp.eye(n, dtype=G.dtype)
    L = gram_lib.gram_factor(A)
    return gram_lib.gram_solve(L, c), 1, None


@register_gram_solver("lasso")
def lasso_from_stats(G: Array, c: Array, mu: float, iters: int = 2000,
                     x0: Optional[Array] = None, l2: float = 0.0, **_):
    # l2 is honoured, not swallowed: a lasso request carrying the elastic-
    # net knob gets the elastic-net solution (l2=0 is plain lasso).
    res = fasta_lib.transpose_reduction_lasso(G, c, mu, iters=iters, x0=x0,
                                              l2=l2)
    return res.x, res.iters, res.objective


@register_gram_solver("elastic_net")
def elastic_net_from_stats(G: Array, c: Array, mu: float, l2: float = 0.0,
                           iters: int = 2000, x0: Optional[Array] = None, **_):
    """min mu|x| + l2/2||x||^2 + 0.5 x^T G x - x^T c: lasso's FASTA with
    the l2 term folded into the smooth part; l2=0 recovers lasso."""
    res = fasta_lib.transpose_reduction_lasso(G, c, mu, iters=iters, x0=x0,
                                              l2=l2)
    return res.x, res.iters, res.objective


@register_gram_solver("nnls")
def nnls_from_stats(G: Array, c: Array, iters: int = 2000,
                    x0: Optional[Array] = None, **_):
    """min_{x>=0} 0.5||Dx-b||^2 — projected gradient (FASTA, prox = clip)."""
    n = G.shape[0]
    if x0 is None:
        x0 = jnp.zeros((n,), G.dtype)
    t0 = 1.0 / fasta_lib.power_lmax(G)
    solver = fasta_lib.Fasta(
        gradg=lambda x: G @ x - c,
        g=lambda x: 0.5 * jnp.vdot(x, G @ x) - jnp.vdot(x, c),
        proxJ=lambda z, t: prox_lib.project_nonneg(z),
        J=lambda x: jnp.asarray(0.0, x.dtype),
    )
    res = solver.run(x0, t0, iters)
    return res.x, res.iters, res.objective


# ---------------------------------------------------------------------------
# Data-path solvers (the old core/fit.py if-chain, relocated).
# ---------------------------------------------------------------------------

def _flatten(D: Array):
    N, mi, n = node_shape(D)
    return D.reshape(N * mi, n), N * mi, n


@register_problem("lasso", "transpose", gram_path=True, aliases=("fasta",))
def _lasso_transpose(D, aux, mu=None, iters=500, x0=None, l2: float = 0.0,
                     **_):
    assert mu is not None
    # §4: direct transpose reduction + single-node FASTA.
    Dflat, m, n = _flatten(D)
    G, c = gram_stats(Dflat, aux.reshape(m))
    x, it, hist = lasso_from_stats(G, c, mu, iters=iters, x0=x0, l2=l2)
    return _result(x, int(it), hist, "transpose", "lasso")


@register_problem("lasso", "consensus")
def _lasso_consensus(D, aux, mu=None, tau=None, iters=500, **_):
    assert mu is not None
    r = cons.ConsensusLasso(mu=mu, tau=tau).run(D, aux, iters)
    return _result(r.z, int(r.iters), r.history.objective,
                   "consensus", "lasso")


@register_problem("logistic", "transpose")
def _logistic_transpose(D, aux, tau=None, iters=500, record=True, x0=None,
                        **_):
    r = UnwrappedADMM(loss=prox_lib.make_logistic(), tau=tau).run(
        D, aux, iters, x0=x0, record=record)
    hist = r.history.objective if r.history else None
    return _result(r.x, int(r.iters), hist, "transpose", "logistic")


@register_problem("logistic", "consensus")
def _logistic_consensus(D, aux, tau=None, iters=500, **_):
    r = cons.ConsensusLogistic(tau=tau).run(D, aux, iters)
    return _result(r.z, int(r.iters), r.history.objective,
                   "consensus", "logistic")


@register_problem("sparse_logistic", "transpose")
def _sparse_logistic_transpose(D, aux, mu=None, tau=None, iters=500,
                               record=True, x0=None, **_):
    assert mu is not None
    # §7 stacking [I; D]: identity block rides on a virtual node.
    Dflat, m, n = _flatten(D)
    D_hat = jnp.concatenate([jnp.eye(n, dtype=D.dtype), Dflat], 0)[None]
    sp = prox_lib.StackedProx(
        blocks=(prox_lib.make_l1(mu), prox_lib.make_logistic()),
        sizes=(n, m),
    )
    aux_hat = jnp.concatenate(
        [jnp.zeros((n,), aux.dtype), aux.reshape(m)])[None]
    r = UnwrappedADMM(loss=sp.as_loss("sparse_logistic"), tau=tau).run(
        D_hat, aux_hat, iters, x0=x0, record=record)
    hist = r.history.objective if r.history else None
    return _result(r.x, int(r.iters), hist, "transpose", "sparse_logistic")


@register_problem("sparse_logistic", "consensus")
def _sparse_logistic_consensus(D, aux, mu=None, tau=None, iters=500, **_):
    assert mu is not None
    r = cons.ConsensusLogistic(mu=mu, tau=tau).run(D, aux, iters)
    return _result(r.z, int(r.iters), r.history.objective,
                   "consensus", "sparse_logistic")


@register_problem("svm", "transpose")
def _svm_transpose(D, aux, C=1.0, tau=None, iters=500, record=True, x0=None,
                   **_):
    r = UnwrappedADMM(loss=prox_lib.make_hinge(C), tau=tau, rho=1.0).run(
        D, aux, iters, x0=x0, record=record)
    hist = r.history.objective if r.history else None
    return _result(r.x, int(r.iters), hist, "transpose", "svm")


@register_problem("svm", "consensus")
def _svm_consensus(D, aux, C=1.0, tau=None, iters=500, **_):
    r = cons.ConsensusSVM(C=C, tau=tau).run(D, aux, iters)
    return _result(r.z, int(r.iters), r.history.objective,
                   "consensus", "svm")


@register_problem("ridge", "transpose", gram_path=True, aliases=("fasta",))
def _ridge_transpose(D, aux, mu=None, **_):
    mu = 1.0 if mu is None else mu
    Dflat, m, n = _flatten(D)
    G, c = gram_stats(Dflat, aux.reshape(m))
    x, it, hist = ridge_from_stats(G, c, mu=mu)
    return _result(x, it, hist, "transpose", "ridge")


@register_problem("elastic_net", "transpose", gram_path=True,
                  aliases=("fasta",))
def _elastic_net_transpose(D, aux, mu=None, l2: float = 0.0, iters=500,
                           x0=None, **_):
    assert mu is not None
    Dflat, m, n = _flatten(D)
    G, c = gram_stats(Dflat, aux.reshape(m))
    x, it, hist = elastic_net_from_stats(G, c, mu=mu, l2=l2, iters=iters,
                                         x0=x0)
    return _result(x, int(it), hist, "transpose", "elastic_net")


@register_problem("nnls", "transpose", gram_path=True, aliases=("fasta",))
def _nnls_transpose(D, aux, iters=500, x0=None, **_):
    Dflat, m, n = _flatten(D)
    G, c = gram_stats(Dflat, aux.reshape(m))
    x, it, hist = nnls_from_stats(G, c, iters=iters, x0=x0)
    return _result(x, int(it), hist, "transpose", "nnls")


@register_problem("huber", "transpose")
def _huber_transpose(D, aux, delta: float = 1.0, tau=None, iters=500,
                     record=True, x0=None, **_):
    """Robust regression min sum h_delta(Dx - b): unwrapped ADMM, huber prox."""
    r = UnwrappedADMM(loss=prox_lib.make_huber(delta), tau=tau).run(
        D, aux, iters, x0=x0, record=record)
    hist = r.history.objective if r.history else None
    return _result(r.x, int(r.iters), hist, "transpose", "huber")


@register_problem("quantile", "transpose")
def _quantile_transpose(D, aux, q: float = 0.5, tau=None, iters=500,
                        record=True, x0=None, **_):
    """Quantile regression min sum rho_q(Dx - b): pinball prox, same
    transpose-reduction loop (and the fused Pallas prox kind)."""
    r = UnwrappedADMM(loss=prox_lib.make_quantile(q),
                      tau=1.0 if tau is None else tau).run(
        D, aux, iters, x0=x0, record=record)
    hist = r.history.objective if r.history else None
    return _result(r.x, int(r.iters), hist, "transpose", "quantile")


@register_problem("group_lasso", "transpose")
def _group_lasso_transpose(D, aux, mu=None, groups=None, tau=None,
                           iters=500, record=True, x0=None, **_):
    """Group lasso min 0.5||Dx-b||^2 + mu sum_g ||x_g||: least-squares
    data term plus an x-space group penalty solved by the driver's
    composite prox-gradient x-update (repro.exec.base.Regularizer)."""
    assert mu is not None
    from repro.exec import make_group_lasso_reg
    n = D.shape[-1]
    g = jnp.arange(n) // 4 if groups is None else jnp.asarray(groups)
    reg = make_group_lasso_reg(float(mu), g, int(g[-1]) + 1)
    r = UnwrappedADMM(loss=prox_lib.make_least_squares(),
                      tau=1.0 if tau is None else tau).solve(
        D, aux, max_iters=iters, x0=x0, record=record, reg=reg)
    hist = r.history.objective if r.history else None
    return _result(r.x, int(r.iters), hist, "transpose", "group_lasso")


@register_problem("multinomial", "transpose")
def _multinomial_transpose(D, aux, classes: int = 3, tau=None, iters=500,
                           record=True, x0=None, **_):
    """Multinomial logistic over K classes: (m, K) splitting iterates
    through the same multi-RHS Gram machinery; x comes back (n, K)."""
    r = UnwrappedADMM(loss=prox_lib.make_multinomial(int(classes)),
                      tau=0.5 if tau is None else tau).solve(
        D, aux, max_iters=iters, x0=x0, record=record)
    hist = r.history.objective if r.history else None
    return _result(r.x, int(r.iters), hist, "transpose", "multinomial")
