"""The unwrapped-ADMM fit job: the program's solve driver
(``exec/base.solve_with_executor``) to the mix's tolerance, cold start,
and the check of its answers against the plain reference.

The fit is ``UnwrappedADMM.solve`` on one chip (a ``LocalExecutor``).
A config with ``"executor": "shard_map"`` runs each fit as
``launch.fit --executor shard_map`` does: a ``ShardMapExecutor`` over the
cell's chips, holding D's rows where the harness placed them, under the
same driver (paper Alg. 2). Its engine is built once per job around the
job's one loss, so the step is traced once per process, in the warm-up.
Loss, tau and rho come from the program's own
table (``launch.fit._admm_params``), and the job refuses to run when
that table no longer says what the mix states, because the reference
follows the mix.
"""
from __future__ import annotations

import math

import numpy as np


def tolerance(mix: dict, m: int):
    """(eps_rel, eps_abs) of Boyd's stopping rule. ``eps_abs`` is stated
    per unit column norm: D's columns have norm about sqrt(m), so the
    rule gets ``eps_abs * sqrt(m)``."""
    return mix["eps_rel"], mix["eps_abs_per_sqrt_row"] * math.sqrt(m)


EXECUTORS = ("local", "shard_map")


class Job:
    def __init__(self, cfg: dict, mix: dict, D, aux, mesh=None):
        from repro.launch.fit import _admm_params

        self.loss, rho, tau, _ = _admm_params(mix["problem"])
        if (tau, rho) != (mix["tau"], mix["rho"]):
            raise SystemExit(
                f"the program's table gives tau={tau}, rho={rho} for "
                f"{mix['problem']!r}; the mix states tau={mix['tau']}, "
                f"rho={mix['rho']}: the benchmark's mix must change first")
        self.tau, self.rho = tau, rho
        self.cfg, self.mix = cfg, mix
        self.D, self.aux = D, aux
        eps_rel, eps_abs = tolerance(mix, cfg["rows"])
        self.tol = dict(eps_rel=eps_rel, eps_abs=eps_abs,
                        max_iters=mix["max_iters"])
        executor = cfg.get("executor", "local")
        if executor not in EXECUTORS:
            raise SystemExit(f"config {cfg['name']!r} names executor "
                             f"{executor!r}; known: {EXECUTORS}")
        self.mesh, self.engine = mesh, None
        if executor == "shard_map":
            from repro.core.unwrapped import UnwrappedADMM

            self.engine = UnwrappedADMM(loss=self.loss, tau=tau,
                                        rho=rho).engine

    def fit(self):
        """One cold-start fit to tolerance on the current ``self.D``.
        Returns (x on the host, iterations)."""
        if self.engine is not None:
            return self._fit_sharded()
        from repro.core.unwrapped import UnwrappedADMM

        tol = self.tol
        res = UnwrappedADMM(loss=self.loss, tau=self.tau, rho=self.rho,
                            eps_rel=tol["eps_rel"],
                            eps_abs=tol["eps_abs"]).solve(
            self.D, self.aux, max_iters=tol["max_iters"])
        return np.asarray(res.x), int(res.iters)

    def _fit_sharded(self):
        from repro.exec import ShardMapExecutor, solve_with_executor

        ex = ShardMapExecutor(self.engine, self.D, aux=self.aux,
                              mesh=self.mesh)
        res = solve_with_executor(ex, loss=self.loss, tau=self.tau,
                                  rho=self.rho, **self.tol)
        return np.asarray(res.x), int(res.iters)


def check(mix, cfg, blocks, fits, limits, reference, log) -> dict:
    """{name: (number, limit)}: every fit of the window against the plain
    reference, run once on the last fit's data (``blocks``: its rows and
    labels as [(D_i, labels_i), ...], one block per chip). Fit k's data
    is the last fit's with some columns negated, so its exact answer is
    the last one's with the same entries negated.

    ``x_gap``: the widest relative gap between a fit's x and the
    reference's x after as many iterations. ``stop_residual``: the
    reference's own residuals at the iteration where each fit stopped,
    as the larger of r / eps_pri and s / eps_dual; above 1 the fit
    stopped before the reference met the tolerance. Fits that reached
    the cap are counted as failed and not held to it."""
    import time

    eps_rel, eps_abs = tolerance(mix, cfg["rows"])
    keep = sorted({f["iters"] for f in fits})
    hist = []
    t0 = time.perf_counter()
    ref = reference.admm(
        blocks, loss=mix["loss"], tau=mix["tau"], rho=mix["rho"],
        C=mix.get("C", 1.0), eps_rel=eps_rel, eps_abs=eps_abs,
        max_iters=mix["max_iters"], keep=keep, history=hist)
    log(f"info: reference {time.perf_counter() - t0:.3f}s, stops at "
        f"{ref['stop']}, program fits {keep} iterations")
    last = fits[-1]["signs"]
    x_gap, stop_res = 0.0, 0.0
    for f in fits:
        xr = ref["x"][f["iters"]]
        x = f["x"].astype(np.float64) * f["signs"] * last
        gap = float(np.linalg.norm(x - xr) / np.linalg.norm(xr))
        x_gap = max(x_gap, gap if math.isfinite(gap) else math.inf)
        if f["iters"] < mix["max_iters"]:
            r, eps_pri, s, eps_dual = hist[f["iters"] - 1]
            stop_res = max(stop_res, r / eps_pri, s / eps_dual)
    return {"x_gap": (x_gap, limits["x_gap"]),
            "stop_residual": (float(stop_res), limits["stop_residual"])}
