"""Distributed GLM fitting launcher — THE PAPER'S end-to-end driver.

``python -m repro.launch.fit --problem logistic --method transpose
     --nodes 8 --rows-per-node 50000 --features 200 [--heterogeneous]``

ONE topology knob selects where the solve runs (DESIGN.md §14):

``--executor local``      — in-memory single-process solve (default);
``--executor streaming``  — the out-of-core path (DESIGN.md §9): data is
    staged into a ``ShardedMatrixStore`` (host RAM, or memory-mapped
    under ``--store-dir``) sized by ``--device-budget-mb``, and the solve
    streams row blocks through the fused engine body — the paper's 5 Tb
    regime, where D never fits the accelerator;
``--executor shard_map``  — row-shard D over all local devices via
    shard_map and the transpose-reduction all-reduce (paper Alg. 2);
``--executor cluster``    — the solve over ``--workers N`` worker
    PROCESSES (DESIGN.md §11): each worker owns a set of row blocks and
    ships only n-length reductions per iteration, with heartbeats, block
    reassignment on worker death, and optional int8-compressed tree
    reduction (``--cluster-compress``) or bounded-staleness quorum
    aggregation (``--cluster-staleness S``). Lasso here is the paper-§4
    regression path: ONE distributed stats reduction, then a local FASTA
    solve — no per-iteration communication at all.

All four are the SAME shared driver over different SolveExecutor
backends (``repro.exec``). The old ``--streaming`` / ``--multi-device`` /
``--cluster N`` selector flags still work as deprecated aliases.

``--density p`` generates the data SPARSE (Bernoulli(p) pattern) and —
with the default ``--sparse-format blockcsr`` — runs the whole pipeline
through the padded block-CSR path (DESIGN.md §10): O(nnz) iterations,
O(nnz) Gram setup, nnz-scaled stores. ``--sparse-format dense``
densifies the same data and runs the dense path (the comparison knob).
"""
from __future__ import annotations

import argparse
import time
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fit import FitResult, fit as fit_glm
from repro.core.prox import make_hinge, make_logistic
from repro.data import synthetic
from repro.launch.jax_cache import use_compile_cache
from repro.obs import Observability


def _admm_params(problem):
    """(loss, rho, tau, spec) for the separable-loss ADMM paths — ONE
    table for the streaming, multi-device AND cluster branches, so a
    calibration change cannot leave them inconsistent. ``spec`` is the
    picklable form cluster workers rebuild the same loss from
    (``repro.cluster.worker.make_loss``)."""
    if problem == "logistic":
        return make_logistic(), 0.0, 0.1, {"name": "logistic"}
    C = 1.0                                    # svm
    return make_hinge(C), 1.0, 0.5, {"name": "hinge", "C": C}


def _fit_streaming(args, D, aux, mu, obs=None):
    """Out-of-core fit: stage into a block store, stream the solve.
    ``D`` may be dense node-stacked or a BlockCSR (nnz-scaled store)."""
    from repro.core.unwrapped import UnwrappedADMM
    from repro.data.sparse import BlockCSR
    from repro.data.store import ShardedMatrixStore
    from repro.engine import autotune
    from repro.service.stats import SufficientStats

    if isinstance(D, BlockCSR):
        # Honor the device budget like the dense branch: the pipeline
        # holds up to 4 blocks in flight (DESIGN.md §9), so re-block
        # when 4x the current per-block bytes exceeds it.
        budget = args.device_budget_mb * 2 ** 20
        per_block = D.nbytes // max(D.nblocks, 1)
        if 4 * per_block > budget:
            bytes_per_row = max(per_block // D.block_m, 1)
            D = D.reblock(max(8, budget // (4 * bytes_per_row)))
        store = ShardedMatrixStore.from_sparse(D, np.asarray(aux))
    else:
        n = D.shape[-1]
        m = D.reshape(-1, n).shape[0]
        br = autotune.streaming_block_rows(
            m, n, D.dtype, budget_bytes=args.device_budget_mb * 2 ** 20)
        store = ShardedMatrixStore.from_arrays(
            np.asarray(D.reshape(-1, n)), np.asarray(aux.reshape(-1)),
            block_rows=br)
    if args.store_dir:
        store = ShardedMatrixStore.open(store.save(args.store_dir))
    print(f"store: {store} (budget {args.device_budget_mb} MiB "
          f"-> {store.nblocks} blocks)", flush=True)
    if args.problem == "lasso":
        # quadratic data term: one streaming stats pass, then the cached-
        # Gram FASTA solve — no iteration ever touches the rows again.
        from repro.core.fasta import transpose_reduction_lasso
        stats = SufficientStats.from_store(store)
        fr = transpose_reduction_lasso(stats.G, stats.c, mu,
                                       iters=args.iters)
        return FitResult(fr.x, int(fr.iters), fr.objective, "transpose",
                         "lasso")
    if args.problem not in ("logistic", "svm"):
        raise SystemExit(f"--executor streaming does not support "
                         f"{args.problem!r} "
                         f"(needs a separable ProxLoss on Dx)")
    loss, rho, tau, _ = _admm_params(args.problem)
    solver = UnwrappedADMM(loss=loss, tau=tau, rho=rho)
    res = solver.solve_streaming(store, max_iters=args.iters, record=True,
                                 checkpoint_dir=args.checkpoint_dir,
                                 checkpoint_every=args.checkpoint_every,
                                 resume=args.resume, obs=obs)
    return FitResult(res.x, int(res.iters), res.history.objective,
                     "transpose", args.problem)


def _fit_cluster(args, D, aux, mu):
    """Multi-process fit: stage a shared block store, spawn workers,
    solve through the cluster coordinator (DESIGN.md §11)."""
    from repro.cluster.chaos import ChaosSchedule
    from repro.cluster.coordinator import (
        ClusterConfig,
        DegradePolicy,
        cluster_solve,
        cluster_stats,
    )

    chaos = None
    if args.chaos_spec:
        chaos = ChaosSchedule.parse(args.chaos_spec)
    elif args.chaos_seed is not None:
        # scale the default fault mix down so small clusters keep a
        # survivor (generate refuses kills+stops >= n_workers)
        chaos = ChaosSchedule.generate(args.chaos_seed,
                                       n_workers=args.cluster,
                                       iters=args.iters,
                                       kills=1 if args.cluster > 1 else 0,
                                       stops=1 if args.cluster > 2 else 0)
    degrade = None
    if args.min_quorum is not None or args.iter_deadline is not None:
        degrade = DegradePolicy(
            min_quorum=(args.min_quorum if args.min_quorum is not None
                        else 0.25),
            iter_deadline_s=(args.iter_deadline
                             if args.iter_deadline is not None else 60.0),
        )
    cfg = ClusterConfig(
        n_workers=args.cluster,
        compress=args.cluster_compress,
        staleness=args.cluster_staleness,
        quorum=0.5 if args.cluster_staleness else 1.0,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        resume=args.resume,
        obs_dir=args.obs_dir,   # the coordinator owns the run directory
        chaos=chaos,
        degrade=degrade,
        # faults are survivable only if killed workers come back
        reconnect={"retries": 8} if chaos is not None else None,
    )
    if chaos is not None:
        print(f"chaos: seed={chaos.seed} spec={chaos.to_spec()!r}",
              flush=True)
    if args.problem == "lasso":
        from repro.core.fasta import transpose_reduction_lasso
        stats, telemetry = cluster_stats(D, aux, store_dir=args.store_dir,
                                         config=cfg)
        wire = sum(telemetry["workers"].get("sent_bytes", {}).values())
        print(f"cluster stats: {stats.rows} rows over {args.cluster} "
              f"workers, {wire} worker-tx bytes total", flush=True)
        fr = transpose_reduction_lasso(stats.G, stats.c, mu,
                                       iters=args.iters)
        return FitResult(fr.x, int(fr.iters), fr.objective, "transpose",
                         "lasso")
    if args.problem not in ("logistic", "svm"):
        raise SystemExit(f"--executor cluster does not support "
                         f"{args.problem!r} "
                         f"(needs a separable ProxLoss on Dx)")
    _, rho, tau, spec = _admm_params(args.problem)
    res = cluster_solve(D, aux, spec, tau=tau, rho=rho,
                        max_iters=args.iters, store_dir=args.store_dir,
                        config=cfg)
    t = res.telemetry
    print(f"cluster: {t['workers_alive']}/{t['workers_spawned']} workers "
          f"alive, {len(t['deaths'])} deaths, "
          f"{t['blocks_reassigned']} blocks reassigned, "
          f"{t['reduction_rx_bytes_per_iter']:.0f} reduction B/iter "
          f"at the coordinator "
          f"({t['payload_bytes_per_nvec']} B payload per n-vector)",
          flush=True)
    rec = t.get("recovery") or {}
    if t.get("status") != "converged" or t.get("joins") or rec.get("events"):
        print(f"cluster status: {t.get('status')} — "
              f"{t.get('joins', 0)} joins, "
              f"{t.get('blocks_rebalanced', 0)} blocks rebalanced, "
              f"{len(rec.get('events', []))} recovery events "
              f"(time-to-recover "
              f"{rec.get('time_to_recover_s') or 0.0:.2f}s, "
              f"{rec.get('iterations_retried', 0)} iterations retried), "
              f"{t.get('degraded_rounds', 0)} degraded rounds",
              flush=True)
    hist = (jnp.asarray(res.history["objective"])
            if res.history else None)
    return FitResult(jnp.asarray(res.x), int(res.iters), hist,
                     "transpose", args.problem)


def _fit_sparse(args, bcsr, aux, mu, obs=None):
    """In-memory sparse fit over the block-CSR engine backend."""
    from repro.core.unwrapped import UnwrappedADMM
    from repro.service.stats import SufficientStats

    if args.method != "transpose":
        raise SystemExit("--density blockcsr supports --method transpose "
                         "only (consensus is a dense-data path)")
    print(f"sparse: {bcsr}", flush=True)
    if args.problem == "lasso":
        from repro.core.fasta import transpose_reduction_lasso
        stats = SufficientStats.from_data(bcsr, aux)
        fr = transpose_reduction_lasso(stats.G, stats.c, mu,
                                       iters=args.iters)
        return FitResult(fr.x, int(fr.iters), fr.objective, "transpose",
                         "lasso")
    if args.problem not in ("logistic", "svm"):
        raise SystemExit(f"--density does not support {args.problem!r} "
                         f"(needs a separable ProxLoss on Dx)")
    loss, rho, tau, _ = _admm_params(args.problem)
    solver = UnwrappedADMM(loss=loss, tau=tau, rho=rho)
    res = solver.run(bcsr, aux, iters=args.iters, obs=obs)
    return FitResult(res.x, int(res.iters), res.history.objective,
                     "transpose", args.problem)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--problem", default="logistic",
                    choices=["lasso", "logistic", "svm", "sparse_logistic"])
    ap.add_argument("--method", default="transpose",
                    choices=["transpose", "consensus"])
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--rows-per-node", type=int, default=5000)
    ap.add_argument("--features", type=int, default=200)
    ap.add_argument("--heterogeneous", action="store_true")
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--mu", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--executor", default=None,
                    choices=["local", "streaming", "shard_map", "cluster"],
                    help="solve topology: in-memory local (default), "
                         "out-of-core streaming, multi-device shard_map, "
                         "or multi-process cluster (--workers N) — all "
                         "the same driver over repro.exec backends")
    ap.add_argument("--workers", type=int, default=2, metavar="N",
                    help="worker processes for --executor cluster")
    ap.add_argument("--multi-device", action="store_true",
                    help="deprecated alias for --executor shard_map")
    ap.add_argument("--streaming", action="store_true",
                    help="deprecated alias for --executor streaming")
    ap.add_argument("--device-budget-mb", type=int, default=256,
                    help="per-block device-memory budget for "
                         "--executor streaming")
    ap.add_argument("--store-dir", default=None,
                    help="persist the block store here (memory-mapped "
                         "reopen) instead of holding it in host RAM")
    ap.add_argument("--cluster", type=int, default=0, metavar="N",
                    help="deprecated alias for --executor cluster "
                         "--workers N")
    ap.add_argument("--cluster-compress", action="store_true",
                    help="int8 error-feedback compression on every "
                         "reduce hop (with --cluster)")
    ap.add_argument("--cluster-staleness", type=int, default=0,
                    metavar="S",
                    help="bounded-staleness quorum aggregation: proceed "
                         "on a quorum, tolerate reductions up to S "
                         "iterations old (0 = strict synchronous)")
    ap.add_argument("--chaos-seed", type=int, default=None, metavar="S",
                    help="with --cluster: inject a seeded, deterministic "
                         "fault schedule (worker kills/hangs, wire "
                         "delays/drops, a mid-solve join) generated from "
                         "this seed (DESIGN.md §13)")
    ap.add_argument("--chaos-spec", default=None, metavar="SPEC",
                    help="with --cluster: explicit fault schedule, e.g. "
                         "'kill@13:w2,delay@5:w0:80,join@9:w4' — "
                         "overrides --chaos-seed")
    ap.add_argument("--min-quorum", type=float, default=None, metavar="F",
                    help="graceful degradation: fraction of workers that "
                         "must stay reachable before the solve returns "
                         "best-so-far with status=degraded")
    ap.add_argument("--iter-deadline", type=float, default=None,
                    metavar="SEC",
                    help="graceful degradation: per-iteration collection "
                         "deadline; expired rounds are retried, then the "
                         "quorum is relaxed / the solve degrades")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="persist solver state here every "
                         "--checkpoint-every iterations (streaming and "
                         "cluster paths)")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest checkpoint in "
                         "--checkpoint-dir")
    ap.add_argument("--density", type=float, default=None,
                    help="generate SPARSE data with this Bernoulli "
                         "density (0 < p <= 1); omit for dense")
    ap.add_argument("--sparse-format", default="blockcsr",
                    choices=["blockcsr", "dense"],
                    help="with --density: run the padded block-CSR path "
                         "(O(nnz) per pass) or densify for comparison")
    ap.add_argument("--obs-dir", default=None,
                    help="write observability artifacts here: trace.json "
                         "(Perfetto), metrics.json, telemetry.jsonl "
                         "(summarize with repro.launch.obs_report)")
    args = ap.parse_args(argv)

    # one topology knob; the old selector flags resolve into it with a
    # deprecation warning (their tuning companions are still honored)
    if args.executor is None:
        if args.cluster:
            warnings.warn("--cluster N is deprecated; use --executor "
                          "cluster --workers N", DeprecationWarning,
                          stacklevel=2)
            args.executor = "cluster"
        elif args.streaming:
            warnings.warn("--streaming is deprecated; use --executor "
                          "streaming", DeprecationWarning, stacklevel=2)
            args.executor = "streaming"
        elif args.multi_device:
            warnings.warn("--multi-device is deprecated; use --executor "
                          "shard_map", DeprecationWarning, stacklevel=2)
            args.executor = "shard_map"
        else:
            args.executor = "local"
    if args.executor == "cluster" and not args.cluster:
        args.cluster = args.workers
    return args


def main(argv=None):
    args = parse_args(argv)
    use_compile_cache()
    N, mi, n = args.nodes, args.rows_per_node, args.features
    t0 = time.time()
    sparse_input = False
    mesh = None
    if args.density is not None:
        from repro.data import sparse as sparse_data
        m = N * mi
        if args.problem == "lasso":
            prob = sparse_data.sparse_lasso_problem(args.seed, m, n,
                                                    args.density)
            D, aux = prob.D, prob.b
            mu = args.mu if args.mu is not None else float(prob.mu)
        else:
            prob = sparse_data.sparse_classification_problem(
                args.seed, m, n, args.density)
            D, aux = prob.D, prob.labels
            mu = args.mu if args.mu is not None else 1.0
        if args.sparse_format == "dense":
            D = D.to_dense().reshape(N, mi, n)
            aux = aux.reshape(N, mi)
        else:
            sparse_input = True
        gib = (D.nbytes if sparse_input else N * mi * n * 4) / 2 ** 30
        print(f"data: {m} rows x {n} features at density "
              f"{args.density} -> {args.sparse_format} "
              f"({gib:.3f} GiB) in {time.time()-t0:.1f}s", flush=True)
    else:
        if args.executor == "shard_map":
            from repro.exec.shard_map import default_mesh
            mesh = default_mesh()
        D, aux, mu_data = generate_dense(args, mesh)
        jax.block_until_ready(D)
        mu = args.mu if args.mu is not None else float(mu_data)
        layout = "flat" if D.ndim == 2 else "node-stacked"
        print(f"data: {N} nodes x {mi} rows x {n} features "
              f"({N*mi*n*4/2**30:.2f} GiB, {layout}) generated on "
              f"{D.devices().pop().platform} in {time.time()-t0:.1f}s",
              flush=True)
    backend = _engine_backend(args, sparse_input)
    dev = jax.devices()[0]
    print(f"engine: backend={backend} device={dev.platform} "
          f"kind={dev.device_kind!r} count={len(jax.devices())}",
          flush=True)

    # one Observability bundle per run: the cluster path hands the run
    # directory to the coordinator instead (it owns the merged trace),
    # so this process's bundle stays disabled there
    obs = Observability(
        dir=args.obs_dir if args.executor != "cluster" else None,
        process_name="fit")
    t0 = time.time()
    if args.executor == "cluster":
        if sparse_input:
            raise SystemExit("--executor cluster currently takes dense "
                             "data (use --sparse-format dense)")
        res = _fit_cluster(args, D, aux, mu)
    elif sparse_input and args.executor != "streaming":
        res = _fit_sparse(args, D, aux, mu, obs=obs)
    elif args.executor == "streaming":
        res = _fit_streaming(args, D, aux, mu, obs=obs)
    elif args.executor == "shard_map" and args.method == "transpose" \
            and args.problem in ("logistic", "svm"):
        # the shard_map SolveExecutor under the shared driver: the same
        # stopping rule / telemetry as every other topology; D arrives
        # already row-sharded over the default mesh
        from repro.engine import IterationEngine
        from repro.exec import ShardMapExecutor, solve_with_executor
        loss, rho, tau, _ = _admm_params(args.problem)
        ex = ShardMapExecutor(IterationEngine(loss=loss, tau=tau), D,
                              aux=aux, mesh=mesh)
        r = solve_with_executor(ex, loss=loss, tau=tau, rho=rho,
                                max_iters=args.iters, record=True,
                                obs=obs)
        res = FitResult(r.x, int(r.iters), r.history.objective,
                        "transpose", args.problem)
    else:
        with obs.span("fit_glm", problem=args.problem,
                      method=args.method):
            res = fit_glm(args.problem, D, aux, method=args.method,
                          mu=mu if args.problem.startswith(("lasso", "sparse"))
                          else None, iters=args.iters)
        if obs.enabled and getattr(res.objective, "ndim", None) == 1:
            for i, o in enumerate(np.asarray(res.objective)):
                obs.record(iter=i + 1, objective=float(o))
    jax.block_until_ready(res.x)
    dt = time.time() - t0
    obs.finish()
    if args.obs_dir:
        print(f"obs: wrote {args.obs_dir} (trace.json / metrics.json / "
              "telemetry.jsonl)", flush=True)
    print(f"[{args.method}] {args.problem}: {res.iters} iters in {dt:.1f}s",
          flush=True)
    _print_diagnostics(args, D, aux, np.asarray(res.x), mu, sparse_input)
    return FitRun(res, backend, dt)


class FitRun(NamedTuple):
    result: FitResult
    backend: str       # engine backend the solve resolved to
    seconds: float     # solve wall time, compilation included


def generate_dense(args, mesh=None):
    """(D, aux, mu) generated from ``--seed`` on the device in one jitted
    program, so the elementwise steps fuse and the peak stays near two
    copies of D. Transpose-method data comes back flat (m, n): on a TPU
    that is the layout the kernels read without a copy, which a
    node-stacked (N, m_i, n) array's is not (kernels/tiling.py). With
    ``mesh`` the rows come back sharded over its devices; with as many
    nodes as devices each chip generates only its own node."""
    N, mi, n = args.nodes, args.rows_per_node, args.features
    het = 1.0 if args.heterogeneous else 0.0
    flat = args.method == "transpose"

    def gen(key):
        if args.problem == "lasso":
            p = synthetic.lasso_problem(key, N, mi, n, heterogeneity=het)
            D, aux, mu = p.D, p.b, p.mu
        else:
            p = synthetic.classification_problem(key, N, mi, n,
                                                 heterogeneity=het)
            D, aux, mu = p.D, p.labels, jnp.float32(1.0)
        if flat:
            D, aux = D.reshape(N * mi, n), aux.reshape(N * mi)
        return D, aux, mu

    out = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        rows = P(mesh.axis_names)
        out = (NamedSharding(mesh, rows), NamedSharding(mesh, rows),
               NamedSharding(mesh, P()))
    return jax.jit(gen, out_shardings=out)(jax.random.PRNGKey(args.seed))


def _engine_backend(args, sparse_input: bool) -> str:
    """The engine backend this run's data passes resolve to."""
    from repro.engine import IterationEngine, default_backend
    if sparse_input:
        return "sparse"
    if args.method == "transpose" and args.problem in ("logistic", "svm"):
        loss = _admm_params(args.problem)[0]
        return IterationEngine(loss=loss).resolve(jnp.float32)
    return default_backend()     # Gram-path setup on the device default


def _print_diagnostics(args, D, aux, x, mu, sparse_input: bool):
    """Objective / KKT lines from Dx and D^T r alone, computed where D
    lives (full f32 precision on a TPU), so no copy of D leaves it."""
    a2 = np.asarray(aux, np.float64).reshape(-1)
    if sparse_input:
        from repro.kernels.spgram import ops as spgram_ops
        matvec = lambda v: spgram_ops.matvec(D, jnp.asarray(v, jnp.float32))
        rmatvec = lambda r: spgram_ops.rmatvec(D, jnp.asarray(r,
                                                              jnp.float32))
    else:
        D2 = D.reshape(-1, D.shape[-1])
        hi = jax.lax.Precision.HIGHEST
        matvec = lambda v: jnp.dot(D2, jnp.asarray(v, D2.dtype),
                                   precision=hi)
        rmatvec = lambda r: jnp.dot(jnp.asarray(r, D2.dtype), D2,
                                    precision=hi)
    Dx = np.asarray(matvec(x), np.float64)
    if args.problem == "lasso":
        corr = np.asarray(rmatvec(Dx - a2), np.float64)
        viol = max(float(np.max(np.abs(corr))) - mu, 0.0)
        on = np.abs(x) > 1e-7
        sup = (float(np.max(np.abs(corr[on] + mu * np.sign(x[on]))))
               if on.any() else 0.0)
        print(f"KKT violation: {viol:.2e}, support err: {sup:.2e}")
    elif args.problem in ("logistic", "sparse_logistic"):
        obj = float(np.sum(np.logaddexp(0.0, -a2 * Dx)))
        acc = float(np.mean(np.sign(Dx) == a2))
        print(f"objective: {obj:.2f}, train acc: {acc:.4f}")
    else:
        obj = float(np.sum(np.maximum(1.0 - a2 * Dx, 0.0))
                    + 0.5 * np.sum(x * x))
        print(f"objective: {obj:.2f}")


if __name__ == "__main__":
    main()
