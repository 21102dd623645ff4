#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU, from the repository root.

    python chip_smoke.py             # one chip: fit, reference, serve
    python chip_smoke.py --chips 4   # four chips: the shard_map fit only

One chip runs the paper's star-catalog fit at its one-chip share (Table 1's
950,272,000 x 307 rows over 256 chips: 3,712,000 x 307 f32, 4.56 GB of D)
through ``repro.launch.fit`` on the pallas backend, checks x against the
``reference`` engine backend at full f32 precision and the Pallas Gram
against D^T D summed in float64 on the host, then serves 8 fits at the
same width through ``repro.launch.serve_fit``. Four chips run the same fit over 4 x the share
with ``--executor shard_map`` (paper Alg. 2), D generated row-sharded on
the chips, and check x against the same driver on the ``reference``
backend.

Every check prints its value beside its bound. The last line of standard
output is one JSON object naming the device; it is printed only when every
check passed. Without a TPU, or outside a checkout, the script exits
non-zero and prints no result. Timings are information, not benchmark
numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
STAR_ROWS, STAR_FEATURES = 3_712_000, 307   # paper Table 1, per chip
SERVE_ROWS = 1_000_000
X_RTOL, G_RTOL = 1e-3, 1e-4


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(name: str, value: float, bound: float):
    ok = value <= bound
    print(f"check {name}: {value:.3e} <= {bound:.0e}: "
          f"{'pass' if ok else 'FAIL'}", flush=True)
    if not ok:
        fail(f"{name} {value:.3e} exceeds {bound:.0e}")


def rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def fit_argv(chips: int, iters: int, seed: int):
    return ["--problem", "logistic", "--method", "transpose",
            "--executor", "local" if chips == 1 else "shard_map",
            "--nodes", str(chips), "--rows-per-node", str(STAR_ROWS),
            "--features", str(STAR_FEATURES), "--iters", str(iters),
            "--seed", str(seed)]


def phase_fit_one_chip(iters: int, seed: int):
    import jax
    import numpy as np
    from repro.core.oracles import default_tau
    from repro.core.prox import make_logistic
    from repro.core.unwrapped import UnwrappedADMM
    from repro.engine import gram_stats
    from repro.launch import fit

    argv = fit_argv(1, iters, seed)
    cold = fit.main(argv)
    warm = fit.main(argv)
    print(f"info: fit solve {cold.seconds:.2f}s cold (compile included), "
          f"{warm.seconds:.2f}s warm; compile ~{cold.seconds - warm.seconds:.2f}s",
          flush=True)
    for run in (cold, warm):
        if run.backend != "pallas":
            fail(f"fit resolved engine backend {run.backend!r}, not pallas")
    x = np.asarray(warm.result.x)
    if not np.isfinite(x).all() or x.shape != (STAR_FEATURES,):
        fail(f"fit x has shape {x.shape} or non-finite entries")
    print(f"info: fit x cold vs warm rel l2 {rel_err(cold.result.x, x):.3e}",
          flush=True)

    # the same data from the same seed, the same iterations on the
    # reference backend; XLA's default f32 dot on a TPU is one bf16 pass,
    # so the oracle runs at full precision
    args = fit.parse_args(argv)
    D, aux, _ = fit.generate_dense(args)
    fmt = getattr(D, "format", None)
    print(f"info: D {D.shape} {D.dtype} layout "
          f"{getattr(fmt, 'layout', 'unknown')}", flush=True)
    m = D.shape[0]
    with jax.default_matmul_precision("highest"):
        ref = UnwrappedADMM(loss=make_logistic(),
                            tau=default_tau("logistic", m),
                            backend="reference").run(D, aux, iters,
                                                     record=False)
        G_xla = D.T @ D
    t0 = time.time()
    G, _ = gram_stats(D, backend="pallas")
    G = jax.block_until_ready(G)
    print(f"info: Gram {time.time() - t0:.2f}s pallas (compile included)",
          flush=True)
    G_ref = host_gram(D)
    print(f"info: XLA D^T D at highest vs the float64 Gram (rel Frobenius) "
          f"{rel_err(G_xla, G_ref):.3e}", flush=True)
    check("fit x vs reference backend (rel l2)", rel_err(x, ref.x), X_RTOL)
    check("pallas Gram vs float64 D^T D (rel Frobenius)",
          rel_err(G, G_ref), G_RTOL)


def host_gram(D, rows: int = 262_144):
    """D^T D summed in float64 on the host, a slab of rows at a time: the
    oracle for the Gram. A single f32 dot over millions of rows is not
    one, whatever its precision, because its own sum runs in f32."""
    import numpy as np
    G = np.zeros((D.shape[1],) * 2)
    for s in range(0, D.shape[0], rows):
        slab = np.asarray(D[s:s + rows], np.float64)
        G += slab.T @ slab
    return G


def phase_serve(seed: int):
    from repro.launch import serve_fit

    out = serve_fit.main(["--rows", str(SERVE_ROWS), "--features",
                          str(STAR_FEATURES), "--requests", "8",
                          "--seed", str(seed)])
    bad = [s for s in out["statuses"] if s != "ok"]
    if len(out["statuses"]) != 8 or bad:
        fail(f"served statuses {out['statuses']}")
    print("check served statuses: 8 of 8 ok: pass", flush=True)
    passes = out["counters"]["gram_passes"]
    if passes != 1:
        fail(f"served path made {passes} Gram passes, not 1")
    print("check served Gram passes: 1 == 1: pass", flush=True)


def phase_fit_four_chips(iters: int, seed: int):
    import jax
    import numpy as np
    from repro.engine import IterationEngine
    from repro.exec import ShardMapExecutor, solve_with_executor
    from repro.exec.shard_map import default_mesh
    from repro.launch import fit

    argv = fit_argv(4, iters, seed)
    run = fit.main(argv)
    if run.backend != "pallas":
        fail(f"fit resolved engine backend {run.backend!r}, not pallas")
    x = np.asarray(run.result.x)
    if not np.isfinite(x).all():
        fail("fit x has non-finite entries")

    args = fit.parse_args(argv)
    mesh = default_mesh()
    D, aux, _ = fit.generate_dense(args, mesh)
    devices = {s.device for s in D.addressable_shards}
    print(f"info: D {D.shape} in {len(D.addressable_shards)} shards of "
          f"{D.addressable_shards[0].data.shape} on {len(devices)} "
          "devices", flush=True)
    if len(devices) != 4:
        fail(f"D's shards sit on {len(devices)} devices, not 4")
    loss, rho, tau, _ = fit._admm_params(args.problem)
    with jax.default_matmul_precision("highest"):
        ex = ShardMapExecutor(IterationEngine(loss=loss, tau=tau,
                                              backend="reference"),
                              D, aux=aux, mesh=mesh)
        ref = solve_with_executor(ex, loss=loss, tau=tau, rho=rho,
                                  max_iters=iters)
    print(f"info: iterations pallas {run.result.iters}, reference "
          f"{int(ref.iters)}", flush=True)
    check("shard_map x vs reference backend (rel l2)", rel_err(x, ref.x),
          X_RTOL)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the shard_map phase on four chips")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro" / "launch" / "fit.py").is_file():
        fail(f"no repro package under {SRC}: run from a checkout of the "
             "repository")
    sys.path.insert(0, str(SRC))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {len(devs)} {devs[0].platform} device(s)")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPUs, found "
             f"{len(devs)}")
    from repro.launch.jax_cache import use_compile_cache
    print(f"device: {devs[0].device_kind} x{len(devs)}, jax "
          f"{jax.__version__}, compile cache {use_compile_cache()}",
          flush=True)

    t0 = time.time()
    if args.chips == 1:
        phase_fit_one_chip(args.iters, args.seed)
        phase_serve(args.seed)
    else:
        phase_fit_four_chips(args.iters, args.seed)
    stats = devs[0].memory_stats() or {}
    print(f"info: all phases {time.time() - t0:.1f}s, device 0 "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
