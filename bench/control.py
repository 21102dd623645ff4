#!/usr/bin/env python3
"""Readings for the limits of ``correct``, on the chip, in one process:

    python3 bench/control.py --workload star.logistic --seeds 101 102 103

For each seed, one run of the cell as the benchmark makes it (a short
window, then the check), and one run with the control in the program's
place: the plain reference computed in the precision below the one the
configuration states (``--precision``, default ``high``: three bf16
products for f32 at HIGHEST). On a cell over several chips,
``--fault-seeds`` adds runs of the program with the exchange between
chips left out (:func:`exchange_dropped`). Prints each run's compared
numbers, one JSON line per run. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def control_fit(precision: str):
    """A fit hook: the reference, at ``precision``, to the mix's tolerance."""
    import harness

    reference = harness.bench_module("reference")
    admm = harness.bench_module("jobs.admm")
    data = harness.bench_module("data")

    def fit(job):
        eps_rel, eps_abs = admm.tolerance(job.mix, job.cfg["rows"])
        out = reference.admm(
            data.blocks(job.D, job.aux),
            loss=job.mix["loss"], tau=job.mix["tau"], rho=job.mix["rho"],
            C=job.mix.get("C", 1.0), eps_rel=eps_rel, eps_abs=eps_abs,
            max_iters=job.mix["max_iters"], precision=precision)
        k = out["stop"] or job.mix["max_iters"]
        return out["x"][k], k

    return fit


@contextlib.contextmanager
def exchange_dropped():
    """A planted fault of the program: while on, the sharded step's
    n-vector sums (d, w, v) stay each chip's own rows' sums and are not
    added across chips; the Gram and the scalars are still all-reduced.
    Steps built while it is on keep it, so the step cache is emptied on
    the way in and out."""
    import jax
    from repro.exec import shard_map

    psum = jax.lax.psum

    def psum_but_vectors(x, axis_name, **kw):
        return x if jax.numpy.ndim(x) == 1 else psum(x, axis_name, **kw)

    jax.lax.psum = psum_but_vectors
    shard_map._shard_fns.cache_clear()
    try:
        yield
    finally:
        jax.lax.psum = psum
        shard_map._shard_fns.cache_clear()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=(),
                    help="seeds of the program's runs")
    ap.add_argument("--control-seeds", type=int, nargs="*", default=(),
                    help="seeds of the control's runs")
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=(),
                    help="seeds of the program's runs with the exchange "
                         "between chips left out")
    ap.add_argument("--precision", default="high")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import harness

    log = lambda m: print(m, file=sys.stderr, flush=True)
    runs = ([(s, "program") for s in args.seeds]
            + [(s, "control") for s in args.control_seeds]
            + [(s, "exchange_dropped") for s in args.fault_seeds])
    for seed, side in runs:
        hook = control_fit(args.precision) if side == "control" else None
        fault = (exchange_dropped() if side == "exchange_dropped"
                 else contextlib.nullcontext())
        with fault:
            res = harness.run_cell(args.workload, seed, 0.0, False, log=log,
                                   fit_hook=hook)
        row = {"cell": args.workload, "seed": seed, "side": side,
               "precision": args.precision if hook else "program",
               "checks": res["checks"], "attempted": res["attempted"],
               "failed": res["failed"], "metrics": res["metrics"]}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
