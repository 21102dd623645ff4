"""Benchmark harness — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig1,...]
[--json [PATH]]``

Prints ``name,us_per_call,derived`` CSV rows (one per measured cell).
``--json`` additionally makes the engine benchmark write its machine-
readable result (default ``BENCH_engine.json``) so CI can diff the perf
trajectory run over run. The exit code is non-zero when any module
failed or reported a parity MISMATCH.
"""
from __future__ import annotations

import argparse
import sys
import time


def host_meta() -> dict:
    """Host/provenance block stamped into every BENCH_*.json payload —
    one shared definition so a result can always be traced back to the
    machine, software stack, and commit that produced it. Imports stay
    lazy: bench modules ``from benchmarks.run import host_meta`` without
    pulling jax at import time.
    """
    import os
    import platform
    import subprocess
    meta: dict = {
        "cpu_count": os.cpu_count(),
        "platform": platform.platform(),
        "python": platform.python_version(),
    }
    try:
        import jax
        import jaxlib
        meta["jax"] = jax.__version__
        meta["jaxlib"] = jaxlib.__version__
        meta["jax_backend"] = jax.default_backend()
    except Exception:
        meta["jax"] = meta["jaxlib"] = meta["jax_backend"] = None
    try:
        p = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=5)
        meta["git_sha"] = p.stdout.strip() if p.returncode == 0 else None
    except Exception:
        meta["git_sha"] = None
    return meta


MODULES = {
    "fig1": "benchmarks.fig1_scaling",        # Fig 1 a/b/c scaling sweeps
    "fig2": "benchmarks.fig2_convergence",    # Fig 2 a/b/c curves
    "table1": "benchmarks.table1_star",       # Table 1 star-catalog sweep
    "appendix": "benchmarks.appendix_tables", # Appendix B sweeps
    "tau": "benchmarks.tau_calibration",      # §9 tuning protocol
    "roofline": "benchmarks.roofline_report", # §Roofline collation
    "engine": "benchmarks.engine_bench",      # iteration-engine backends
    "streaming": "benchmarks.streaming_bench",  # out-of-core block streaming
    "sparse": "benchmarks.sparse_bench",      # block-CSR vs dense chunked
    "cluster": "benchmarks.cluster_bench",    # multi-process runtime
    "service": "benchmarks.service_load",     # multi-tenant front end load
}

# modules that can emit a machine-readable result: module key -> default path
JSON_MODULES = {"engine": "BENCH_engine.json",
                "streaming": "BENCH_streaming.json",
                "sparse": "BENCH_sparse.json",
                "cluster": "BENCH_cluster.json",
                "service": "BENCH_service.json"}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI-friendly)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(MODULES))
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="write machine-readable results for the JSON-"
                         "capable modules in the selection (engine -> "
                         "BENCH_engine.json, streaming -> "
                         "BENCH_streaming.json); an explicit PATH names "
                         "the sole selected module's output, or the "
                         "engine result when several are selected "
                         "(legacy behavior)")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else set(MODULES)
    if args.json is not None:
        targets = [k for k in JSON_MODULES if k in only] or ["engine"]
        if args.json and len(targets) > 1:
            # an explicit PATH with several JSON-capable modules in the
            # selection keeps the legacy meaning: PATH names the engine
            # result; the others write their defaults
            targets = ["engine"] + [k for k in targets if k != "engine"]
        only.update(targets)
        for key in targets:
            mod = __import__(MODULES[key], fromlist=["JSON_PATH"])
            mod.JSON_PATH = (args.json
                             if args.json and key == targets[0]
                             else JSON_MODULES[key])

    rows = ["name,us_per_call,derived"]
    for key, modname in MODULES.items():
        if key not in only:
            continue
        mod = __import__(modname, fromlist=["run"])
        t0 = time.time()
        try:
            mod.run(rows, quick=args.quick)
            rows.append(f"{key}_total,{(time.time()-t0)*1e6:.0f},ok")
        except Exception as e:  # keep the harness going, report the failure
            rows.append(f"{key}_total,0,FAILED:{type(e).__name__}:{e}")
    print("\n".join(rows))
    if any(",FAILED:" in r or r.endswith(",MISMATCH") for r in rows):
        sys.exit(1)


if __name__ == "__main__":
    main()
