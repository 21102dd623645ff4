"""One run of one cell: set up, measure whole fits for a fixed time, check
the answers against the plain reference, and report.

Everything that belongs to one configuration, mix, cell limit or per-layer
metric is a file of its own, found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: shape, dtype, chips, executor, source;
* ``bench/mixes/<traffic>.json``: the fit job, loss, tau, rho, tolerance;
* ``bench/limits/<cell>.json``: the limit of each number ``correct``
  compares, with the readings it was set from;
* ``bench/metrics/<metric>.py``: ``read(run)`` turns a traced run into
  one number, or None where it finds nothing to read;
* ``bench/jobs/<job>.py``: the call into the program for one fit.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

BENCH = Path(__file__).resolve().parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def process_start() -> float:
    """Wall-clock time at which this process started (Linux), else now."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19]) / ticks
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start
    except (OSError, ValueError, IndexError):
        return time.time()


def _module(path: Path, name: str):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def bench_module(name: str):
    """``bench/<name>.py``, imported once under the name ``bench_<name>``
    (plain names such as ``trace`` would shadow the standard library)."""
    return _module(BENCH / f"{name.replace('.', '/')}.py",
                   "bench_" + name.replace(".", "_"))


def load_cell(name: str, root: Path) -> SimpleNamespace:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its config, mix,
    limits and the per-layer metrics it reports."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "bench" / "mixes"
                      / f"{cell['traffic']}.json").read_text())
    limits = json.loads((root / "bench" / "limits"
                         / f"{name}.json").read_text())
    reports = lambda m: name in m.get("workloads", [name])
    return SimpleNamespace(
        name=name, chips=cell["chips"], cfg=cfg, mix=mix,
        limits=limits["limits"], end_to_end=[m for m in spec["end_to_end"]
                                             if reports(m)],
        per_layer=[m for m in spec["per_layer"] if reports(m)], root=root)


def use_compile_cache(jax, root: Path) -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is
    cached, however short its compile, so a warm set-up compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Compiles:
    """Programs that JAX compiled, and that it read from the persistent
    compile cache, while ``on``: every program the window asks for should
    come from a cache, so ``compiled`` stays 0 there."""
    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    READ = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self, jax):
        self.on, self.programs, self.cached, self.read_s = False, 0, 0, 0.0
        self._monitoring = jax.monitoring
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def close(self):
        self.on = False
        self._monitoring.unregister_event_listener(self._event)
        self._monitoring.unregister_event_duration_listener(self._duration)

    def _event(self, name, **_):
        if self.on and name == self.HIT:
            self.cached += 1

    def _duration(self, name, secs, **_):
        if self.on and name == self.BACKEND:
            self.programs += 1
        if self.on and name == self.READ:
            self.read_s += secs

    @property
    def compiled(self) -> int:
        return self.programs - self.cached


def devices(jax, chips: int, require_chip: bool):
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {len(devs)} {devs[0].platform} "
                     "device(s)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = BENCH.parent, require_chip: bool = True,
             t_start: float | None = None, log=print,
             fit_hook=None) -> dict:
    """One run. Returns the result object that ``run.py`` prints.
    ``fit_hook(job)``, where given, runs each fit in the program's place
    (the control: the reference at a lower precision)."""
    t_start = process_start() if t_start is None else t_start
    cell = load_cell(name, root)
    cfg, mix = cell.cfg, cell.mix
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    use_compile_cache(jax, root)
    devs = devices(jax, cell.chips, require_chip)
    bench_data = bench_module("data")
    bench_trace = bench_module("trace_reduce")

    m, n, dtype = cfg["rows"], cfg["features"], cfg["dtype"]
    mesh = shardings = None
    if cell.chips > 1:
        mesh = bench_data.row_mesh(devs)
        shardings = bench_data.row_shardings(mesh)
    D, aux = bench_data.make(cfg["data"], m, n, dtype, shardings)
    job_kind = bench_module(f"jobs.{mix['job']}")
    job = job_kind.Job(cfg, mix, D, aux, mesh)
    del D
    if fit_hook is not None:
        job.fit = lambda: fit_hook(job)

    def one_fit(k: int):
        s = bench_data.signs(seed, k, n)
        with jax.profiler.TraceAnnotation(bench_trace.FLIP):
            job.D = bench_data.flip(job.D, s)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(bench_trace.FIT):
            x, iters = job.fit()
        return s, x, iters, time.perf_counter() - t0

    compiles = Compiles(jax)
    cum, _, warm_iters, warm_s = one_fit(0)      # compiles, or reads cache
    log(f"info: warm-up fit {warm_s:.3f}s, {warm_iters} iterations")
    compiles.on = True

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    fits = []
    t_first = time.time()
    setup_s = t_first - t_start
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(bench_trace.WINDOW):
        k = 0
        while not fits or time.perf_counter() - t0 < seconds:
            k += 1
            s, x, iters, dt = one_fit(k)
            cum = cum * s
            fits.append({"x": x, "iters": iters, "seconds": dt,
                         "signs": cum.copy()})
    window_s = time.perf_counter() - t0
    compiles.close()
    log(f"info: window {window_s:.3f}s, {len(fits)} fits of "
        f"{sorted({f['iters'] for f in fits})} iterations, seconds "
        + " ".join(f"{f['seconds']:.3f}" for f in fits))
    log(f"info: window compiled {compiles.compiled} programs and read "
        f"{compiles.cached} from the compile cache in {compiles.read_s:.3f}s")
    events = None
    if trace:
        jax.profiler.stop_trace()
        events = bench_trace.extract(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devs)

    failed = sum(1 for f in fits if f["iters"] >= mix["max_iters"]
                 or not np.all(np.isfinite(f["x"])))
    result = {"correct": False, "attempted": len(fits), "failed": failed}

    if trace:
        run = SimpleNamespace(cfg=cfg, mix=mix, fits=fits, events=events,
                              devices=[str(d.id) for d in devs],
                              peak=_peak(devs[0].device_kind),
                              rows_per_chip=m // cell.chips,
                              trace=bench_trace, work=bench_module("work"))
        run.window = bench_trace.window(events)
        run.ops_by_device = lambda: [_ops(run, d) for d in run.devices]
        memo = {}

        def metric(name):
            if name not in memo:
                memo[name] = bench_module(f"metrics.{name}").read(run)
            return memo[name]

        run.metric = metric
        metrics = {}
        for spec in cell.per_layer:
            v = metric(spec["name"])
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
            else:
                log(f"warn: {spec['name']} found nothing to read in this "
                    "trace and is left out")
        busy = [bench_trace.busy_ns(_ops(run, d)) for d in run.devices]
        lo, hi = run.window
        dev_info = {"busy_s": float(np.mean(busy)) / 1e9,
                    "window_s": (hi - lo) / 1e9}
        ops0 = _ops(run, run.devices[0])
        result["breakdown"] = {
            "device_ops": bench_trace.top_ops(ops0),
            "idle_gaps": bench_trace.name_gaps(
                events, bench_trace.gaps(ops0, lo, hi))}
    else:
        per_fit = window_s / len(fits)
        metrics = {"fit_s": per_fit, "peak_hbm_gb": peak_bytes / 1e9,
                   "setup_s": setup_s}
        metrics = {spec["name"]: {"value": metrics[spec["name"]],
                                  "unit": spec["unit"]}
                   for spec in cell.end_to_end}
        dev_info = {}

    # The program's state goes before the reference runs: the peak above
    # is the program's alone, and the reference needs room of its own.
    blocks = bench_data.blocks(job.D, job.aux)
    del job
    gc.collect()
    checks = job_kind.check(mix, cfg, blocks, fits, cell.limits,
                            bench_module("reference"), log)
    result["correct"] = bool(checks) and all(
        v <= lim for v, lim in checks.values())
    result["metrics"] = metrics
    result["device"] = {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs),
                        "memory_peak_bytes": int(peak_bytes), **dev_info}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def _peak(kind: str):
    try:
        return bench_module("work").peaks(kind)
    except ValueError:
        return None          # no roofline shares off the table's devices


def _ops(run, device: str):
    lo, hi = run.window
    return run.trace.clip_ops(run.events, device, lo, hi)

