"""Multi-tenant service load benchmark — BENCH_service.json (DESIGN.md §15).

Closed-loop load generator against the networked fit front end
(:mod:`repro.service.frontend`) with seeded chaos, proving the service's
robustness contract rather than raw speed:

  * FIVE concurrent tenants with different behaviour profiles — a warm
    ridge tenant, a lasso mu-grid tenant, a bursty over-quota tenant
    (drives admission rejections), a cold logistic tenant with deadlines
    (drives the degrade path when the seeded chaos stalls the cold
    backend), and a flaky tenant that repeatedly crashes mid-flight
    (client kill); plus two hostile non-tenant connections, a slow-loris
    and a corrupt-frame sender, that must be severed without touching
    anyone else.
  * The seeded :class:`~repro.cluster.chaos.FaultInjector` stalls the
    cold-solve backend (``slow`` process faults) so cold requests blow
    their budget and are answered ``degraded`` from cached Gram stats —
    and enough of them trip the circuit breaker, which is the designed
    cascade, not a failure.
  * ZERO LOST REQUESTS is the acceptance bar, checked from both sides:
    server-side every decoded fit has exactly one terminal response and
    nothing stays in flight; client-side every healthy tenant got back
    exactly as many terminal responses as it submitted, and no response
    arrived later than its request's deadline plus a scheduling grace.

Latency is recorded client-side (wire included) and split warm
(gram-path problems served from cached stats) vs cold (full solves).

Observability gates (DESIGN.md §16): the front end runs with a live
:class:`~repro.obs.Observability` plane and a scrape endpoint, and the
run must additionally demonstrate (a) one MULTI-PROCESS trace — a
spawned client process whose ``client.fit`` span is the ancestor of the
frontend's ``frontend.cold_solve`` span under one trace_id; (b) live
``/metrics.json`` scrape samples taken DURING the load whose counters
are monotone and reconcile with the final snapshot; (c) an SLO
burn-rate evaluation where the zero-lost and availability objectives
pass; (d) at least one flight-recorder incident dumped by the seeded
breaker trip and loadable by ``obs_report``; and (e) observability must
be TRANSPARENT — the same fits through an obs-on and an obs-off front
end produce bit-identical solutions.
"""
from __future__ import annotations

import glob
import json
import os
import socket
import struct
import tempfile
import threading
import time

import numpy as np

JSON_PATH = None          # set by benchmarks.run when --json is given

#: responses later than deadline + this grace count as overruns; the
#: grace covers solver-loop scheduling + the degraded fallback solve on
#: a timeshared CI VM, not algorithmic slack
GRACE_S = 1.5


def _dataset(m, n, seed):
    rng = np.random.default_rng(seed)
    D = rng.standard_normal((m, n)).astype(np.float32)
    w = rng.standard_normal(n).astype(np.float32)
    b = np.sign(D @ w + 0.1).astype(np.float32)     # ±1 labels
    return D, b


class _Tenant(threading.Thread):
    """One closed-loop tenant: submit, wait for the terminal response,
    record (status, latency), repeat until the wall deadline."""

    def __init__(self, name, address, body, stop_at):
        super().__init__(name=f"tenant-{name}", daemon=True)
        self.tenant = name
        self.address = address
        self.body = body
        self.stop_at = stop_at
        self.records = []          # dicts: problem/status/latency_s/...
        self.submitted = 0
        self.received = 0
        self.error = None

    def run(self):
        from repro.service.frontend import FitServiceClient
        try:
            with FitServiceClient(self.address, tenant=self.tenant) as c:
                while time.monotonic() < self.stop_at:
                    self.body(self, c)
        except Exception as e:      # noqa: BLE001 — surfaced in acceptance
            self.error = f"{type(e).__name__}: {e}"

    def fit(self, client, problem, fingerprint, deadline_s=None, **kw):
        self.submitted += 1
        t0 = time.monotonic()
        r = client.fit(problem, fingerprint, timeout=60.0,
                       deadline_s=deadline_s, **kw)
        lat = time.monotonic() - t0
        self.received += 1
        self.records.append({"problem": problem, "status": r["status"],
                             "latency_s": lat, "deadline_s": deadline_s})
        return r


def _flaky_tenant(address, fingerprint, stop_at, rounds_done):
    """Client-kill chaos: open a connection, fire requests, slam the
    socket shut without reading. Its responses become undeliverable —
    accounted server-side, never blocking a sibling."""
    from repro.service.frontend import FitServiceClient
    while time.monotonic() < stop_at:
        try:
            c = FitServiceClient(address, tenant="flaky")
            for _ in range(2):
                c.fit_async("ridge", fingerprint, mu=1.0)
            c.conn.close()          # crash with responses in flight
            rounds_done.append(2)
        except Exception:           # noqa: BLE001 — dying IS the job
            pass
        time.sleep(0.15)


def _hostile_connections(address):
    """One slow-loris (partial header, stall) and one corrupt-frame
    sender. Returns the open sockets so the caller controls lifetime."""
    loris = socket.create_connection(address)
    loris.sendall(struct.pack(">Q", 4096)[:3])
    corrupt = socket.create_connection(address)
    corrupt.sendall(struct.pack(">Q", 24) + b"\xa5" * 24)
    return [loris, corrupt]


# -- observability gates (DESIGN.md §16) ------------------------------------

def _traced_client_proc(address, fingerprint, out_path):
    """Spawn target: a SEPARATE process running one traced cold fit, so
    the merged timeline provably crosses a process boundary. Ships its
    trace events back through a JSON file (no shared memory). A client
    needs no accelerator: it is pinned to the CPU before anything imports
    jax, so it never claims a chip its parent holds."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from repro.obs.trace import Tracer
    from repro.service.frontend import FitServiceClient
    tracer = Tracer(enabled=True, process_name="client")
    with FitServiceClient(tuple(address), tenant="traced",
                          tracer=tracer) as c:
        r = c.fit("logistic", fingerprint, iters=100, deadline_s=30.0,
                  timeout=120.0)
    with open(out_path, "w") as f:
        json.dump({"pid": os.getpid(), "status": r["status"],
                   "events": tracer.events()}, f)


def _run_traced_client(address, fingerprint, rundir, timeout_s=120.0):
    """Run the traced client in a spawned process; returns its shipped
    {pid, status, events} doc, or None if it failed/hung."""
    import multiprocessing as mp
    out_path = os.path.join(rundir, "traced_client.json")
    p = mp.get_context("spawn").Process(
        target=_traced_client_proc,
        args=(tuple(address), fingerprint, out_path), daemon=True)
    p.start()
    p.join(timeout=timeout_s)
    if p.is_alive():
        p.terminate()
        p.join(timeout=5.0)
        return None
    if p.exitcode != 0 or not os.path.exists(out_path):
        return None
    with open(out_path) as f:
        return json.load(f)


def _scrape_json(url, timeout=5.0):
    import urllib.request
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


def _scrape_text(url, timeout=5.0):
    import urllib.request
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def _counter_total(snap, name):
    return sum(c.get("value", 0) for c in snap.get("counters", [])
               if c.get("name") == name)


def _trace_connectivity(events):
    """Find a client.fit span whose trace contains a frontend.cold_solve
    DESCENDANT — the client -> frontend -> cold-executor chain of the
    acceptance criterion — and report the trace's shape."""
    from repro.obs.trace import is_ancestor
    for ev in events:
        if ev.get("ph") != "X" or ev.get("name") != "client.fit":
            continue
        args = ev.get("args") or {}
        tid, sid = args.get("trace_id"), args.get("span_id")
        if not tid:
            continue
        in_trace = [e for e in events if e.get("ph") == "X"
                    and (e.get("args") or {}).get("trace_id") == tid]
        for cold in in_trace:
            if (cold.get("name") == "frontend.cold_solve"
                    and is_ancestor(events, sid,
                                    cold["args"]["span_id"])):
                return {"connected": True, "trace_id": tid,
                        "processes": len({e.get("pid")
                                          for e in in_trace}),
                        "spans": sorted({e["name"] for e in in_trace})}
    return {"connected": False, "trace_id": None, "processes": 0,
            "spans": []}


def _obs_transparency(D, b):
    """Run identical fits through an obs-ON and an obs-OFF front end;
    observability must not perturb a single output bit."""
    from repro.obs import Observability
    from repro.service.frontend import FitFrontend, FitServiceClient

    def one(obs):
        fe = FitFrontend(window=4, flush_interval_s=0.01, obs=obs)
        try:
            with FitServiceClient(fe.address, tenant="xcheck") as c:
                fp = c.register(D, b)
                out = {}
                for problem, kw in (("ridge", {"mu": 1.0}),
                                    ("logistic", {"iters": 100})):
                    r = c.fit(problem, fp, timeout=120.0, **kw)
                    out[problem] = (r["status"],
                                    None if r["x"] is None
                                    else np.asarray(r["x"]))
            return out
        finally:
            fe.close()

    with tempfile.TemporaryDirectory(prefix="obs_xcheck_") as d:
        obs = Observability(dir=d, process_name="xcheck")
        try:
            with_obs = one(obs)
        finally:
            obs.finish()
    without = one(None)
    identical = all(
        with_obs[k][0] == without[k][0] == "ok"
        and with_obs[k][1] is not None and without[k][1] is not None
        and with_obs[k][1].tobytes() == without[k][1].tobytes()
        for k in with_obs)
    return {"problems": sorted(with_obs),
            "statuses": {k: with_obs[k][0] for k in with_obs},
            "bit_identical": bool(identical)}


def _pct(vals, q):
    return None if not vals else round(
        float(np.percentile(np.asarray(vals), q)) * 1e3, 3)   # ms


def _latency_summary(records, problems, statuses=("ok",)):
    vals = [r["latency_s"] for r in records
            if r["problem"] in problems and r["status"] in statuses]
    return {"count": len(vals), "p50_ms": _pct(vals, 50),
            "p99_ms": _pct(vals, 99),
            "max_ms": _pct(vals, 100)}


def run(rows, quick: bool = False):
    from repro.cluster.chaos import FaultEvent, FaultInjector
    from repro.launch.obs_report import summarize_incident
    from repro.obs import Observability
    from repro.service.frontend import (
        SERVICE_DATA_PLANE,
        FitFrontend,
        FitServiceClient,
    )

    seed = 0
    if quick:
        m, n, duration_s = 1500, 24, 2.5
    else:
        m, n, duration_s = 8000, 48, 8.0
    D, b = _dataset(m, n, seed)
    mu_grid = [0.05, 0.1, 0.5, 1.0]

    # seeded chaos: slow faults against the cold backend, spread over
    # the run's expected request-sequence range so they fire on distinct
    # cold solves rather than piling onto the first one
    rng = np.random.default_rng(seed)
    slow_points = sorted(int(p) for p in
                         rng.integers(5, 40 * int(duration_s), size=4))
    chaos = FaultInjector(
        [FaultEvent(p, "svc", "slow", 1200.0) for p in slow_points],
        data_plane=SERVICE_DATA_PLANE)

    # live observability plane: run-dir artifacts + flight recorder +
    # an OS-assigned scrape port sampled while the load is running
    rundir = tempfile.mkdtemp(prefix="bench_service_obs_")
    obs = Observability(dir=rundir, process_name="frontend")
    fe = FitFrontend(window=8, flush_interval_s=0.01, max_queue=64,
                     tenant_rate=40.0, tenant_burst=5.0,
                     default_deadline_s=20.0, cold_budget_s=0.4,
                     breaker_threshold=3, breaker_reset_s=1.0,
                     frame_deadline_s=1.0, chaos=chaos,
                     obs=obs, scrape_port=0)
    sampler_stop = threading.Event()
    try:
        with FitServiceClient(fe.address, tenant="setup") as setup:
            fp = setup.register(D, b)
            # untimed warmup: pay jit compilation for every path the
            # tenants exercise before the clock starts
            setup.fit("ridge", fp, mu=1.0, timeout=120.0)
            setup.fit("lasso", fp, mu=0.1, iters=200, timeout=120.0)
            setup.fit("logistic", fp, iters=100, timeout=120.0)

        # multi-process trace: a SPAWNED client runs one cold fit before
        # the chaos window opens (fit_seq 4 < first slow point), ships
        # its client-side spans back, and they merge with the frontend's
        # into one timeline under one trace_id
        traced = _run_traced_client(fe.address, fp, rundir)
        if traced is not None:
            fe.tracer.add_events(traced["events"], process_name="client",
                                 pid=traced["pid"])

        stop_at = time.monotonic() + duration_s

        def warm_body(t, c):
            t.fit(c, "ridge", fp, mu=1.0)
            time.sleep(0.02)

        def grid_body(t, c):
            mu = mu_grid[t.submitted % len(mu_grid)]
            t.fit(c, "lasso", fp, mu=mu, iters=200)
            time.sleep(0.02)

        def greedy_body(t, c):
            # burst past the token bucket on purpose, then drain
            rids = [c.fit_async("ridge", fp, mu=1.0) for _ in range(8)]
            t.submitted += len(rids)
            for rid in rids:
                t0 = time.monotonic()
                r = c.result(rid, timeout=60.0)
                t.received += 1
                t.records.append({"problem": "ridge",
                                  "status": r["status"],
                                  "latency_s": time.monotonic() - t0,
                                  "deadline_s": None})
            time.sleep(0.1)

        def cold_body(t, c):
            # every 4th request carries an unmeetable deadline so the
            # mid-queue expiry path shows up in every run
            if t.submitted % 4 == 3:
                t.fit(c, "ridge", fp, mu=1.0, deadline_s=0.002)
            else:
                t.fit(c, "logistic", fp, iters=100, deadline_s=4.0)
            time.sleep(0.02)

        tenants = [
            _Tenant("warm", fe.address, warm_body, stop_at),
            _Tenant("grid", fe.address, grid_body, stop_at),
            _Tenant("greedy", fe.address, greedy_body, stop_at),
            _Tenant("cold", fe.address, cold_body, stop_at),
        ]
        flaky_rounds = []
        flaky = threading.Thread(
            target=_flaky_tenant, args=(fe.address, fp, stop_at,
                                        flaky_rounds),
            daemon=True, name="tenant-flaky")
        t_start = time.monotonic()

        # live scrape sampling DURING the run (acceptance: the samples
        # must be monotone and reconcile with the final snapshot)
        scrape_samples = []

        def _sample_loop():
            url = fe.scrape.url("/metrics.json")
            while not sampler_stop.is_set():
                try:
                    snap = _scrape_json(url)
                    scrape_samples.append({
                        "t_s": round(time.monotonic() - t_start, 3),
                        "responses": _counter_total(
                            snap, "service.responses"),
                        "fit_seen": _counter_total(
                            snap, "service.fit_seen")})
                except Exception:       # noqa: BLE001 — sampling is
                    pass                # best-effort; gate counts hits
                sampler_stop.wait(0.15)

        sampler = threading.Thread(target=_sample_loop, daemon=True,
                                   name="scrape-sampler")
        sampler.start()
        for t in tenants:
            t.start()
        flaky.start()
        time.sleep(duration_s * 0.3)
        hostile = _hostile_connections(fe.address)
        for t in tenants:
            t.join(timeout=120.0)
        flaky.join(timeout=30.0)
        for s in hostile:
            s.close()
        wall_s = time.monotonic() - t_start

        # let the victim responses / severs finish accounting
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            sc = fe.status_counts()
            if sc["in_flight"] == 0 and sc["severed"] >= 2:
                break
            time.sleep(0.05)
        sampler_stop.set()
        sampler.join(timeout=5.0)

        counts = fe.status_counts()
        zero_lost_server = fe.zero_lost_requests()
        records = [r for t in tenants for r in t.records]
        tenant_errors = {t.tenant: t.error for t in tenants if t.error}
        client_balanced = (not tenant_errors and all(
            t.submitted == t.received for t in tenants))
        overruns = [r for r in records
                    if r["deadline_s"] is not None
                    and r["latency_s"] > r["deadline_s"] + GRACE_S]
        status_mix = {s: sum(1 for r in records if r["status"] == s)
                      for s in ("ok", "degraded", "deadline", "rejected",
                                "error")}
        warm_lat = _latency_summary(records, ("ridge", "lasso"))
        cold_lat = _latency_summary(records, ("logistic",))
        degraded_why = {k: int(v) for k, v in fe.metrics.labeled(
            "service.degraded", "why").items()}
        healthy_rps = round(sum(t.received for t in tenants) / wall_s, 1)

        # -- observability gates (DESIGN.md §16) ------------------------
        # (a) multi-process trace connectivity
        trace_info = _trace_connectivity(fe.tracer.events())
        trace_info["client_status"] = (None if traced is None
                                       else traced["status"])
        trace_connected = bool(trace_info["connected"]
                               and trace_info["processes"] >= 2)

        # (b) live scrape reconciliation: counters sampled mid-run are
        # monotone, and a final quiesced scrape equals the authoritative
        # server-side accounting
        terminal_total = sum(counts.get(s, 0) for s in
                             ("ok", "degraded", "deadline", "rejected",
                              "error"))
        resp_series = [s["responses"] for s in scrape_samples]
        seen_series = [s["fit_seen"] for s in scrape_samples]
        monotone = (all(a <= b for a, b in
                        zip(resp_series, resp_series[1:]))
                    and all(a <= b for a, b in
                            zip(seen_series, seen_series[1:])))
        try:
            final_snap = _scrape_json(fe.scrape.url("/metrics.json"))
            prom_text = _scrape_text(fe.scrape.url("/metrics"))
            healthz = _scrape_json(fe.scrape.url("/healthz"))
            slo_http = _scrape_json(fe.scrape.url("/slo"))
            scrape_error = None
        except Exception as e:          # noqa: BLE001 — gate fails below
            final_snap, prom_text, healthz, slo_http = {}, "", {}, {}
            scrape_error = f"{type(e).__name__}: {e}"
        final_matches = (
            _counter_total(final_snap, "service.responses")
            == terminal_total
            and _counter_total(final_snap, "service.fit_seen")
            == counts["fit_seen"])
        live_scrape = {
            "samples": len(scrape_samples),
            "monotone": bool(monotone),
            "final_matches_server": bool(final_matches),
            "prom_text_served": "service_responses_total" in prom_text,
            "healthz_status": healthz.get("status"),
            "slo_route_served": bool(slo_http.get("objectives")),
            "error": scrape_error,
            "series": scrape_samples,
        }
        scrape_ok = bool(len(scrape_samples) >= 3 and monotone
                         and final_matches
                         and live_scrape["prom_text_served"]
                         and healthz.get("status") == "ok"
                         and live_scrape["slo_route_served"])

        # (c) SLO burn-rate evaluation over the run
        slo_final = fe.slo_snapshot()
        slo_by_name = {o["name"]: o for o in slo_final["objectives"]}
        slo_pass = (slo_by_name.get("zero_lost", {}).get("ok") is True
                    and slo_by_name.get("availability", {}).get("ok")
                    is True)

        # (d) flight-recorder incident from the seeded breaker trip,
        # loaded back through obs_report
        incident_summaries = []
        for path in sorted(glob.glob(
                os.path.join(rundir, "incidents", "incident-*.json"))):
            try:
                incident_summaries.append(summarize_incident(path))
            except Exception as e:      # noqa: BLE001 — gate fails below
                incident_summaries.append({"path": path,
                                           "error": str(e)})
        breaker_incidents = [s for s in incident_summaries
                             if s.get("reason") == "breaker_trip"]

        # (e) obs-on x bit-identical to obs-off
        transparency = _obs_transparency(D, b)

        acceptance = {
            "criterion": (
                "every fit request decoded by the service receives "
                "exactly one terminal response (ok/degraded/deadline/"
                "rejected/error) and none is left in flight; every "
                "healthy tenant's submitted == received; no response "
                f"arrives later than its deadline + {GRACE_S}s grace; "
                "the seeded chaos demonstrably exercised every degrade "
                "path: slow cold backend -> degraded answers, bursty "
                "tenant -> quota rejections, unmeetable deadlines -> "
                "mid-queue expiry, and both hostile connections "
                "(slow-loris, corrupt frame) severed without touching "
                "sibling tenants; PLUS the observability gates: a "
                "multi-process trace connects client -> frontend -> "
                "cold executor under one trace_id, live scrape samples "
                "taken during the run reconcile with the final "
                "snapshot, the zero-lost and availability SLOs pass "
                "their burn-rate evaluation, the seeded breaker trip "
                "dumped a flight-recorder incident loadable by "
                "obs_report, and obs-on is bit-identical to obs-off"),
            "zero_lost_requests": bool(zero_lost_server
                                       and client_balanced),
            "server_accounting_balanced": bool(zero_lost_server),
            "client_accounting_balanced": bool(client_balanced),
            "tenant_errors": tenant_errors,
            "deadline_overruns": len(overruns),
            "degrade_path_exercised": bool(status_mix["degraded"] >= 1),
            "rejection_path_exercised": bool(status_mix["rejected"] >= 1),
            "deadline_path_exercised": bool(status_mix["deadline"] >= 1),
            "hostiles_severed": bool(counts["severed"] >= 2),
            "trace_connected": trace_connected,
            "live_scrape_reconciled": scrape_ok,
            "slo_pass": bool(slo_pass),
            "incident_captured": bool(len(breaker_incidents) >= 1),
            "obs_transparent": bool(transparency["bit_identical"]),
        }
        acceptance["pass"] = bool(
            acceptance["zero_lost_requests"]
            and not overruns
            and acceptance["degrade_path_exercised"]
            and acceptance["rejection_path_exercised"]
            and acceptance["deadline_path_exercised"]
            and acceptance["hostiles_severed"]
            and acceptance["trace_connected"]
            and acceptance["live_scrape_reconciled"]
            and acceptance["slo_pass"]
            and acceptance["incident_captured"]
            and acceptance["obs_transparent"])

        rows.append(f"service_warm_latency,"
                    f"{(warm_lat['p50_ms'] or 0) * 1e3:.0f},"
                    f"p99={warm_lat['p99_ms']}ms_n{warm_lat['count']}")
        rows.append(f"service_cold_latency,"
                    f"{(cold_lat['p50_ms'] or 0) * 1e3:.0f},"
                    f"p99={cold_lat['p99_ms']}ms_n{cold_lat['count']}")
        rows.append(f"service_throughput,0,{healthy_rps}rps_"
                    f"{counts['fit_seen']}seen")
        rows.append(
            "service_mix,0,"
            f"ok{status_mix['ok']}_deg{status_mix['degraded']}_"
            f"rej{status_mix['rejected']}_ddl{status_mix['deadline']}_"
            f"err{status_mix['error']}_sev{counts['severed']}")
        rows.append("service_zero_lost,0,"
                    + ("ok" if acceptance["pass"] else "VIOLATED"))
        rows.append(
            "service_obs,0,"
            f"trace{'_ok' if trace_connected else '_FAIL'}_"
            f"scrape{len(scrape_samples)}"
            f"{'ok' if scrape_ok else 'FAIL'}_"
            f"slo{'ok' if slo_pass else 'FAIL'}_"
            f"inc{len(breaker_incidents)}_"
            f"xparent{'ok' if transparency['bit_identical'] else 'FAIL'}")

        if JSON_PATH:
            from benchmarks.run import host_meta
            payload = {
                "generated_by": "benchmarks/service_load.py",
                "host_meta": host_meta(),
                "quick": quick,
                "seed": seed,
                "problem": {"m": m, "n": n, "duration_s": duration_s},
                "chaos": {
                    "slow_cold_backend_at_seq": slow_points,
                    "slow_ms": 1200.0,
                    "client_kill_rounds": len(flaky_rounds),
                    "hostile_connections": ["slow_loris",
                                            "corrupt_frame"],
                },
                "tenants": [
                    {"tenant": t.tenant, "submitted": t.submitted,
                     "received": t.received, "error": t.error}
                    for t in tenants],
                "warm_latency": warm_lat,
                "cold_latency": cold_lat,
                "healthy_responses_per_s": healthy_rps,
                "status_mix_client": status_mix,
                "status_counts_server": counts,
                "degraded_why": degraded_why,
                "breaker": fe.breaker.snapshot(),
                "admission": fe.admission.snapshot(),
                "observability": {
                    "rundir": rundir,
                    "trace": trace_info,
                    "live_scrape": live_scrape,
                    "slo": slo_final,
                    "incidents": incident_summaries,
                    "transparency": transparency,
                },
                "acceptance": acceptance,
            }
            with open(JSON_PATH, "w") as f:
                json.dump(payload, f, indent=2)
                f.write("\n")
    finally:
        sampler_stop.set()
        fe.close()
        obs.finish()
