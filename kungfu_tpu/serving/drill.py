"""Scripted serving drill — the serve-mode chaos smoke + bench probe.

Launches a real serving fleet (`python -m kungfu_tpu.serving`) on CPU with a
`crash_serve` fault armed, drives it with a threaded client, and asserts the
serving contract end to end:

  1. failover: a worker dies MID-STREAM with requests in flight; every
     request still completes (zero drops), the router journals the
     re-queues, the victim rejoins from a live peer's weights
     (`rank_rejoined` with recovery_rung=buddy) in under the rejoin budget,
     and client-visible p99 latency stays under the bound
  2. determinism: a prompt replayed after the failover yields byte-identical
     tokens (greedy decode + identical replica weights — the re-queue path
     changed nothing observable)
  3. autoscale: an idle window commits a scale-DOWN through the config
     server's conditional PUT, a burst then commits a scale-UP; both are
     read back via the cheap /health document-version endpoint

Returns a metrics dict (steady tokens/sec, TTFT/decode percentiles,
failover_requeue_s, rejoin rung/latency: CPU-drill numbers, not measured
on the chip; the serving cells of benchmark/ are).  Exit-code semantics live in the chaos CLI wrapper
(`python -m kungfu_tpu.chaos --serve-drill`).
"""
from __future__ import annotations

import glob
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional

from ..monitor.journal import filter_events


def _percentile(xs: List[float], p: float) -> Optional[float]:
    if not xs:
        return None
    xs = sorted(xs)
    k = min(len(xs) - 1, max(0, int(round(p * (len(xs) - 1)))))
    return xs[k]


def _journal_events(journal_dir: str) -> List[dict]:
    events = []
    for path in sorted(glob.glob(os.path.join(journal_dir, "journal-*.jsonl"))):
        with open(path, encoding="utf-8") as f:
            for line in f:
                try:
                    events.append(json.loads(line))
                except ValueError:
                    continue
    return events


def _poll_requests(telemetry_url: str, want_completed: int,
                   deadline_s: float = 45.0) -> Optional[dict]:
    """Poll the fleet /requests assembler until it holds `want_completed`
    completed timelines (late-arriving spans merge in, so keep polling
    until the view is consistent); returns the final report or None."""
    t0 = time.monotonic()
    report = None
    while time.monotonic() - t0 < deadline_s:
        try:
            with urllib.request.urlopen(telemetry_url + "/requests",
                                        timeout=10) as r:
                report = json.loads(r.read().decode())
        except (OSError, ValueError):
            time.sleep(0.5)
            continue
        if report.get("completed_total", 0) >= want_completed and not any(
                t.get("partial") for t in report.get("requests", ())):
            return report
        time.sleep(0.5)
    return report


def _assert_stitched(report: dict, requests: int) -> List[str]:
    """The trace drill's acceptance: 100% of completed requests stitched
    across >= 2 processes with zero orphan spans; failover victims carry
    the requeue + warm-graft spans."""
    failures: List[str] = []
    rows = report.get("requests") or []
    if report.get("completed_total", 0) < requests:
        failures.append(
            f"only {report.get('completed_total')}/{requests} requests "
            "assembled into completed traces")
    not_stitched = [t["req_id"] for t in rows if len(t.get("processes", ())) < 2]
    if not_stitched:
        failures.append(f"single-process traces (not stitched): {not_stitched}")
    orphaned = [t["req_id"] for t in rows
                if t.get("orphans", 0) or t.get("partial")]
    if orphaned:
        failures.append(f"partial/orphaned traces: {orphaned}")
    flagged = (report.get("tail") or {}).get("flagged") or []
    victims = [t for t in flagged if t.get("requeues", 0) > 0]
    if not victims:
        failures.append("tail sampler retained no failover-touched request")
    for t in victims:
        names = {s["name"] for s in t.get("spans", ())}
        if not {"requeue", "warm_graft"} <= names:
            failures.append(
                f"failover victim {t['req_id']} trace lacks the requeue/"
                f"warm_graft spans (saw {sorted(names)})")
    return failures


def run_induced_tail_drill(timeout_s: float = 240.0, slow_ms: int = 600,
                           start_after_s: float = 35.0,
                           threshold_ms: float = 250.0,
                           max_new: int = 16) -> Dict:
    """The induced-tail half of `--trace-drill`: a CLEAN disaggregated
    fleet (no kills) with `slow_serve@phase=kv_ship:start_after=S` armed —
    ships pass undelayed for the first S seconds (boot churn + jit
    compiles), then every ship pays `slow_ms`.  A tight request-latency
    SLO must breach with the journaled `slo_breach` naming kv_ship as the
    dominant phase (the attribution windows on the violation start — the
    requests that CAUSED it).  The compile era can honestly breach the
    rule too (first requests take seconds); that breach clears during the
    post-warmup fast window (clear_s << start_after), and the drill
    asserts on the breach the INDUCED window drives."""
    failures: List[str] = []
    metrics: Dict = {"slow_ms": slow_ms, "start_after_s": start_after_s,
                     "threshold_ms": threshold_ms}
    tmp = tempfile.mkdtemp(prefix="kft-trace-slo-drill-")
    jdir = os.path.join(tmp, "journal")
    slo_file = os.path.join(tmp, "slo.json")
    with open(slo_file, "w") as f:
        json.dump({"rules": [{
            "name": "drill_request_latency_p99",
            "metric": "hist:request_latency_ms:p99",
            "op": "<=", "threshold": threshold_ms,
            "sustain_s": 3.0, "clear_s": 4.0, "severity": "page",
            "description": "trace drill: request p99 stays under the "
                           "threshold (the induced kv_ship delay breaches)",
        }]}, f)
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        KFT_FAULT_PLAN=(f"slow_serve@phase=kv_ship:ms={slow_ms}"
                        f":tier=prefill:start_after={start_after_s:g}"),
        KFT_JOURNAL_DIR=jdir,
        KFT_SLO_FILE=slo_file,
        KFT_TS_INTERVAL_S="0.5",
        KFT_TRACE_BUFFER="65536",
    )
    env.pop("XLA_FLAGS", None)
    cmd = [
        sys.executable, "-m", "kungfu_tpu.serving", "-np", "3",
        "--min-size", "3", "--max-size", "3", "--platform", "cpu",
        "--preset", "tiny", "--slots", "2", "--prefill-ranks", "1",
        "--no-autoscale", "--telemetry",
        "--timeout", str(int(timeout_s)), "-q",
    ]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    lines: List[str] = []
    pump = threading.Thread(
        target=lambda: [lines.append(ln) for ln in proc.stdout], daemon=True
    )
    pump.start()

    def find(pattern: str, deadline_s: float = 60.0) -> Optional[str]:
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            for line in list(lines):
                m = re.search(pattern, line)
                if m:
                    return m.group(1)
            if proc.poll() is not None:
                return None
            time.sleep(0.1)
        return None

    breach = None
    sent = [0]
    try:
        serve_url = find(r"SERVE_URL: (\S+)")
        if not serve_url:
            failures.append("fleet never printed SERVE_URL")
            return {"ok": False, "failures": failures,
                    "output_tail": "".join(lines)[-3000:], **metrics}
        client = _Client(serve_url)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 90:
            try:
                with urllib.request.urlopen(serve_url + "/stats",
                                            timeout=3) as r:
                    st = json.loads(r.read().decode())
                if sum(1 for w in st["workers"].values()
                       if w["healthy"]) >= 3:
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.25)

        # two closed-loop clients keep fresh latency samples flowing:
        # ships stay undelayed through the start_after grace (compile +
        # warmup), then pay the kv_ship delay and sustain the violation
        stop = threading.Event()

        def loop(i: int) -> None:
            k = 0
            while not stop.is_set():
                try:
                    client.generate([1 + (k + i) % 5, 2, 3], max_new,
                                    timeout_s=60)
                    sent[0] += 1
                except OSError:
                    time.sleep(0.2)
                k += 1

        clients = [threading.Thread(target=loop, args=(i,), daemon=True)
                   for i in range(2)]
        for t in clients:
            t.start()
        # wait for the breach the INDUCED window drives (a compile-era
        # breach may come first — it clears during the fast window and
        # carries a different attribution; keep the last breach as the
        # fallback evidence either way)
        deadline = time.monotonic() + min(150.0, timeout_s - 10)
        while time.monotonic() < deadline:
            for e in _journal_events(jdir):
                if (e.get("event") == "slo_breach"
                        and "request_latency" in str(e.get("rule", ""))):
                    breach = e
                    if e.get("dominant_phase") == "kv_ship":
                        break
            if breach is not None and breach.get("dominant_phase") == "kv_ship":
                break
            time.sleep(0.5)
        stop.set()
        for t in clients:
            t.join(timeout=70)
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        pump.join(timeout=5)

    metrics["requests_sent"] = sent[0]
    events = _journal_events(jdir)
    if not any(e.get("event") == "chaos_slow_serve" for e in events):
        failures.append("the slow_serve@phase=kv_ship window never armed "
                        "(no chaos_slow_serve journal event)")
    if breach is None:
        failures.append("no slo_breach journal event for the "
                        "request-latency rule despite the induced "
                        "kv_ship delay")
    else:
        metrics["slo_breach_value_ms"] = breach.get("value")
        metrics["slo_breach_dominant_phase"] = breach.get("dominant_phase")
        metrics["slo_breach_phase_fracs"] = breach.get("phase_p99_fracs")
        if breach.get("dominant_phase") != "kv_ship":
            failures.append(
                "SLO breach attributed the wrong dominant phase: "
                f"{breach.get('dominant_phase')!r} (induced delay was "
                "in kv_ship)")
    return {"ok": not failures, "failures": failures,
            "output_tail": "".join(lines)[-3000:] if failures else "",
            **metrics}


def run_fairness_drill(timeout_s: float = 300.0,
                       burst_plan: str = "burst@tenant=bursty:rps=20:secs=3",
                       threshold_ms: float = 30000.0,
                       batch_requests: int = 9, batch_new: int = 32,
                       sensitive_requests: int = 3,
                       decode_delay_ms: int = 40) -> Dict:
    """Multi-tenant QoS drill (`python -m kungfu_tpu.chaos --fairness-drill`,
    docs/serving.md "Multi-tenancy & QoS"): a 3-rank CPU fleet with three
    tenant classes driven through an adversarial mix, asserting the whole
    tenancy contract end to end:

      1. rate limiting: a `burst@tenant=bursty:rps=R:secs=S` traffic shape
         (parsed from the chaos fault grammar, executed CLIENT-side — burst
         never arms a worker injector) fires well past the bursty tenant's
         token bucket; the router must journal `tenant_rate_limited` and
         the client must see 429s, while every ADMITTED request completes
      2. priority preemption: low-priority batch traffic fills every engine
         slot, then sensitive-tenant requests arrive; a worker must evict a
         batch slot (`slot_preempted`), serve the sensitive request, and
         warm-readmit the victim (`preempted_readmitted`)
      3. determinism: every preempted-then-readmitted batch prompt replays
         to byte-identical tokens (greedy decode; the generated prefix
         re-enters as a prefix-cache graft, not recomputation)
      4. isolation: the sensitive tenant's client-measured p99 stays inside
         its per-tenant SLO rule (`tenant=sensitive` selector on the
         labeled `hist:request_latency_ms[sensitive]:p99` series) and the
         rule never journals `slo_breach`
      5. zero drops: router `dropped` stays 0 — QoS pressure degrades and
         defers, it never silently loses admitted work
    """
    failures: List[str] = []
    metrics: Dict = {"burst_plan": burst_plan, "threshold_ms": threshold_ms}
    from ..chaos.plan import parse_fault_plan
    bursts = parse_fault_plan(burst_plan).burst_faults()
    if not bursts:
        return {"ok": False, "failures": [f"no burst fault in plan "
                                          f"{burst_plan!r}"], **metrics}

    tmp = tempfile.mkdtemp(prefix="kft-fairness-drill-")
    jdir = os.path.join(tmp, "journal")
    tenants_file = os.path.join(tmp, "tenants.json")
    slo_file = os.path.join(tmp, "slo.json")
    with open(tenants_file, "w") as f:
        json.dump({
            "default": {"weight": 1.0, "priority": 1},
            "tenants": {
                # the protected tenant: 4x scheduling share, highest
                # priority (preempts batch at the slot layer), SLO-ruled
                "sensitive": {"weight": 4.0, "priority": 2},
                # best-effort backfill: lowest priority = preemption victim
                "batch": {"weight": 1.0, "priority": 0},
                # the adversary: same class as batch but rate-limited at
                # the front door (4 req/s, burst of 6)
                "bursty": {"weight": 1.0, "priority": 0,
                           "rate": 4.0, "burst": 6.0},
            },
        }, f)
    with open(slo_file, "w") as f:
        json.dump({"rules": [{
            "name": "sensitive_latency_p99",
            "metric": "hist:request_latency_ms:p99",
            "tenant": "sensitive",
            "op": "<=", "threshold": threshold_ms,
            "sustain_s": 2.0, "clear_s": 3.0, "severity": "page",
            "description": "fairness drill: the sensitive tenant's p99 "
                           "stays inside its SLO while batch + bursty "
                           "traffic contends",
        }]}, f)
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        KFT_TENANTS_FILE=tenants_file,
        # the burst shape rides the normal fault-plan env to prove it
        # composes with a REAL worker fault in the same string: the
        # decode delay holds batch requests in their slots long enough
        # for the sensitive wave to find every slot occupied (warm tiny
        # decode on CPU is otherwise too fast to contend with), while
        # the workers' injectors ignore the burst kind entirely
        KFT_FAULT_PLAN=(f"{burst_plan};"
                        f"slow_serve@phase=decode:ms={decode_delay_ms}"),
        KFT_JOURNAL_DIR=jdir,
        KFT_SLO_FILE=slo_file,
        KFT_TS_INTERVAL_S="0.5",
        KFT_TRACE_BUFFER="65536",
    )
    env.pop("XLA_FLAGS", None)
    cmd = [
        sys.executable, "-m", "kungfu_tpu.serving", "-np", "3",
        "--min-size", "3", "--max-size", "3", "--platform", "cpu",
        "--preset", "tiny", "--slots", "2", "--no-autoscale",
        "--telemetry", "--timeout", str(int(timeout_s)), "-q",
    ]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    lines: List[str] = []
    pump = threading.Thread(
        target=lambda: [lines.append(ln) for ln in proc.stdout], daemon=True
    )
    pump.start()

    def find(pattern: str, deadline_s: float = 60.0) -> Optional[str]:
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            for line in list(lines):
                m = re.search(pattern, line)
                if m:
                    return m.group(1)
            if proc.poll() is not None:
                return None
            time.sleep(0.1)
        return None

    stats: Dict = {}
    try:
        serve_url = find(r"SERVE_URL: (\S+)")
        if not serve_url:
            failures.append("fleet never printed SERVE_URL")
            return {"ok": False, "failures": failures,
                    "output_tail": "".join(lines)[-3000:], **metrics}
        if not find(r"TENANTS: (\[.*\])", 5.0):
            failures.append("router never loaded the tenant registry "
                            "(no TENANTS line)")
        client = _Client(serve_url)

        def get_stats() -> Optional[dict]:
            try:
                with urllib.request.urlopen(serve_url + "/stats",
                                            timeout=3) as r:
                    return json.loads(r.read().decode())
            except (OSError, ValueError):
                return None

        t0 = time.monotonic()
        healthy = 0
        while time.monotonic() - t0 < 90:
            st = get_stats()
            if st:
                healthy = sum(1 for w in st["workers"].values()
                              if w["healthy"])
                if healthy >= 3:
                    break
            time.sleep(0.25)
        if healthy < 3:
            failures.append(f"only {healthy}/3 workers came healthy")
        metrics["boot_s"] = round(time.monotonic() - t0, 3)

        prompts = [[1 + (i % 5), 2, 3 + (i % 7), 4, 5 + (i % 3)]
                   for i in range(max(batch_requests, 12))]

        # ---- warmup: pay the jit compiles under a throwaway tenant so the
        # compile-era latencies land in the `warmup` series, never in the
        # SLO-ruled sensitive one --------------------------------------------------
        warm_errs: List[str] = []

        def warm_one(i: int) -> None:
            try:
                client.generate(prompts[i], 8, timeout_s=120,
                                tenant="warmup")
            except OSError as e:
                warm_errs.append(f"warmup {i}: {e}")

        warm = [threading.Thread(target=warm_one, args=(i,))
                for i in range(6)]
        for t in warm:
            t.start()
        for t in warm:
            t.join(timeout=150)
        if warm_errs:
            failures.append(f"warmup errors: {warm_errs[:3]}")

        # ---- phase A: the burst shape vs the token bucket --------------------
        codes: Dict[int, int] = {}
        burst_errs: List[str] = []
        burst_threads: List[threading.Thread] = []

        def burst_one(i: int, tenant: str) -> None:
            try:
                client.generate(prompts[i % len(prompts)], 4,
                                timeout_s=120, tenant=tenant)
                codes[200] = codes.get(200, 0) + 1
            except urllib.error.HTTPError as e:
                codes[e.code] = codes.get(e.code, 0) + 1
            except OSError as e:
                burst_errs.append(f"burst {i}: {e}")

        for fault in bursts:
            if fault.start_after_s:
                time.sleep(fault.start_after_s)
            n = max(1, int(fault.rps * fault.secs))
            gap = 1.0 / fault.rps
            for i in range(n):
                t = threading.Thread(target=burst_one,
                                     args=(i, fault.tenant), daemon=True)
                t.start()
                burst_threads.append(t)
                time.sleep(gap)
        for t in burst_threads:
            t.join(timeout=120)
        metrics["burst_codes"] = dict(sorted(codes.items()))
        if burst_errs:
            failures.append(f"burst client errors: {burst_errs[:3]}")
        if not codes.get(429):
            failures.append("the burst never hit the token bucket "
                            "(no 429 responses)")
        if not codes.get(200):
            failures.append("the bucket admitted nothing from the burst "
                            "(no 200 responses)")

        # drain the admitted burst backlog before staging the preemption mix
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60:
            st = get_stats()
            if st and st["queue_depth"] == 0 and st["in_flight"] == 0:
                break
            time.sleep(0.25)

        # ---- phase B: batch fills every slot, sensitive preempts -------------
        batch_results: List[Optional[dict]] = [None] * batch_requests
        sens_lat: List[float] = []
        mix_errs: List[str] = []

        def batch_one(i: int) -> None:
            try:
                batch_results[i] = client.generate(
                    prompts[i], batch_new, timeout_s=180, tenant="batch")
            except OSError as e:
                mix_errs.append(f"batch {i}: {e}")

        def sensitive_one(i: int) -> None:
            t0 = time.monotonic()
            try:
                r = client.generate(prompts[i], 8, timeout_s=180,
                                    tenant="sensitive")
                if r["status"] == "ok":
                    sens_lat.append(time.monotonic() - t0)
                else:
                    mix_errs.append(f"sensitive {i}: status {r['status']}")
            except OSError as e:
                mix_errs.append(f"sensitive {i}: {e}")

        batch_threads = [threading.Thread(target=batch_one, args=(i,))
                         for i in range(batch_requests)]
        for t in batch_threads:
            t.start()
        # give the batch wave time to occupy every engine slot (decode is
        # warm — fast — so don't wait long enough for it to finish)
        time.sleep(0.5)
        sens_threads = [threading.Thread(target=sensitive_one, args=(i,))
                        for i in range(sensitive_requests)]
        for t in sens_threads:
            t.start()
        for t in batch_threads + sens_threads:
            t.join(timeout=240)
        if mix_errs:
            failures.append(f"mix client errors: {mix_errs[:3]}")
        done = [r for r in batch_results
                if r is not None and r["status"] == "ok"]
        if len(done) != batch_requests:
            failures.append(f"only {len(done)}/{batch_requests} batch "
                            "requests completed (preemption dropped work?)")

        # the preemption evidence is journaled by the WORKER process; its
        # emit is flushed, but give the fs a moment under load
        preempted: List[dict] = []
        t0 = time.monotonic()
        while time.monotonic() - t0 < 30:
            events = _journal_events(jdir)
            preempted = filter_events(events, "slot_preempted")
            if preempted and filter_events(events, "preempted_readmitted"):
                break
            time.sleep(0.5)

        # a few post-contention sensitive probes pad the client-side p99
        # sample beyond the contended trio
        for i in range(3):
            sensitive_one(i + sensitive_requests)

        # ---- phase C: byte-identical replay of the (possibly preempted)
        # batch prompts on the now-idle fleet ----------------------------------
        for i, r in enumerate(batch_results):
            if r is None or r["status"] != "ok":
                continue
            try:
                replay = client.generate(prompts[i], batch_new,
                                         timeout_s=120, tenant="batch")
            except OSError as e:
                failures.append(f"replay {i} failed: {e}")
                continue
            if replay["tokens"] != r["tokens"]:
                failures.append(
                    f"batch prompt {i} diverged after preemption churn: "
                    f"{r['tokens']} vs {replay['tokens']}")
        stats = get_stats() or {}
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        pump.join(timeout=5)

    # ---- journal + stats assertions ------------------------------------------
    events = _journal_events(jdir)
    limited = filter_events(events, "tenant_rate_limited", tenant="bursty")
    if not limited:
        failures.append("no tenant_rate_limited journal event for the "
                        "bursty tenant")
    preempted = filter_events(events, "slot_preempted")
    readmitted = filter_events(events, "preempted_readmitted")
    if not preempted:
        failures.append("no slot_preempted journal event — the sensitive "
                        "tenant never displaced a batch slot")
    if not readmitted:
        failures.append("no preempted_readmitted journal event — evicted "
                        "batch work never resumed")
    breaches = filter_events(events, "slo_breach",
                             rule="sensitive_latency_p99")
    if breaches:
        failures.append(
            f"sensitive tenant breached its SLO {len(breaches)}x "
            f"(value={breaches[0].get('value')})")
    p99 = _percentile(sens_lat, 0.99)
    metrics["sensitive_p99_s"] = round(p99, 3) if p99 is not None else None
    if p99 is None:
        failures.append("no successful sensitive-tenant requests")
    elif p99 > threshold_ms / 1000.0:
        failures.append(f"client-measured sensitive p99 {p99:.3f}s exceeds "
                        f"the {threshold_ms / 1000.0:g}s SLO")
    if stats.get("dropped", 0) != 0:
        failures.append(f"router reports dropped={stats.get('dropped')}")
    metrics.update(
        rate_limited=len(limited),
        preemptions=len(preempted),
        readmits=len(readmitted),
        tenancy_stats=stats.get("tenancy", {}),
    )
    return {"ok": not failures, "failures": failures,
            "output_tail": "".join(lines)[-3000:] if failures else "",
            **metrics}


class _Client:
    def __init__(self, url: str):
        self.url = url

    def generate(self, prompt, max_new: int, timeout_s: float = 120.0,
                 tenant: str = "") -> dict:
        payload = {"prompt": list(prompt), "max_new_tokens": max_new}
        if tenant:
            payload["tenant"] = tenant
        body = json.dumps(payload).encode()
        req = urllib.request.Request(
            self.url + "/v1/generate", data=body, method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=timeout_s) as r:
            return json.loads(r.read().decode())

    def health_size(self, config_url: str) -> Optional[int]:
        try:
            with urllib.request.urlopen(config_url + "/health", timeout=3) as r:
                return int(json.loads(r.read().decode()).get("size", -1))
        except (OSError, ValueError):
            return None


def run_serve_drill(np: int = 2, buddy: str = "on", timeout_s: float = 300.0,
                    requests: int = 12, max_new: int = 16,
                    crash_tokens: int = 24, p99_bound_s: float = 60.0,
                    skip_autoscale: bool = False, tier: str = "",
                    trace: bool = False) -> Dict:
    """Run the drill; returns {"ok": bool, "failures": [...], metrics...}.

    `tier="prefill"|"decode"` runs the DISAGGREGATED variant: a 3-rank
    fleet (1 prefill + 2 decode), with the scripted kill targeting a rank
    of that pool (`crash_serve@...:tier=...`).  A prefill kill fires on the
    prefilled-token counter mid-burst (the router's dispatch dies and
    re-queues); a decode kill fires mid-stream with shipped-KV requests
    decoding (the prefill worker's proxy read dies, surfaces as a failed
    dispatch, re-queues).  Either way: zero drops, bounded p99,
    `rank_rejoined` journaled by the respawned victim.

    `trace=True` runs the distributed-tracing variant on top (half of the
    `--trace-drill` stage, docs/observability.md "Request tracing"): every
    completed request must assemble into a stitched multi-process trace on
    the fleet `/requests` endpoint (>= 2 process lanes, zero orphan spans,
    not partial; failover victims carry the requeue + warm_graft spans).
    The induced-tail half (slow_serve -> SLO breach attribution) is
    `run_induced_tail_drill` — a separate clean fleet, so failover churn
    cannot pollute the breach's phase attribution."""
    failures: List[str] = []
    metrics: Dict = {"np": np, "buddy": buddy, "requests": requests,
                     "tier": tier, "trace": trace}

    prefill_ranks = 0
    if tier:
        assert tier in ("prefill", "decode"), tier
        np = max(np, 3)
        prefill_ranks = 1
        skip_autoscale = True  # the tier drill is a failover drill
        # prefill workers count PREFILLED tokens (one bucketed prompt per
        # request); decode workers count generated tokens
        crash_tokens = 15 if tier == "prefill" else crash_tokens
        plan = f"crash_serve@tokens={crash_tokens}:tier={tier}:rank=-1"
    else:
        plan = f"crash_serve@tokens={crash_tokens}:rank=1"

    tmp = tempfile.mkdtemp(prefix="kft-serve-drill-")
    jdir = os.path.join(tmp, "journal")
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        KFT_FAULT_PLAN=plan,
        KFT_JOURNAL_DIR=jdir,
        # failover churn must not wrap the router's span ring mid-drill —
        # the stitching assertions need every route span still resident
        KFT_TRACE_BUFFER="65536",
        # aggressive autoscale windows so the drill finishes in seconds
        KFT_SERVE_SCALE_UP_DEPTH="3",
        KFT_SERVE_SCALE_UP_TICKS="2",
        KFT_SERVE_SCALE_DOWN_TICKS="6",
        KFT_SERVE_TICK_S="0.25",
    )
    if trace:
        assert tier, "the trace drill needs a tiered fleet (tier=decode)"
    env.pop("XLA_FLAGS", None)
    if buddy == "off":
        env["KFT_BUDDY"] = "0"
    cmd = [
        sys.executable, "-m", "kungfu_tpu.serving", "-np", str(np),
        "--min-size", "1", "--max-size", str(np), "--platform", "cpu",
        "--preset", "tiny", "--slots", "2", "--telemetry",
        "--timeout", str(int(timeout_s)), "-q",
    ]
    if prefill_ranks:
        cmd += ["--prefill-ranks", str(prefill_ranks)]
    if skip_autoscale:
        cmd.append("--no-autoscale")
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    lines: List[str] = []
    pump = threading.Thread(
        target=lambda: [lines.append(ln) for ln in proc.stdout], daemon=True
    )
    pump.start()

    def find(pattern: str, deadline_s: float = 60.0) -> Optional[str]:
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            for line in list(lines):
                m = re.search(pattern, line)
                if m:
                    return m.group(1)
            if proc.poll() is not None:
                return None
            time.sleep(0.1)
        return None

    try:
        serve_url = find(r"SERVE_URL: (\S+)")
        config_url = find(r"CONFIG_URL: (\S+)", 5.0)
        if not serve_url or not config_url:
            failures.append("fleet never printed SERVE_URL/CONFIG_URL")
            return {"ok": False, "failures": failures,
                    "output": "".join(lines)[-3000:], **metrics}
        client = _Client(serve_url)

        # wait for the full fleet to come healthy before loading it (CPU
        # workers pay several seconds of jax import before their first probe)
        t0 = time.monotonic()
        healthy = 0
        while time.monotonic() - t0 < 90:
            try:
                with urllib.request.urlopen(serve_url + "/stats",
                                            timeout=3) as r:
                    st = json.loads(r.read().decode())
                healthy = sum(
                    1 for w in st["workers"].values() if w["healthy"]
                )
                if healthy >= np:
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.25)
        if healthy < np:
            failures.append(f"only {healthy}/{np} workers came healthy")
        metrics["boot_s"] = round(time.monotonic() - t0, 3)

        # ---- phase A: failover under load ------------------------------------
        prompts = [[1 + (i % 5), 2, 3 + (i % 7), 4, 5 + (i % 3)]
                   for i in range(requests)]
        results: List[Optional[dict]] = [None] * requests
        lat: List[float] = [0.0] * requests
        errs: List[str] = []

        def one(i: int) -> None:
            t0 = time.monotonic()
            try:
                results[i] = client.generate(prompts[i], max_new,
                                             timeout_s=p99_bound_s + 30)
            except OSError as e:
                errs.append(f"request {i}: {e}")
            lat[i] = time.monotonic() - t0

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(requests)]
        t_load0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=p99_bound_s + 60)
        load_s = time.monotonic() - t_load0
        if errs:
            failures.append(f"client errors: {errs[:3]}")
        done = [r for r in results if r is not None and r["status"] == "ok"]
        if len(done) != requests:
            failures.append(f"only {len(done)}/{requests} requests completed")
        requeued = [r for r in done if r.get("requeues", 0) > 0]
        p99 = _percentile([x for x in lat if x > 0], 0.99)
        metrics.update(
            completed=len(done),
            requeued_requests=len(requeued),
            latency_p50_s=round(_percentile(lat, 0.50) or 0, 3),
            latency_p99_s=round(p99 or 0, 3),
            load_window_s=round(load_s, 3),
        )
        tok_total = sum(max_new for _ in done)
        metrics["tokens_per_sec"] = round(tok_total / max(load_s, 1e-9), 2)
        if p99 is None or p99 > p99_bound_s:
            failures.append(f"p99 latency {p99} exceeds bound {p99_bound_s}s")

        # ---- phase B: determinism across the failover ------------------------
        if done:
            replay = client.generate(prompts[0], max_new)
            if replay["tokens"] != results[0]["tokens"]:
                failures.append(
                    "replayed prompt diverged after failover: "
                    f"{results[0]['tokens']} vs {replay['tokens']}"
                )

        # wait for the victim's rejoin to land in the journal before any
        # teardown: the respawned worker pays a multi-second jax import
        # before it can journal rank_rejoined, and the assertion below
        # reads that record
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60:
            if any(e.get("event") == "rank_rejoined"
                   for e in _journal_events(jdir)):
                break
            time.sleep(0.5)
        metrics["rejoin_visible_s"] = round(time.monotonic() - t0, 3)

        # ---- tracing: stitched cross-process timelines + tail SLO ------------
        telemetry_url = find(r"TELEMETRY_URL: (\S+)", 5.0)
        if telemetry_url:
            report = _poll_requests(telemetry_url, requests,
                                    deadline_s=45.0 if trace else 15.0)
            if report is None:
                if trace:
                    failures.append("fleet /requests never assembled "
                                    f"{requests} completed request traces")
            else:
                metrics["traces_completed"] = report.get("completed_total")
                metrics["traces_partial"] = report.get("partial_total")
                att = report.get("attribution") or {}
                if att:
                    metrics["request_attribution"] = att
                if trace:
                    failures.extend(_assert_stitched(report, requests))
        elif trace:
            failures.append("fleet never printed TELEMETRY_URL "
                            "(trace drill needs --telemetry)")

        # ---- phase C: autoscale down then up ---------------------------------
        if not skip_autoscale:
            t0 = time.monotonic()
            scaled_down = False
            while time.monotonic() - t0 < 30:
                if client.health_size(config_url) == 1:
                    scaled_down = True
                    break
                time.sleep(0.25)
            if not scaled_down:
                failures.append("idle fleet never scaled down to min size")
            metrics["scale_down_s"] = round(time.monotonic() - t0, 3)

            # sustained closed-loop burst: 10 concurrent clients against 2
            # slots keeps queue depth above the high-water mark until the
            # scale-up commits (a finite burst on the tiny model drains
            # faster than the autoscaler's sustain window)
            stop_burst = threading.Event()

            def burst_loop(i: int) -> None:
                while not stop_burst.is_set():
                    try:
                        client.generate(prompts[i % requests], max_new,
                                        timeout_s=60)
                    except OSError:
                        time.sleep(0.1)

            burst = [threading.Thread(target=burst_loop, args=(i,),
                                      daemon=True) for i in range(10)]
            for t in burst:
                t.start()
            t0 = time.monotonic()
            scaled_up = False
            while time.monotonic() - t0 < 45:
                if (client.health_size(config_url) or 0) >= 2:
                    scaled_up = True
                    break
                time.sleep(0.25)
            stop_burst.set()
            for t in burst:
                t.join(timeout=p99_bound_s + 60)
            if not scaled_up:
                failures.append("loaded fleet never scaled back up")
            metrics["scale_up_s"] = round(time.monotonic() - t0, 3)
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        pump.join(timeout=5)

    out = "".join(lines)
    stats = {}
    m = re.search(r"SERVE_STATS: (\{.*\})", out)
    if m:
        stats = json.loads(m.group(1))
    scale_events = []
    m = re.search(r"AUTOSCALE_EVENTS: (\[.*\])", out)
    if m:
        scale_events = json.loads(m.group(1))

    # ---- journal assertions --------------------------------------------------
    events = _journal_events(jdir)
    by_kind: Dict[str, List[dict]] = {}
    for e in events:
        by_kind.setdefault(str(e.get("event")), []).append(e)

    if stats.get("dropped", 0) != 0:
        failures.append(f"router reports dropped={stats.get('dropped')}")
    crashes = by_kind.get("chaos_crash_serve", [])
    if not crashes:
        failures.append("crash_serve fault never fired")
    elif tier:
        crash_tiers = {e.get("tier") for e in crashes}
        if crash_tiers != {tier}:
            failures.append(f"crash fired on tier {sorted(crash_tiers)}, "
                            f"expected {tier}")
    if not by_kind.get("request_requeued"):
        failures.append("no request_requeued journal events (kill missed "
                        "the in-flight window?)")
    rejoins = by_kind.get("rank_rejoined", [])
    if tier and rejoins:
        rejoin_tiers = {e.get("tier") for e in rejoins}
        if tier not in rejoin_tiers:
            failures.append(f"rank_rejoined tiers {sorted(rejoin_tiers)}, "
                            f"expected a {tier} rejoin")
    if not rejoins:
        failures.append("victim never journaled rank_rejoined")
    else:
        want_rung = "buddy" if buddy == "on" else "seed"
        rungs = {e.get("recovery_rung") for e in rejoins}
        if want_rung not in rungs:
            failures.append(f"rank_rejoined rung {sorted(rungs)}, "
                            f"expected {want_rung}")
        metrics["rejoin_rung"] = sorted(rungs)[0]
        metrics["rejoin_restore_s"] = max(
            float(e.get("restore_s", 0)) for e in rejoins
        )
    requeues_t = [e["t_wall"] for e in by_kind.get("request_requeued", [])]
    resumed_t = [e["t_wall"]
                 for e in by_kind.get("requeued_request_completed", [])]
    if requeues_t and resumed_t:
        metrics["failover_requeue_s"] = round(
            max(resumed_t) - min(requeues_t), 3
        )
    if not skip_autoscale:
        kinds = {e["kind"] for e in scale_events}
        if "scale_down" not in kinds or "scale_up" not in kinds:
            failures.append(
                f"autoscaler committed {sorted(kinds)}, need both "
                "scale_down and scale_up"
            )
        if not by_kind.get("scale_down") or not by_kind.get("scale_up"):
            failures.append("scale events missing from the journal")
    metrics["journal_event_counts"] = {k: len(v) for k, v in by_kind.items()}
    metrics["warm_resumes"] = sum(
        1 for e in by_kind.get("request_requeued", [])
        if e.get("warm_tokens", 0) > 0
    )
    return {"ok": not failures, "failures": failures,
            "output_tail": out[-3000:] if failures else "", **metrics}
