#!/usr/bin/env python
"""Simulated pod-scale chaos drill — M netns hosts x K workers, shaped DCN.

The receiving harness for ROADMAP item 1: grows the 3-rank netns cluster
drill into a pod (kungfu_tpu/testing/pod.py) big enough to exercise the
robustness subsystems at the scale their failure modes appear, with faults
injected at the NETWORK layer (partition routes, tc link shaping, whole-
host SIGKILL) instead of in-process sleeps.

Drill phases (default / --smoke):

    1. resize        schedule-driven shrink + regrow across the full fleet
                     (the planned-membership-change baseline)
    2. kill_host     one host's launcher + all K workers SIGKILLed at once —
                     the survivors' RemoteHostJudge must shrink ALL K ranks
                     out in EXACTLY ONE conditional PUT (journal
                     host_heal_shrink x1, local heal_shrink x0) and every
                     worker heal must resync from the buddy RAM tier
                     (cross-host placement means the dead host never held a
                     snapshot AND its only copy: RPO=0)
    3. partition     the remaining hosts split into two groups that cannot
                     reach each other (the config server stays reachable —
                     control plane rides its own network): the leader must
                     journal partition_suspected and NOT shrink; after
                     heal_after seconds the partition heals and the fleet
                     re-rendezvouses at UNCHANGED membership via reconvene
                     version bumps
    4. degrade_link  one host's DCN link shaped mid-run (latency/loss under
                     netem, rate cap under tbf) — training rides it out

Exit 0 = every assertion held.  Needs root + netns (auto-SKIP otherwise —
same contract as scripts/netns_cluster_drill.py).  Link shaping degrades
honestly: netem -> tbf(rate only) -> none, stamped on the record.

    sudo python scripts/pod_drill.py --smoke            # 4 hosts x 1, CI
    sudo python scripts/pod_drill.py --hosts 8 --workers-per-host 8   # 64
    sudo python scripts/pod_drill.py --bench --sizes 1,2,4 --workers-per-host 2

--bench runs the weak-scaling arm instead: fault-free fleets across host
counts x {ring, hierarchical} strategies on the shaped fabric, efficiency
vs the single-host baseline, the `scaling_efficiency` SLO floor applied to
the curve (a pod-scale scaling regression FAILS the bench), and the
hierarchical-vs-ring verdict on the shaped DCN tier.  The record lands in
the BENCH json's `scaling.pod` section via `--bench scaling --pod-hosts`.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RESULT_RE = re.compile(
    r"RESULT: fake-adaptive trained=(\d+) resizes=(\d+) final_size=(\d+) "
    r"mesh=(\S+) loss=([-\d.naninf]+) heals=(\d+)(?: seconds=([\d.]+))?")


def _worker_cmd(total_samples: int, schedule: str = "", check_every: int = 2):
    cmd = [sys.executable, "-m", "kungfu_tpu.testing.fake_adaptive_trainer",
           "--total-samples", str(total_samples), "--batch-size", "32",
           "--check-every", str(check_every)]
    if schedule:
        cmd += ["--schedule", schedule]
    return cmd


def _parse_results(pod) -> list:
    out = []
    for ip in pod.launchers:
        for m in RESULT_RE.finditer(pod.launcher_output(ip)):
            out.append({
                "host": ip, "trained": int(m.group(1)),
                "resizes": int(m.group(2)), "final_size": int(m.group(3)),
                "mesh": m.group(4), "loss": float(m.group(5)),
                "heals": int(m.group(6)),
                "seconds": float(m.group(7)) if m.group(7) else None,
            })
    return out


def run_chaos_drill(args) -> int:
    from kungfu_tpu.chaos.plan import parse_fault_plan
    from kungfu_tpu.testing.pod import LinkShape, PlanExecutor, Pod, PodSpec

    M, K = args.hosts, args.workers_per_host
    if M < 3:
        print("FAIL: the chaos drill needs >= 3 hosts (kill one, "
              "partition the rest)", file=sys.stderr)
        return 1
    W = M * K
    spec = PodSpec(
        hosts=M, workers_per_host=K,
        shape=LinkShape(latency_ms=args.latency_ms, jitter_ms=args.jitter_ms,
                        loss_pct=args.loss_pct, rate_mbit=args.rate_mbit),
        heartbeat_timeout_s=args.heartbeat_timeout,
        suspicion_s=args.suspicion,
    )
    # phase plan: planned resizes finish by ~step 12 (schedule exhausts —
    # no later proposals to regrow onto the dead host), then the whole-host
    # kill, then the partition among the SURVIVING hosts
    schedule = f"{W}:4,{W - K}:4,{W}:4"
    kill_victim = f"h{M}"
    alive = [f"h{i + 1}" for i in range(M - 1)]
    half = max(1, len(alive) // 2)
    part_a, part_b = alive[:half], alive[half:]
    plan = (f"kill_host@step={args.kill_step}:host={kill_victim};"
            f"partition@step={args.partition_step}:"
            f"hosts={','.join(part_a)}|{','.join(part_b)}"
            f":heal_after={args.partition_heal_after}")
    if args.degrade_step >= 0:
        plan += (f";degrade_link@host=h1:step={args.degrade_step}"
                 f":latency_ms={args.degrade_latency_ms}"
                 f":rate_mbit={args.degrade_rate_mbit}:duration=10")
    faults = parse_fault_plan(plan).network_faults()
    # enough samples that the fleet is still training well past the last
    # fault: ~45+ post-kill-size steps
    total = args.total_samples or 32 * (W - K) * 120

    pod = Pod(spec)
    print(f"# pod drill: {M} hosts x {K} workers = {W} ranks, "
          f"shaping={pod.shaping}, plan: {plan}")
    t0 = time.monotonic()
    failures: list = []
    try:
        pod.setup()
        pod.spawn(_worker_cmd(total, schedule=schedule), timeout_s=args.timeout)
        ex = PlanExecutor(pod, faults)
        finished = pod.wait(args.timeout, tick=ex.tick, poll_s=0.25)
        if not finished:
            failures.append(f"fleet did not finish within {args.timeout:.0f}s")
        results = _parse_results(pod)
        events = pod.journal_events()
        by_kind: dict = {}
        for e in events:
            by_kind.setdefault(e.get("event", "?"), []).append(e)

        # -- membership: one host death == exactly one shrink CAS ---------------------
        host_shrinks = by_kind.get("host_heal_shrink", [])
        killed_ip = spec.host_ip(M - 1)
        if len(host_shrinks) != 1:
            failures.append(f"host_heal_shrink x{len(host_shrinks)}, want "
                            f"exactly 1 (split-brain or missed heal)")
        elif host_shrinks[0].get("host") != killed_ip:
            failures.append(f"host_heal_shrink targeted "
                            f"{host_shrinks[0].get('host')}, not {killed_ip}")
        elif len(host_shrinks[0].get("workers", ())) != K:
            failures.append(f"host shrink removed "
                            f"{len(host_shrinks[0].get('workers', ()))} "
                            f"workers, want all {K} at once")
        if by_kind.get("heal_shrink"):
            failures.append(f"{len(by_kind['heal_shrink'])} per-worker "
                            "heal_shrink CASes landed — the host death must "
                            "heal as ONE membership change")
        if not by_kind.get("host_suspected"):
            failures.append("no host_suspected journal event (suspicion "
                            "window never armed)")

        # -- partition: suspected, never shrunk, rejoined -----------------------------
        if not by_kind.get("partition_suspected"):
            failures.append("no partition_suspected journal event")
        if not by_kind.get("reconvene"):
            failures.append("no reconvene journal event (nothing nudged the "
                            "partitioned workers back)")
        part_applied = [r for r in ex.applied if r["kind"] == "partition"]
        if not part_applied:
            failures.append("the partition fault never fired (fleet never "
                            f"reached step {args.partition_step}?)")

        # -- recovery ladder: every heal from the buddy RAM tier ----------------------
        heals = by_kind.get("heal", [])
        rungs = {e.get("recovery_rung") for e in heals}
        if not heals:
            failures.append("no worker heal events journaled")
        elif rungs - {"buddy"}:
            failures.append(f"heal rungs {sorted(rungs)} — kill_host must "
                            "recover from the buddy RAM tier only (RPO=0)")
        if by_kind.get("buddy_colocated"):
            failures.append("buddy_colocated journaled: a snapshot and its "
                            "copy shared a host")

        # -- the fleet finished, at the right size ------------------------------------
        want_final = W - K
        survivors = [r for r in results if r["final_size"] == want_final
                     and r["trained"] >= total]
        if len(survivors) != want_final:
            failures.append(
                f"{len(survivors)}/{want_final} workers finished at "
                f"final_size={want_final} with trained>={total} "
                f"(results: {[(r['trained'], r['final_size']) for r in results]})")
        if results and max(r["resizes"] for r in results) < 2:
            failures.append("schedule-driven resizes never exercised")

        summary = {
            "ranks": W, "hosts": M, "workers_per_host": K,
            "shaping": pod.shaping, "plan": plan,
            "wall_s": round(time.monotonic() - t0, 1),
            "host_heal_shrinks": len(host_shrinks),
            "partition_suspected": len(by_kind.get("partition_suspected", ())),
            "reconvenes": len(by_kind.get("reconvene", ())),
            "heal_rungs": sorted(r for r in rungs if r),
            "journal_counts": {k: len(v) for k, v in sorted(by_kind.items())},
            "applied": ex.applied,
            "ok": not failures, "failures": failures,
        }
        if args.json_out:
            with open(args.json_out, "w") as f:
                json.dump(summary, f, indent=2)
        if failures:
            print("POD DRILL FAILED: " + "; ".join(failures), file=sys.stderr)
            for ip in sorted(pod.launchers):
                out = pod.launcher_output(ip)
                print(f"--- launcher {ip} (tail) ---\n{out[-2500:]}",
                      file=sys.stderr)
            return 1
        print(f"POD DRILL OK: {W} ranks on {M} hosts (shaping={pod.shaping}) "
              f"survived resize + kill_host (1 shrink CAS, {K} ranks at "
              f"once, rung=buddy) + partition "
              f"({summary['partition_suspected']} suspected, "
              f"{summary['reconvenes']} reconvenes, zero shrinks) "
              f"in {summary['wall_s']}s")
        return 0
    finally:
        pod.teardown()


def run_bench(args) -> int:
    """Weak-scaling arm: fault-free fleets across host counts x strategies
    on the shaped fabric; efficiency vs the single-host baseline; the
    shipped `scaling_efficiency` SLO floor gates the curve."""
    from kungfu_tpu.monitor.slo import evaluate_scaling_slo
    from kungfu_tpu.testing.pod import LinkShape, Pod, PodSpec

    sizes = sorted({int(s) for s in args.sizes.split(",") if s})
    K = args.workers_per_host
    shape = LinkShape(latency_ms=args.latency_ms, jitter_ms=args.jitter_ms,
                      loss_pct=args.loss_pct, rate_mbit=args.rate_mbit)
    strategies = {"ring": "RING", "hierarchical": "BINARY_TREE_STAR"}
    rows: list = []
    shaping = None
    for algo, strat in strategies.items():
        for n in sizes:
            spec = PodSpec(hosts=n, workers_per_host=K, shape=shape)
            total = 32 * spec.world * args.steps_per_rank  # weak scaling
            pod = Pod(spec)
            shaping = pod.shaping
            try:
                pod.setup()
                pod.spawn(_worker_cmd(total), strategy=strat,
                          timeout_s=args.timeout)
                ok = pod.wait(args.timeout)
                results = _parse_results(pod)
            finally:
                pod.teardown()
            secs = [r["seconds"] for r in results if r.get("seconds")]
            done = [r for r in results if r["trained"] >= total]
            if not ok or len(done) != spec.world or not secs:
                print(f"# pod bench {algo}@hosts={n} failed "
                      f"({len(done)}/{spec.world} finished)", file=sys.stderr)
                continue
            t = statistics.median(secs)
            row = {"algorithm": algo, "hosts": n, "np": spec.world,
                   "train_s": round(t, 3),
                   "samples_per_s": round(total / t, 1)}
            rows.append(row)
            print(f"RESULT: bench=pod-scaling algo={algo} hosts={n} "
                  f"np={spec.world} train_s={row['train_s']} "
                  f"samples_per_s={row['samples_per_s']}", flush=True)

    by_algo: dict = {}
    eff_samples: list = []
    for algo in strategies:
        curve = [r for r in rows if r["algorithm"] == algo]
        base = next((r for r in curve if r["hosts"] == min(sizes)), None)
        for r in curve:
            # weak scaling: per-rank work is constant, so ideal wall time is
            # flat — efficiency is the baseline time over this size's time
            r["scaling_efficiency"] = (
                round(base["train_s"] / r["train_s"], 3) if base else None)
        multi = [r for r in curve if r["hosts"] > min(sizes)
                 and r.get("scaling_efficiency") is not None]
        if multi:
            by_algo[algo] = multi[-1]["scaling_efficiency"]
            eff_samples.append(by_algo[algo])

    # the pod exists to make hierarchical the MEASURED default on shaped
    # DCN links; on an unshaped fabric (no netem/tbf) the verdict is
    # recorded but not asserted — there is no slow tier to win on
    hier_wins = None
    if "ring" in by_algo and "hierarchical" in by_algo:
        hier_wins = by_algo["hierarchical"] >= by_algo["ring"] - 0.02

    breached = False
    slo_report = None
    if eff_samples:
        engine, breached = evaluate_scaling_slo(eff_samples)
        slo_report = engine.report()

    record = {
        "bench": "pod_scaling", "shaping": shaping,
        "shape": {"latency_ms": shape.latency_ms, "jitter_ms": shape.jitter_ms,
                  "loss_pct": shape.loss_pct, "rate_mbit": shape.rate_mbit},
        "sizes": sizes, "workers_per_host": K, "rows": rows,
        "efficiency_by_algorithm": by_algo,
        "allreduce_scaling_efficiency": (min(eff_samples) if eff_samples
                                         else None),
        "hierarchical_wins_on_shaped_dcn": hier_wins,
        "slo": slo_report, "slo_breached": breached,
    }
    print(json.dumps(record), flush=True)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(record, f, indent=2)
    if not rows:
        print("POD BENCH FAILED: no sizes completed", file=sys.stderr)
        return 1
    if breached:
        print("POD BENCH: scaling efficiency below the SLO floor "
              f"(worst={record['allreduce_scaling_efficiency']}) — failing",
              file=sys.stderr)
        return 4
    if shaping == "netem" and hier_wins is False:
        # only a REAL latency asymmetry makes this a verdict: under the
        # tbf/none fallbacks (or a CPU-oversubscribed host) the bottleneck
        # is not the DCN tier and the comparison is recorded, not asserted
        print("POD BENCH: hierarchical lost to ring on a SHAPED DCN tier "
              f"({by_algo}) — failing", file=sys.stderr)
        return 5
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="simulated pod-scale chaos / scaling drill (netns)")
    ap.add_argument("--hosts", type=int, default=8)
    ap.add_argument("--workers-per-host", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="scaled-down CI shape: 4 hosts x 1 worker")
    ap.add_argument("--bench", action="store_true",
                    help="weak-scaling bench arm instead of the chaos drill")
    ap.add_argument("--sizes", default="1,2,4",
                    help="--bench: comma-separated host counts")
    ap.add_argument("--steps-per-rank", type=int, default=30)
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--total-samples", type=int, default=0)
    # link shape (per host, both directions)
    ap.add_argument("--latency-ms", type=float, default=2.0)
    ap.add_argument("--jitter-ms", type=float, default=0.5)
    ap.add_argument("--loss-pct", type=float, default=0.0)
    ap.add_argument("--rate-mbit", type=float, default=200.0)
    # fault schedule
    ap.add_argument("--kill-step", type=int, default=20)
    ap.add_argument("--partition-step", type=int, default=55)
    ap.add_argument("--partition-heal-after", type=float, default=12.0)
    ap.add_argument("--degrade-step", type=int, default=80,
                    help="-1 disables the degrade_link phase")
    ap.add_argument("--degrade-latency-ms", type=float, default=40.0)
    ap.add_argument("--degrade-rate-mbit", type=float, default=20.0)
    # healer windows
    ap.add_argument("--heartbeat-timeout", type=float, default=5.0)
    ap.add_argument("--suspicion", type=float, default=6.0)
    ap.add_argument("--json-out", default="")
    args = ap.parse_args(argv)

    if args.smoke:
        args.hosts, args.workers_per_host = 4, 1

    from kungfu_tpu.testing.pod import pod_available

    if not pod_available():
        print("SKIP: network namespaces unavailable (need root + ip/veth)")
        return 0

    if args.bench:
        return run_bench(args)
    return run_chaos_drill(args)


if __name__ == "__main__":
    sys.exit(main())
