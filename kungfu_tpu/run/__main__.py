"""kungfu-tpu-run CLI — `python -m kungfu_tpu.run -np 4 python train.py`.

Flag set mirrors the reference launcher (srcs/go/kungfu/runner/flags.go:28-110
and cmd/kungfu-run/app/kungfu-run.go:18-112): -np, -H, -strategy, -w (watch),
-k (keep), -config-server, -builtin-config-server, -logdir, -q, -timeout,
-self/-nic discovery; TPU additions: -platform, -devices-per-worker,
-chips-per-host, -telemetry (fleet metrics/timeline aggregation,
docs/observability.md).
"""
from __future__ import annotations

import argparse
import os
import socket
import sys
import time

from ..elastic.config_client import ConfigClient
from ..elastic.config_server import ConfigServer
from ..plan import Cluster, HostList, Strategy
from ..utils import get_logger
from .job import Job
from .launcher import WatchRunner, simple_run

log = get_logger("kungfu.run")


def infer_self_ip(hostlist: HostList) -> str:
    """Pick our address from the host list (runner/discovery.go:18-58 analog)."""
    candidates = {h.host for h in hostlist}
    if "127.0.0.1" in candidates or "localhost" in candidates:
        return "127.0.0.1" if "127.0.0.1" in candidates else "localhost"
    names = {socket.gethostname(), socket.getfqdn()}
    try:
        names.add(socket.gethostbyname(socket.gethostname()))
    except OSError:
        pass
    for c in candidates:
        if c in names:
            return c
    return sorted(candidates)[0]


def main(argv=None):
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "-serve":
        # `kungfu-run -serve ...` — the serving fleet has its own flag set
        # (worker count, autoscale bounds, model preset); delegate wholesale
        from ..serving.__main__ import main as serve_main

        sys.exit(serve_main(argv[1:]))
    from ..env import starts_dir
    from ..monitor import boot
    from ..utils import trace

    # the job clock starts with this process, -telemetry or not, so that a
    # worker's record reads job start -> spawn -> its own entry by itself
    trace.stamp_job_start()
    boot.enter("launcher", starts_dir())
    t_config = time.monotonic()
    ap = argparse.ArgumentParser(
        "kungfu-tpu-run", description="launch distributed kungfu_tpu workers"
    )
    ap.add_argument("-np", type=int, default=1, help="total number of workers")
    ap.add_argument("-H", dest="hosts", default="", help="host list ip:slots[:pub],...")
    ap.add_argument("-self", dest="self_host", default="", help="this host's address")
    ap.add_argument("-strategy", default="AUTO", help="allreduce strategy")
    ap.add_argument("-w", dest="watch", action="store_true", help="watch (elastic) mode")
    ap.add_argument("-k", dest="keep", action="store_true", help="keep job on worker failure")
    ap.add_argument(
        "-heal", dest="heal", action="store_true",
        help="self-heal in watch mode: shrink the cluster around dead workers "
             "instead of stopping the job (implies -w)",
    )
    ap.add_argument(
        "-restart-budget", dest="restart_budget", type=int, default=0,
        help="automatic restarts per worker after a heal (exponential backoff)",
    )
    ap.add_argument(
        "-heartbeat-timeout", dest="heartbeat_timeout", type=float, default=0.0,
        help="seconds without worker heartbeat before the healer kills it "
             "(0 = disabled; catches hung-not-crashed workers)",
    )
    ap.add_argument(
        "-suspicion-timeout", dest="suspicion_timeout", type=float, default=0.0,
        help="heal mode: seconds a REMOTE host's runner heartbeat must stay "
             "silent before its workers are shrunk out (partition-vs-death "
             "window, docs/fault_tolerance.md; 0 = auto from "
             "-heartbeat-timeout)",
    )
    ap.add_argument(
        "-telemetry", dest="telemetry", action="store_true",
        help="fleet telemetry: enable worker monitoring+tracing+journal and "
             "serve merged /metrics and /timeline from this runner",
    )
    ap.add_argument(
        "-telemetry-port", dest="telemetry_port", type=int, default=0,
        help="fleet telemetry port (0 = ephemeral, printed as TELEMETRY_URL)",
    )
    ap.add_argument(
        "-slo-file", dest="slo_file", default="",
        help="JSON SLO rule file for the fleet engine (KFT_SLO_FILE; "
             "default = the shipped rules, docs/observability.md)",
    )
    ap.add_argument(
        "-slo-exit-code", dest="slo_exit_code", action="store_true",
        help="exit nonzero when any SLO rule sustained a breach during the "
             "run, even if the job itself succeeded (drills/CI; implies "
             "-telemetry)",
    )
    ap.add_argument("-config-server", dest="config_server", default="")
    ap.add_argument(
        "-builtin-config-server", dest="builtin_cs", action="store_true",
        help="embed a config server in this runner (reference builtin-config-server)",
    )
    ap.add_argument("-port", type=int, default=9100, help="builtin config server port")
    ap.add_argument(
        "-config-replicas", dest="config_replicas", type=int, default=1,
        help="builtin config server replica count: >1 spawns a leader-leased "
             "replicated ensemble (supervised, dead replicas respawned) and "
             "hands workers the full KFT_CONFIG_URLS list "
             "(docs/fault_tolerance.md \"Replicated control plane\")",
    )
    ap.add_argument("-logdir", default="")
    ap.add_argument("-q", dest="quiet", action="store_true")
    ap.add_argument("-timeout", type=float, default=0.0, help="watch-mode timeout seconds")
    ap.add_argument("-platform", default="", help="force worker JAX platform (e.g. cpu)")
    ap.add_argument(
        "-devices-per-worker", dest="devices_per_worker", type=int, default=1,
        help="virtual devices per worker on cpu platform",
    )
    ap.add_argument(
        "-chips-per-host", dest="chips_per_host", type=int, default=0,
        help="give each worker one TPU chip of its host (TPU_VISIBLE_CHIPS "
             "and libtpu's process grid); the host's workers form one slice",
    )
    ap.add_argument("prog", nargs=argparse.REMAINDER, help="worker command")
    args = ap.parse_args(argv)

    if not args.prog:
        ap.error("missing worker command")
    prog = args.prog
    if prog and prog[0] == "--":
        prog = prog[1:]

    if args.heal:
        args.watch = True  # healing is a watch-mode capability
    if args.slo_exit_code:
        args.telemetry = True  # the SLO engine lives in the fleet aggregator
    if args.slo_file:
        os.environ["KFT_SLO_FILE"] = args.slo_file

    hosts = HostList.parse(args.hosts) if args.hosts else HostList.parse(f"127.0.0.1:{args.np}")
    cluster = Cluster.from_hostlist(hosts, args.np)
    self_host = args.self_host or infer_self_ip(hosts)
    if args.chips_per_host > 0 and args.platform != "cpu":
        from .job import chip_env

        try:  # refuse a shape libtpu cannot form before anything is spawned
            chip_env(0, n_procs=sum(1 for p in cluster.workers
                                    if p.host == self_host))
        except ValueError as e:
            ap.error(str(e))

    if args.telemetry:
        # arm the whole fleet: workers inherit these via Job.new_proc's env
        # copy; the launcher's own journal lands next to theirs
        os.environ.setdefault("KFT_CONFIG_ENABLE_MONITORING", "1")
        os.environ.setdefault("KFT_CONFIG_ENABLE_TRACE", "1")
        if not os.environ.get("KFT_JOURNAL_DIR"):
            import tempfile

            os.environ["KFT_JOURNAL_DIR"] = (
                args.logdir or tempfile.mkdtemp(prefix="kft-telemetry-")
            )
        os.environ.setdefault("KFT_TRACE_DUMP_DIR", os.environ["KFT_JOURNAL_DIR"])
        from ..monitor.journal import set_journal_context

        set_journal_context(rank="launcher", identity="launcher")

    cs = None
    ensemble = None
    config_url = args.config_server
    if args.builtin_cs or (args.watch and not config_url):
        if args.config_replicas > 1:
            from ..elastic.ensemble import ConfigEnsemble

            ensemble = ConfigEnsemble(
                replicas=args.config_replicas, init=cluster).start()
            config_url = ensemble.urls_spec
        else:
            cs = ConfigServer(port=args.port, init=cluster).start()
            config_url = cs.url

    heartbeat_dir = ""
    if args.heal and args.heartbeat_timeout > 0:
        import tempfile

        heartbeat_dir = tempfile.mkdtemp(prefix="kft-hb-")

    job = Job(
        prog=prog[0],
        args=prog[1:],
        strategy=Strategy.parse(args.strategy),
        config_server=config_url,
        platform=args.platform,
        devices_per_worker=args.devices_per_worker,
        chips_per_host=args.chips_per_host,
        heal=args.heal,
        heartbeat_dir=heartbeat_dir,
    )
    # arguments, the cluster document, the config server if any
    trace.record_span("boot:launcher.config", t_config, cat=trace.BOOT_CAT)

    from .launcher import install_signal_trap

    install_signal_trap()
    fleet = None
    try:
        if args.watch:
            client = ConfigClient(config_url)
            if args.telemetry:
                fleet = _start_fleet(args, lambda: _current_workers(client, cluster))
            runner = WatchRunner(
                job, self_host, client, logdir=args.logdir, quiet=args.quiet,
                keep=args.keep, heal=args.heal, restart_budget=args.restart_budget,
                heartbeat_timeout_s=args.heartbeat_timeout,
                suspicion_s=args.suspicion_timeout,
            )
            rc = runner.run(initial=cluster, timeout_s=args.timeout)
            if runner.heal_events:
                import json as _json

                print("RUNNER_HEAL_EVENTS: " + _json.dumps(runner.heal_events),
                      flush=True)
        else:
            if args.telemetry:
                fleet = _start_fleet(args, lambda: cluster.workers)
            rc = simple_run(
                job, cluster, self_host, logdir=args.logdir, quiet=args.quiet, keep=args.keep
            )
    finally:
        if fleet is not None:
            if args.slo_exit_code:
                from ..monitor.slo import resolve_exit_code

                new_rc = resolve_exit_code(rc, fleet.slo_breach_total())
                if new_rc != rc:
                    print(f"SLO_BREACHED: {fleet.slo_breach_total()} sustained "
                          f"breach(es); exiting {new_rc}", flush=True)
                rc = new_rc
            fleet.close()
        if cs is not None:
            cs.stop()
        if ensemble is not None:
            ensemble.stop()
    sys.exit(rc)


def _current_workers(client: ConfigClient, initial: Cluster):
    """Latest worker list from the config service (elastic jobs shrink and
    grow under the aggregator), falling back to the launch-time cluster."""
    got = client.poll_cluster()
    return got[0].workers if got is not None else initial.workers


def _start_fleet(args, workers_fn):
    from ..monitor.fleet import FleetAggregator, targets_from_workers

    fleet = FleetAggregator(
        targets_fn=lambda: targets_from_workers(workers_fn()),
        port=args.telemetry_port,
    ).start()
    print(f"TELEMETRY_URL: http://127.0.0.1:{fleet.port}", flush=True)
    print(f"TELEMETRY_DIR: {os.environ.get('KFT_JOURNAL_DIR', '')}", flush=True)
    return fleet


if __name__ == "__main__":
    main()
