"""`python -m kungfu_tpu.serving` — the kungfu-serve supervisor.

One process glues the serving fleet together:

  * embedded elastic config server holding the worker document (or join an
    external one with --config-server)
  * worker subprocess supervision RECONCILED FROM THE DOCUMENT: the
    autoscaler (or an operator PUT) changes the document, this loop
    materializes it.  A worker that dies while still in the document is
    respawned IN PLACE with a bumped incarnation — the rejoin pulls weights
    from a live peer (serving/worker.py's buddy rung) in well under a second
  * the Router front door + dispatchers (serving/router.py): requests on a
    dead rank re-queue, never drop
  * the queue-depth Autoscaler committing conditional PUTs
  * optional fleet telemetry (-telemetry contract shared with kungfu-run)

Also reachable as `kungfu-run -serve ...` (run/__main__.py delegates here).

    python -m kungfu_tpu.serving -np 2 --max-size 3 --platform cpu \
        --preset tiny --slots 4 --timeout 120
    # SERVE_URL: http://127.0.0.1:44581   <- POST /v1/generate
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, Optional

from ..elastic.config_client import ConfigClient
from ..elastic.config_server import ConfigServer
from ..plan import Cluster, HostList, PeerID
from ..utils import get_logger

log = get_logger("kungfu.serving")


def _arm_telemetry(logdir: str) -> None:
    os.environ.setdefault("KFT_CONFIG_ENABLE_MONITORING", "1")
    os.environ.setdefault("KFT_CONFIG_ENABLE_TRACE", "1")
    if not os.environ.get("KFT_JOURNAL_DIR"):
        import tempfile

        os.environ["KFT_JOURNAL_DIR"] = (
            logdir or tempfile.mkdtemp(prefix="kft-serve-telemetry-")
        )
    os.environ.setdefault("KFT_TRACE_DUMP_DIR", os.environ["KFT_JOURNAL_DIR"])


class ServeSupervisor:
    def __init__(self, args, cluster: Cluster, client: ConfigClient):
        from ..run.job import ChipPool
        from ..run.launcher import ProcRunner

        self._proc_runner_cls = ProcRunner
        # one chip for each worker: without it every worker would claim
        # every chip of the host, and the second one to start would fail
        self.pool = (ChipPool(args.chips_per_host)
                     if args.chips_per_host > 0 and args.platform != "cpu"
                     else None)
        self.chips: Dict[PeerID, int] = {}
        self.args = args
        self.client = client
        self.cluster = cluster
        self.version = -1
        self.procs: Dict[PeerID, object] = {}
        self.launch_ranks: Dict[PeerID, int] = {}
        self.incarnations: Dict[PeerID, int] = {}
        self._next_rank = 0
        self.failures = 0

    def _worker_cmd(self, peer: PeerID, rank: int, incarnation: int):
        a = self.args
        cmd = [
            sys.executable, "-m", "kungfu_tpu.serving.worker",
            "--host", peer.host, "--port", str(peer.port),
            "--launch-rank", str(rank), "--incarnation", str(incarnation),
            # the FULL endpoint list, not the currently-active one: the
            # worker must survive its own control-plane failovers
            "--config-server", self.client.urls_spec,
            "--preset", a.preset, "--slots", str(a.slots),
            "--queue-capacity", str(a.worker_queue_capacity),
            "--seed", str(a.seed),
        ]
        tier = self.cluster.tier_of(peer)
        if tier:
            cmd += ["--tier", tier]
        if a.model_json:
            cmd += ["--model-json", a.model_json]
        if a.weights_file:
            cmd += ["--weights-file", a.weights_file]
        if a.prefix_cache != "auto":
            cmd += ["--prefix-cache", a.prefix_cache]
        if a.spec_draft:
            cmd += ["--spec-draft", a.spec_draft,
                    "--spec-k", str(a.spec_k)]
        return cmd

    def _spawn(self, peer: PeerID, incarnation: int) -> None:
        from ..run.job import Proc, chip_env

        chip = None
        if self.pool is not None:
            chip = self.pool.get()
            if chip is None:
                log.error("no free chip for serving worker %s "
                          "(--chips-per-host %d)", peer,
                          self.args.chips_per_host)
                return
            self.chips[peer] = chip
        if peer not in self.launch_ranks:
            self.launch_ranks[peer] = self._next_rank
            self._next_rank += 1
        rank = self.launch_ranks[peer]
        env = dict(os.environ)
        if incarnation > 0:
            # scripted serve faults are one-shot PER LAUNCH RANK: the chaos
            # plan already killed this rank once, and the respawned
            # incarnation's token counter restarts at zero — re-arming the
            # plan would turn one scripted kill into a crash loop
            env.pop("KFT_FAULT_PLAN", None)
        if self.args.platform:
            env["KFT_PLATFORM"] = self.args.platform
            if self.args.platform == "cpu":
                env["JAX_PLATFORMS"] = "cpu"
        if chip is not None:
            env.update(chip_env(chip))  # each replica a one-chip world
        proc = Proc(name=str(rank),
                    args=self._worker_cmd(peer, rank, incarnation),
                    env=env, peer=peer)
        r = self._proc_runner_cls(proc, logdir=self.args.logdir,
                                  quiet=self.args.quiet)
        r.start()
        self.procs[peer] = r
        self.incarnations[peer] = incarnation
        log.info("+ serving worker %s (rank %d, incarnation %d)",
                 peer, rank, incarnation)

    def reconcile(self, cluster: Cluster, version: int) -> None:
        want = set(cluster.workers)
        have = set(self.procs)
        # adopt the document BEFORE spawning: _worker_cmd reads each new
        # worker's tier from it (a tiered autoscale grow names the pool)
        self.cluster = cluster
        self.version = version
        for peer in sorted(have - want):
            r = self.procs.pop(peer)
            r.terminate()
            self._release_chip(peer)
            log.info("- serving worker %s (scaled away at v%d)", peer, version)
        for peer in sorted(want - have):
            self._spawn(peer, self.incarnations.get(peer, -1) + 1)

    def _release_chip(self, peer: PeerID) -> None:
        if self.pool is not None and peer in self.chips:
            self.pool.put(self.chips.pop(peer))

    def collect_dead(self) -> None:
        """A dead worker still in the document respawns in place — the
        serving heal (restart + buddy-weight rejoin), distinct from the
        training healer's shrink."""
        from ..monitor.counters import global_counters
        from ..monitor.journal import journal_event

        for peer, r in list(self.procs.items()):
            rc = r.popen.poll() if r.popen else None
            if rc is None:
                continue
            r.wait()
            del self.procs[peer]
            self._release_chip(peer)
            if rc != 0:
                self.failures += 1
                global_counters().inc_event("serve_worker_failures")
                journal_event("worker_failure", peer=str(peer), rc=rc,
                              serving=True)
                log.warning("serving worker %s died (rc=%d)", peer, rc)
            if peer in set(self.cluster.workers):
                self._spawn(peer, self.incarnations.get(peer, 0) + 1)

    def step(self) -> None:
        got = self.client.poll_cluster()
        if got is not None:
            cluster, version = got
            if version > self.version:
                self.reconcile(cluster, version)
        self.collect_dead()

    def shutdown(self) -> None:
        for peer, r in list(self.procs.items()):
            r.terminate()
        self.procs.clear()


def main(argv=None) -> int:
    from ..env import starts_dir
    from ..monitor import boot
    from ..utils.trace import BOOT_CAT, stamp_job_start, trace_scope

    # the job clock starts with this process, --telemetry or not: every
    # worker it spawns, a respawn too, reads its boot from it
    stamp_job_start()
    boot.enter("supervisor", starts_dir())
    ap = argparse.ArgumentParser(prog="kungfu_tpu.serving",
                                 description="elastic inference serving fleet")
    ap.add_argument("-np", type=int, default=2, help="initial worker count")
    ap.add_argument("--min-size", type=int, default=1)
    ap.add_argument("--max-size", type=int, default=0,
                    help="autoscale ceiling (0: max(np, 4))")
    ap.add_argument("--preset", default="tiny")
    ap.add_argument("--model-json", default="")
    ap.add_argument("--weights-file", default="")
    ap.add_argument("--prefill-ranks", type=int, default=0,
                    help="disaggregate: the first N workers form the "
                         "prefill pool, the rest decode (0: monolithic "
                         "workers, the v1 topology)")
    ap.add_argument("--prefix-cache", default="auto",
                    choices=("auto", "on", "off"),
                    help="radix prefix KV cache on the prefill side "
                         "(auto: KFT_PREFIX_CACHE_MB decides)")
    ap.add_argument("--spec-draft", default="",
                    help="arm speculative decoding on decode/monolithic "
                         "workers: a worker PRESETS name or 'same' "
                         "(self-draft)")
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4,
                    help="KV slots (concurrent requests) per worker")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--port", type=int, default=0, help="router front door")
    ap.add_argument("--config-port", type=int, default=0)
    ap.add_argument("--config-server", default="",
                    help="join an external config server instead of embedding "
                         "(accepts the comma KFT_CONFIG_URLS form)")
    ap.add_argument("--config-replicas", type=int, default=1,
                    help="embedded config plane replica count: >1 spawns a "
                         "leader-leased replicated ensemble with respawn "
                         "supervision (docs/fault_tolerance.md)")
    ap.add_argument("--queue-capacity", type=int, default=256)
    ap.add_argument("--worker-queue-capacity", type=int, default=64)
    ap.add_argument("--platform", default="", help="force worker backend (cpu)")
    ap.add_argument("--chips-per-host", type=int, default=0,
                    help="give each worker one TPU chip of this host; the "
                         "fleet then never grows past this many workers")
    ap.add_argument("--timeout", type=float, default=0.0,
                    help="run this long then exit cleanly (0: forever)")
    ap.add_argument("--no-autoscale", action="store_true")
    ap.add_argument("--telemetry", action="store_true")
    ap.add_argument("--logdir", default="")
    ap.add_argument("-q", dest="quiet", action="store_true")
    args = ap.parse_args(argv)

    if args.max_size <= 0:
        args.max_size = max(args.np, 4)
    args.max_size = max(args.max_size, args.np)
    if args.chips_per_host > 0 and args.platform != "cpu":
        if args.np > args.chips_per_host:
            ap.error(f"-np {args.np} one-chip workers need more than "
                     f"--chips-per-host {args.chips_per_host}")
        args.max_size = min(args.max_size, args.chips_per_host)
    model = json.loads(args.model_json) if args.model_json else {}
    if model.get("mamba_d_state") or model.get("mixer_types"):
        # every kind a list may name keeps state (models/transformer.py
        # LISTED_MIXERS; this parent loads no jax, so it asks the JSON).
        # The worker refuses these too (worker.py), but a worker that dies
        # at boot is respawned: say it once, here, before any is spawned
        asked = [flag for flag, on in (
            ("--prefix-cache on", args.prefix_cache == "on"),
            ("--spec-draft", bool(args.spec_draft)),
            ("--prefill-ranks", args.prefill_ranks > 0)) if on]
        if asked:
            ap.error(f"{', '.join(asked)}: not with a model that keeps "
                     "recurrent state (mamba_d_state > 0, or a list of "
                     "mixer_types): prefix reuse, "
                     "speculation and shipped prefills cut or roll back a "
                     "cache by position, and a state has none (ROADMAP R6)")
    if args.telemetry:
        _arm_telemetry(args.logdir)
        from ..monitor.journal import set_journal_context

        set_journal_context(rank="router", identity="router")

    hosts = HostList.parse(f"127.0.0.1:{args.max_size}")
    cluster = Cluster.from_hostlist(hosts, args.np)
    if args.prefill_ranks:
        cluster = cluster.assign_tiers(args.prefill_ranks)

    cs: Optional[ConfigServer] = None
    ensemble = None
    with trace_scope(boot.boot_name("config_server"), cat=BOOT_CAT):
        if args.config_server:
            client = ConfigClient(args.config_server)
        elif args.config_replicas > 1:
            from ..elastic.ensemble import ConfigEnsemble

            ensemble = ConfigEnsemble(replicas=args.config_replicas,
                                      init=cluster).start()
            client = ensemble.client()
        else:
            cs = ConfigServer(host="127.0.0.1", port=args.config_port,
                              init=cluster).start()
            client = ConfigClient(cs.url)
    print(f"CONFIG_URL: {client.urls_spec}", flush=True)

    from ..monitor.counters import counters_if_enabled

    counters = counters_if_enabled()
    with trace_scope(boot.boot_name("router"), cat=BOOT_CAT):
        from .router import Autoscaler, Router
        from .tenancy import TenantRegistry

        # tenancy is opt-in: no KFT_TENANTS_FILE (and no KV document) means
        # None, and the router keeps the v1 single-tenant FIFO path; workers
        # pick the same file up from their inherited environment
        tenants = TenantRegistry.from_env(client=client)
        if tenants is not None:
            print(f"TENANTS: {sorted(tenants.tenants())}", flush=True)
        # tenanted fleets need dispatch concurrency past the fleet's slot
        # budget: preemption evidence only exists when ENGINE queues back
        # up, and the default dispatcher pool (sized for one worker) would
        # cap in-flight work below total slots and starve them of it
        dispatchers = 2 * args.slots * max(1, args.max_size) if tenants else 0
        router = Router(
            slots_per_worker=args.slots, queue_capacity=args.queue_capacity,
            counters=counters, tenants=tenants,
        ).start(port=args.port, dispatchers=dispatchers)
    print(f"SERVE_URL: http://127.0.0.1:{router.port}", flush=True)

    fleet = None
    if args.telemetry:
        from ..monitor.fleet import FleetAggregator, targets_from_workers

        def _targets():
            got = client.poll_cluster()
            workers = got[0].workers if got is not None else cluster.workers
            return targets_from_workers(workers)

        fleet = FleetAggregator(targets_fn=_targets).start()
        print(f"TELEMETRY_URL: http://127.0.0.1:{fleet.port}", flush=True)
        print(f"TELEMETRY_DIR: {os.environ.get('KFT_JOURNAL_DIR', '')}",
              flush=True)

    scaler = None
    if not args.no_autoscale:
        scale_kw = dict(
            hi_depth=int(os.environ.get("KFT_SERVE_SCALE_UP_DEPTH", "4")),
            up_after=int(os.environ.get("KFT_SERVE_SCALE_UP_TICKS", "2")),
            down_after=int(os.environ.get("KFT_SERVE_SCALE_DOWN_TICKS", "12")),
            tick_s=float(os.environ.get("KFT_SERVE_TICK_S", "0.5")),
            counters=counters,
        )
        if args.prefill_ranks:
            # tiered pools size themselves from queue COMPOSITION
            from .disagg import TieredAutoscaler

            scaler = TieredAutoscaler(client, router,
                                      max_size=args.max_size, **scale_kw)
        else:
            scaler = Autoscaler(client, router, min_size=args.min_size,
                                max_size=args.max_size, **scale_kw)
        scaler.start()

    from ..run.launcher import install_signal_trap

    install_signal_trap()
    sup = ServeSupervisor(args, cluster, client)
    t0 = time.monotonic()
    rc = 0
    try:
        sup.reconcile(cluster, 0)
        while True:
            sup.step()
            router.set_workers(sup.cluster.workers, sup.cluster.tiers)
            if args.timeout and time.monotonic() - t0 > args.timeout:
                log.info("serve timeout after %.0fs; clean shutdown",
                         args.timeout)
                break
            time.sleep(0.05)
    except KeyboardInterrupt:
        pass
    finally:
        stats = router.stats()
        stats["worker_failures"] = sup.failures
        print("SERVE_STATS: " + json.dumps(stats), flush=True)
        if scaler is not None:
            print("AUTOSCALE_EVENTS: " + json.dumps(scaler.events),
                  flush=True)
            scaler.stop()
        sup.shutdown()
        router.close()
        if fleet is not None:
            fleet.close()
        if cs is not None:
            cs.stop()
        if ensemble is not None:
            ensemble.stop()
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
