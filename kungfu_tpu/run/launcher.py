"""Process supervisor — the kungfu-run equivalent.

Reference: srcs/go/kungfu/runner/{simple,watch}.go + utils/runner/local:
static mode spawns every local worker in parallel and tees their output with
per-rank prefixes; watch mode additionally polls the elastic config service
and creates/kills workers as the cluster document changes (the reference gets
pushed Stage updates over its TCP control channel; polling the config server
is the deliberate HTTP-only re-design — workers PUT, runners GET).
"""
from __future__ import annotations

import os
import random
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from ..elastic.config_client import ConfigClient
from ..monitor.counters import global_counters
from ..monitor.journal import journal_event
from ..plan import Cluster, PeerID, PeerList
from ..utils import get_logger
from .job import ChipPool, Job, Proc

log = get_logger("kungfu.run")

_COLORS = [36, 32, 33, 35, 34, 31]  # cyan green yellow magenta blue red


class RemoteHostJudge:
    """Partition-vs-death judgment for REMOTE hosts (docs/fault_tolerance.md
    "network failure model").

    The local healer only sees local worker exits; a whole host lost to
    `kill_host` leaves no launcher behind to heal it, and a network
    partition makes every cross-partition peer *look* dead from inside the
    data plane.  The distinguishing signal is the runner heartbeat each
    launcher writes to the config server's KV plane (`runner-hb/<host>`,
    stamped with the SERVER's receive time — no cross-host clock compare):
    the control plane rides a different network than the data plane in real
    pods, so a partitioned-but-alive host keeps beating while a dead one
    goes silent.

      host stale      heartbeat missing/old past `stale_after_s` — journal
                      `host_suspected`, start the suspicion clock.  A
                      heartbeat that returns mid-window journals
                      `host_suspect_cleared` and NO shrink happens.
      host dead       stale continuously for `suspicion_s` — the LEADER
                      (first runner-doc host with a fresh heartbeat)
                      CAS-shrinks ALL of that host's workers out in one
                      conditional PUT: exactly one shrink per real host
                      death, by construction (losers of the CAS re-read
                      and find the host already gone).
      partition       workers report suspected-dead peers (`suspect/<peer>`
                      KV entries, written on entering recovery) while every
                      runner heartbeat stays fresh — journal
                      `partition_suspected`, never shrink, and have the
                      leader nudge a `reconvene` version bump every
                      `reconvene_interval_s` so the waiting workers
                      re-rendezvous (at unchanged membership) as soon as
                      the partition heals.

    Pure state machine — HTTP and process control stay in WatchRunner, so
    the judgment is unit-testable with synthetic tables.
    """

    def __init__(self, self_host: str, suspicion_s: float = 10.0,
                 stale_after_s: float = 3.0, reconvene_interval_s: float = 0.0,
                 journal=journal_event, counters=None):
        self.self_host = self_host
        self.suspicion_s = float(suspicion_s)
        self.stale_after_s = float(stale_after_s)
        self.reconvene_interval_s = float(reconvene_interval_s) or max(
            2.0 * self.suspicion_s, 5.0
        )
        self.journal = journal
        self.counters = counters
        self._suspected_since: Dict[str, float] = {}
        self._journaled: set = set()
        self.partition_active = False
        self._last_reconvene = -1e18

    def clear(self, host: str) -> None:
        """Forget a host's suspicion (after its shrink, or when it left the
        document)."""
        self._suspected_since.pop(host, None)
        self._journaled.discard(host)

    def assess(self, cluster: Cluster, hb: Dict[str, dict],
               suspects: Dict[str, dict], now: float,
               version: Optional[int] = None) -> Dict[str, object]:
        """One judgment sweep.

        Args:
          cluster: the current document.
          hb: `runner-hb/` KV entries ({key: {"t_server": float, ...}}).
          suspects: `suspect/` KV entries (worker recovery reports).
          now: the SERVER's clock from the same kv_list response.
          version: the current document version — suspects filed against an
            OLDER version are explained by the membership change that
            followed them (their filers are re-rendezvousing, not
            partitioned) and carry no partition evidence.

        Returns {"leader": bool, "shrink": [host, ...], "partition": bool,
        "reconvene": bool, "stale": {host: age_or_None}}.
        """
        worker_hosts = cluster.workers.hosts()
        runner_hosts = [r.host for r in cluster.runners]

        def age_of(host: str):
            if host == self.self_host:
                return 0.0  # we are alive by construction
            e = hb.get(f"runner-hb/{host}")
            return None if e is None else max(0.0, now - float(e.get("t_server", 0.0)))

        fresh = {h for h in runner_hosts
                 if (lambda a: a is not None and a <= self.stale_after_s)(age_of(h))}
        fresh.add(self.self_host)
        leader_host = next((h for h in runner_hosts if h in fresh), self.self_host)
        leader = leader_host == self.self_host

        stale: Dict[str, object] = {}
        shrink = []
        for host in worker_hosts:
            if host == self.self_host:
                continue
            age = age_of(host)
            if host in fresh:
                if host in self._suspected_since:
                    self._suspected_since.pop(host)
                    if host in self._journaled:
                        self._journaled.discard(host)
                        log.info("host %s heartbeat returned; suspicion "
                                 "cleared", host)
                        self.journal("host_suspect_cleared", host=host)
                continue
            stale[host] = None if age is None else round(age, 2)
            since = self._suspected_since.get(host)
            # a host that NEVER beat gets a doubled window and a quiet
            # clock: launcher boot staggering at fleet start must neither
            # read as death nor spam the journal; a host that beat and
            # went silent is suspected (journaled) immediately
            window = self.suspicion_s * (2.0 if age is None else 1.0)
            if since is None:
                self._suspected_since[host] = now
            if host not in self._journaled and (
                    age is not None
                    or now - self._suspected_since[host] >= window / 2.0):
                self._journaled.add(host)
                log.warning("host %s heartbeat %s; suspecting (window %.1fs)",
                            host, "missing" if age is None else f"stale {age:.1f}s",
                            window)
                self.journal("host_suspected", host=host,
                             age_s=stale[host], window_s=window)
                if self.counters is not None:
                    self.counters.inc_event("hosts_suspected")
            if since is not None and now - since >= window:
                shrink.append(host)
        # drop suspicion state for hosts that left the document entirely
        for host in list(self._suspected_since):
            if host not in worker_hosts:
                self._suspected_since.pop(host)
                self._journaled.discard(host)

        # partition: recovery reports with every runner heartbeat fresh.
        # Any stale host explains the suspects as a (suspected) death
        # instead, so the two judgments never fire together.  The evidence
        # must also be OLDER than the staleness threshold: right after a
        # host dies its heartbeat is still fresh for up to stale_after_s,
        # and declaring a partition in that gap would reconvene a document
        # that still contains the dead host (guaranteed failed rendezvous).
        def _is_evidence(entry: dict) -> bool:
            if version is not None:
                try:
                    filed_at = int((entry.get("value") or {}).get(
                        "cluster_version", -1))
                except (TypeError, ValueError):
                    filed_at = -1
                if filed_at < version:
                    return False  # a membership change already answered it
            return True

        live_suspects = sorted(
            k.split("/", 1)[1] for k, v in suspects.items()
            if k.startswith("suspect/") and _is_evidence(v)
        )
        evidence_aged = any(
            now - float(v.get("t_server", now)) >= self.stale_after_s + 1.0
            for k, v in suspects.items()
            if k.startswith("suspect/") and _is_evidence(v)
        )
        partition = bool(live_suspects) and evidence_aged and not stale
        if partition and not self.partition_active:
            log.warning("partition suspected: %d worker(s) report dead peers "
                        "but every runner heartbeat is fresh — NOT shrinking",
                        len(live_suspects))
            self.journal("partition_suspected", suspects=live_suspects,
                         hosts=worker_hosts)
            if self.counters is not None:
                self.counters.inc_event("partitions_suspected")
        elif self.partition_active and not live_suspects:
            self.journal("partition_cleared", hosts=worker_hosts)
        self.partition_active = partition

        reconvene = False
        if partition and leader and (
                now - self._last_reconvene >= self.reconvene_interval_s):
            self._last_reconvene = now
            reconvene = True
        return {"leader": leader, "shrink": shrink, "partition": partition,
                "reconvene": reconvene, "stale": stale}


def install_signal_trap() -> None:
    """Route SIGTERM into the KeyboardInterrupt cleanup paths so a killed
    launcher (timeout, supervisor, Ctrl-C on a different tty) never orphans
    its worker processes (reference utils.Trap; watch.go kills procs on
    job stop).  No-op off the main thread."""

    def _raise(signum, frame):  # noqa: ARG001
        # one-shot: supervisors re-send SIGTERM; a second conversion would
        # raise inside the cleanup path and abandon the remaining workers
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        raise KeyboardInterrupt(f"signal {signum}")

    try:
        signal.signal(signal.SIGTERM, _raise)
    except ValueError:  # pragma: no cover - non-main thread (tests)
        pass


class ProcRunner:
    """One worker subprocess with output pumping (utils/runner/local/local.go)."""

    def __init__(self, proc: Proc, logdir: str = "", quiet: bool = False):
        self.proc = proc
        self.logdir = logdir
        self.quiet = quiet
        self.popen: Optional[subprocess.Popen] = None
        self._pump: Optional[threading.Thread] = None

    def start(self) -> None:
        from ..env import PROC_START
        from ..monitor import boot
        from ..utils.trace import BOOT_CAT, trace_scope

        stdout = subprocess.PIPE
        with trace_scope(boot.boot_name("spawn"), cat=BOOT_CAT,
                         args={"worker": self.proc.name}):
            # the spawn, on the wall clock the child shares: where its
            # `boot:interpreter` starts (a respawn gets its own)
            self.proc.env[PROC_START] = repr(time.time())
            self.popen = subprocess.Popen(
                self.proc.args,
                env=self.proc.env,
                stdout=stdout,
                stderr=subprocess.STDOUT,
                text=True,
                bufsize=1,
            )
        boot.launcher_spawned()
        logfile = None
        if self.logdir:
            os.makedirs(self.logdir, exist_ok=True)
            logfile = open(os.path.join(self.logdir, f"worker-{self.proc.name}.log"), "w")
        color = _COLORS[int(self.proc.name) % len(_COLORS)] if self.proc.name.isdigit() else 37
        prefix = f"\x1b[{color}m[{self.proc.name}]\x1b[0m " if sys.stdout.isatty() else f"[{self.proc.name}] "

        def pump():
            assert self.popen and self.popen.stdout
            for line in self.popen.stdout:
                if logfile:
                    logfile.write(line)
                    logfile.flush()
                if not self.quiet:
                    sys.stdout.write(prefix + line)
                    sys.stdout.flush()
            if logfile:
                logfile.close()

        self._pump = threading.Thread(target=pump, daemon=True)
        self._pump.start()

    def wait(self) -> int:
        assert self.popen
        rc = self.popen.wait()
        if self._pump:
            self._pump.join(timeout=5)
        return rc

    def terminate(self, grace_s: float = 5.0) -> None:
        if self.popen and self.popen.poll() is None:
            self.popen.terminate()
            try:
                self.popen.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                self.popen.kill()
                self.popen.wait()


def simple_run(job: Job, cluster: Cluster, self_host: str, version: int = 0,
               logdir: str = "", quiet: bool = False, keep: bool = False) -> int:
    """Static mode (runner/simple.go:13-21): spawn all local workers, wait.

    On any worker failure, kill the rest (unless keep) and return its code.
    """
    local = [p for p in cluster.workers if p.host == self_host]
    pool = ChipPool(job.chips_per_host) if job.chips_per_host else None
    runners: List[ProcRunner] = []
    failed = 0
    try:
        # spawning inside the protected region: a SIGTERM mid-startup must
        # still terminate the workers already running
        for peer in local:
            chip = pool.get() if pool else -1
            proc = job.new_proc(peer, chip if chip is not None else -1, cluster, version)
            r = ProcRunner(proc, logdir=logdir, quiet=quiet)
            r.start()
            runners.append(r)
        log.info("spawned %d/%d workers on %s", len(local), cluster.size(), self_host)

        pending = list(runners)
        while pending:
            for r in list(pending):
                rc = r.popen.poll() if r.popen else None
                if rc is None:
                    continue
                r.wait()  # joins the output pump: don't lose tail lines
                pending.remove(r)
                if rc != 0:
                    failed = failed or rc
                    log.error("worker %s exited with %d", r.proc.name, rc)
                    if not keep:  # fail fast: kill the rest (watch.go:144-149)
                        for other in pending:
                            other.terminate()
                        pending = []
                        break  # snapshot is stale now: stop this sweep
            time.sleep(0.05)
    except KeyboardInterrupt:
        for r in runners:
            r.terminate()
        return 130
    return failed


class WatchRunner:
    """Watch mode (runner/watch.go:42-135): reconcile local procs against the
    config service's cluster document as its version advances.

    With heal=True the runner is a *self-healing supervisor*: an unplanned
    local worker death (non-zero exit, or a heartbeat gone stale past
    `heartbeat_timeout_s`) no longer stops the job — the dead peer is
    removed from the cluster document (conditional PUT, prefix-preserving so
    the surviving head keeps rank 0) and the survivors pick the shrunk
    cluster up through the normal run_elastic resize path.  Each worker
    additionally gets `restart_budget` automatic restarts: after an
    exponentially backed-off delay the healer re-grows the document with the
    peer, and the ordinary watch reconcile re-spawns it as a joiner.
    """

    def __init__(self, job: Job, self_host: str, client: ConfigClient,
                 logdir: str = "", quiet: bool = False, keep: bool = False,
                 poll_s: float = 0.5, heal: bool = False, restart_budget: int = 0,
                 heartbeat_timeout_s: float = 0.0, restart_backoff_s: float = 2.0,
                 suspicion_s: float = 0.0, runner_hb_interval_s: float = 1.0):
        self.job = job
        self.self_host = self_host
        self.client = client
        self.logdir = logdir
        self.quiet = quiet
        self.keep = keep
        self.poll_s = poll_s
        self.heal = heal
        self.restart_budget = restart_budget
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.restart_backoff_s = restart_backoff_s
        # remote-host judgment (partition vs death — RemoteHostJudge): armed
        # in heal mode whenever the config client speaks the KV plane.  The
        # suspicion window defaults off the local heartbeat timeout so a
        # whole-host loss is judged on the same timescale as a hung worker.
        self.suspicion_s = suspicion_s or (
            2.0 * heartbeat_timeout_s if heartbeat_timeout_s > 0 else 10.0
        )
        self.runner_hb_interval_s = runner_hb_interval_s
        self._judge = RemoteHostJudge(
            self_host, suspicion_s=self.suspicion_s,
            stale_after_s=max(3.0 * runner_hb_interval_s, 3.0),
            counters=global_counters(),
        ) if heal else None
        self._last_hb_put = -1e18
        self._last_hosts: Optional[set] = None
        self.current: Dict[PeerID, ProcRunner] = {}
        self.pool: Optional[ChipPool] = (
            ChipPool(job.chips_per_host) if job.chips_per_host else None
        )
        self.version = -1
        self.heal_events: List[dict] = []
        self._chip_of: Dict[PeerID, int] = {}
        self._last_want = -1  # local workers wanted at last reconcile
        self._last_cluster_size = -1
        self._idle_since: Optional[float] = None
        self._restarts: Dict[PeerID, int] = {}  # restarts consumed per peer
        self._regrow_at: Dict[PeerID, float] = {}  # scheduled re-grow times
        self._last_rc = 0
        self._healed_to_zero = False
        self._hb_amnesty_until = 0.0  # no staleness kills before this time
        # graded stall judgment (docs/fault_tolerance.md): peer -> (mtime
        # when first seen past the timeout, monotonic time of that sight).
        # A stale-but-ADVANCING heartbeat is slow-but-alive, not hung.
        self._stale_seen: Dict[PeerID, tuple] = {}
        self._slow_journaled_at: Dict[PeerID, float] = {}

    def _spawn(self, peer: PeerID, cluster: Cluster, version: int) -> None:
        chip = self.pool.get() if self.pool else -1
        proc = self.job.new_proc(peer, chip if chip is not None else -1, cluster, version)
        hb = proc.env.get("KFT_HEARTBEAT_FILE")
        if hb:
            # pre-touch: a worker that wedges before its first step still
            # gets the full heartbeat timeout measured from spawn
            os.makedirs(os.path.dirname(hb), exist_ok=True)
            with open(hb, "w"):
                pass
        r = ProcRunner(proc, logdir=self.logdir, quiet=self.quiet)
        r.start()
        self.current[peer] = r
        self._chip_of[peer] = chip if chip is not None else -1
        log.info("[v%d] + worker %s", version, peer)

    def _kill(self, peer: PeerID) -> None:
        r = self.current.pop(peer, None)
        self._stale_seen.pop(peer, None)
        self._slow_journaled_at.pop(peer, None)
        if r is not None:
            r.terminate()
            if self.pool:
                self.pool.put(self._chip_of.pop(peer, -1))
            log.info("- worker %s", peer)

    def reconcile(self, cluster: Cluster, version: int) -> None:
        """Diff old/new local workers; kill removed, spawn added (watch.go:64-83)."""
        want = {p for p in cluster.workers if p.host == self.self_host}
        have = set(self.current)
        for peer in sorted(have - want):
            self._kill(peer)
        for peer in sorted(want - have):
            self._spawn(peer, cluster, version)
        if self.heal:
            # a host that vanished from the document is dead — but a local
            # worker two ring hops away may be blocked in a collective on a
            # perfectly healthy socket (its neighbor is alive, just also
            # blocked) and will never see an error: the ring deadlocks
            # without one.  Killing flows to the dead host alone only frees
            # its direct neighbors, so on a host death the WHOLE dead
            # epoch's cross-host data plane is torn: every blocked read
            # surfaces as a connection abort and the suspected-dead-peer
            # recovery engages NOW instead of at the stall deadline.  (The
            # control plane is untouched — the config server is not a
            # worker host; a just-rebuilt flow caught in the sweep costs
            # one extra recovery lap, never correctness.)
            new_hosts = {p.host for p in cluster.workers}
            old_hosts = self._last_hosts or set()
            vanished = old_hosts - new_hosts - {self.self_host}
            # gate on OUR judge's suspicion: a host that left the document
            # while its runner heartbeat was fresh detached on purpose
            # (planned resize, local heal, preemption) and its epoch tears
            # down gracefully — sweeping there would abort the healthy
            # teardown barrier and the forming next epoch
            suspected = (self._judge._suspected_since
                         if self._judge is not None else {})
            if any(h in suspected for h in vanished):
                root_port = (cluster.workers[0].port if cluster.workers
                             else 10000)
                for host in sorted((old_hosts | new_hosts) - {self.self_host}):
                    self._kill_stale_flows(host, root_port=root_port)
            self._last_hosts = new_hosts
        self.version = version
        self._last_want = len(want)
        self._last_cluster_size = cluster.size()
        if cluster.size() > 0:
            self._healed_to_zero = False  # an operator/regrow PUT revived the job

    def _stalest_worker(self):
        """(age, peer, runner) for the most-stale *frozen* worker, or None.

        A hung rank wedges its peers too (they block in the collective
        waiting for it), but THEIR stall watchdogs keep their heartbeat
        files fresh — only the truly wedged worker (no monitored op running,
        chaos `hang@...`) goes stale.

        The judgment is GRADED, not binary alive/hung: a heartbeat past the
        timeout whose mtime is still ADVANCING between sweeps belongs to a
        slow-but-alive worker — journaled `worker_slow` (the straggler
        observatory's business, and the detector fingers it long before
        this path triggers) and never killed.  Only a heartbeat frozen at
        the SAME mtime for a further full timeout is judged hung — so a
        genuinely frozen worker dies at ~2x the timeout, and a rank that is
        merely 10x slower than its peers survives to be diagnosed.  The
        healer still kills only ONE worker per sweep, stalest first, and
        then grants an amnesty window: killing the hung rank frees the
        others into recovery, and they must get a full timeout to
        rendezvous before staleness is re-judged.
        """
        if not (self.heal and self.heartbeat_timeout_s > 0):
            return None
        if time.monotonic() < self._hb_amnesty_until:
            return None
        worst = None
        for peer, r in self.current.items():
            if r.popen is None or r.popen.poll() is not None:
                continue  # finished procs are the exit-code path's business
            hb = r.proc.env.get("KFT_HEARTBEAT_FILE")
            if not hb:
                continue
            try:
                mtime = os.path.getmtime(hb)
            except OSError:
                continue  # pre-touched at spawn; missing means already healed
            age = time.time() - mtime
            if age <= self.heartbeat_timeout_s:
                self._stale_seen.pop(peer, None)
                continue
            seen = self._stale_seen.get(peer)
            if seen is None or seen[0] != mtime:
                # stale, but the heartbeat moved since the last judgment:
                # slow-but-alive — record the new mtime and give it a full
                # further timeout to advance again before calling it hung
                self._stale_seen[peer] = (mtime, time.monotonic())
                now = time.monotonic()
                if now - self._slow_journaled_at.get(peer, -1e9) > self.heartbeat_timeout_s:
                    self._slow_journaled_at[peer] = now
                    log.warning("worker %s heartbeat stale %.1fs but advancing"
                                " — slow-but-alive, not killing", peer, age)
                    global_counters().inc_event("workers_slow")
                    journal_event("worker_slow", peer=str(peer),
                                  age_s=round(age, 1),
                                  timeout_s=self.heartbeat_timeout_s)
                continue
            frozen_for = time.monotonic() - seen[1]
            if frozen_for < self.heartbeat_timeout_s:
                continue  # same mtime, but not frozen long enough yet
            if worst is None or age > worst[0]:
                worst = (age, peer, r)
        return worst

    def _heal_dead(self, peer: PeerID, rc: int) -> None:
        """Remove a dead local worker from the cluster document (shrink to
        survive), then schedule a budgeted restart.

        The removal keeps the survivors' relative order (a pure deletion),
        so the surviving head stays rank 0 — the reference's "new root must
        be an old worker" guard (peer.go:211-222) holds by construction.
        Conditional PUTs make concurrent heals from other hosts safe: a
        version conflict re-reads the document and re-derives the shrink.
        """
        counters = global_counters()
        counters.inc_event("worker_failures")
        journal_event("worker_failure", peer=str(peer), rc=rc)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            got = self.client.poll_cluster()
            if got is None:
                time.sleep(self.poll_s)
                continue
            cluster, version = got
            if cluster.workers.rank(peer) is None:
                # planned detach (preemption self-removal or an operator
                # shrink) that raced our exit collection: nothing to heal
                log.info("worker %s already absent from v%d; no heal needed", peer, version)
                return
            shrunk = Cluster(
                runners=cluster.runners,
                workers=PeerList(p for p in cluster.workers if p != peer),
            )
            if not self.client.put_cluster(shrunk, version=version):
                continue  # lost the CAS race or flap: re-read and retry
            log.warning(
                "HEAL: worker %s died (rc=%d); cluster %d -> %d workers (v%d -> v%d)",
                peer, rc, cluster.size(), shrunk.size(), version, version + 1,
            )
            self.heal_events.append({
                "peer": str(peer), "rc": rc,
                "old_size": cluster.size(), "new_size": shrunk.size(),
                "version": version + 1,
            })
            counters.inc_event("heals")
            journal_event("heal_shrink", peer=str(peer), rc=rc,
                          old_size=cluster.size(), new_size=shrunk.size(),
                          cluster_version=version + 1)
            self._healed_to_zero = shrunk.size() == 0
            self._schedule_restart(peer)
            return
        log.error("heal of %s gave up: config server unreachable for 30s", peer)

    def _schedule_restart(self, peer: PeerID) -> None:
        used = self._restarts.get(peer, 0)
        if used >= self.restart_budget:
            if self.restart_budget:
                log.warning("restart budget exhausted for %s (%d used)", peer, used)
            return
        self._restarts[peer] = used + 1
        # exponential backoff + jitter: transient crashes (OOM burst, flaky
        # host) get a quick retry, crash-loops back off and burn the budget
        delay = min(self.restart_backoff_s * (2 ** used), 60.0)
        delay *= 0.8 + 0.4 * random.random()
        self._regrow_at[peer] = time.monotonic() + delay
        log.info("restart %d/%d of %s scheduled in %.1fs",
                 used + 1, self.restart_budget, peer, delay)

    def _remote_tick(self) -> None:
        """Runner heartbeat + remote-host judgment, once per
        `runner_hb_interval_s` (docs/fault_tolerance.md "network failure
        model").  Every HTTP leg is best-effort: a control-plane brownout
        skips the sweep, never kills the launcher."""
        if self._judge is None:
            return
        kv_put = getattr(self.client, "kv_put", None)
        kv_list = getattr(self.client, "kv_list", None)
        if kv_put is None or kv_list is None:  # test doubles without KV
            return
        now = time.monotonic()
        if now - self._last_hb_put < self.runner_hb_interval_s:
            return
        self._last_hb_put = now
        kv_put(f"runner-hb/{self.self_host}", {"pid": os.getpid()})
        got = self.client.poll_cluster()
        if got is None:
            return
        cluster, version = got
        if cluster.workers.host_count() <= 1:
            return  # nothing remote to judge
        hb = kv_list("runner-hb/")
        suspects = kv_list("suspect/")
        if hb is None or suspects is None:
            return
        actions = self._judge.assess(cluster, hb.get("entries", {}),
                                     suspects.get("entries", {}),
                                     float(hb.get("now", 0.0)),
                                     version=version)
        if actions["reconvene"]:
            reconvene = getattr(self.client, "reconvene_cluster", None)
            if reconvene is not None and reconvene(cluster, version):
                log.warning("reconvene: bumped document to v%d at unchanged "
                            "membership (partition-heal nudge)", version + 1)
                journal_event("reconvene", cluster_version=version + 1,
                              size=cluster.size())
        if not actions["leader"]:
            return  # a non-leader never shrinks: exactly-one-CAS guarantee
        for host in actions["shrink"]:
            self._shrink_host(host)

    def _shrink_host(self, host: str) -> None:
        """Leader-side shrink of a dead host: remove ALL its workers in one
        conditional PUT (correlated loss heals as one membership change,
        not K racing ones)."""
        got = self.client.poll_cluster()
        if got is None:
            return
        cluster, version = got
        victims = [p for p in cluster.workers if p.host == host]
        if not victims:
            self._judge.clear(host)  # someone else healed it: stand down
            return
        # the RUNNER goes too: a dead host has no launcher left to spawn
        # workers, so leaving it in the document would let a schedule-driven
        # grow place a worker nobody can start (a restarted host rejoins via
        # an operator POST of a fresh document)
        shrunk = Cluster(
            runners=PeerList(r for r in cluster.runners if r.host != host),
            workers=PeerList(p for p in cluster.workers if p.host != host),
        )
        if not self.client.put_cluster(shrunk, version=version):
            return  # CAS lost: re-read next tick (maybe already healed)
        log.warning(
            "HOST HEAL: %s silent past %.1fs suspicion; cluster %d -> %d "
            "workers (v%d -> v%d, %d ranks removed at once)",
            host, self.suspicion_s, cluster.size(), shrunk.size(),
            version, version + 1, len(victims),
        )
        self.heal_events.append({
            "host": host, "workers": [str(p) for p in victims],
            "old_size": cluster.size(), "new_size": shrunk.size(),
            "version": version + 1,
        })
        global_counters().inc_event("host_heals")
        journal_event("host_heal_shrink", host=host,
                      workers=[str(p) for p in victims],
                      old_size=cluster.size(), new_size=shrunk.size(),
                      cluster_version=version + 1)
        self._judge.clear(host)
        kv_delete = getattr(self.client, "kv_delete", None)
        if kv_delete is not None:
            for p in victims:
                kv_delete(f"suspect/{p}")  # dead workers' reports are moot
        # survivors now tear down + re-rendezvous: restart their staleness
        # clock like the local heal path does
        self._hb_amnesty_until = time.monotonic() + max(
            self.heartbeat_timeout_s, self.suspicion_s
        )

    @staticmethod
    def _kill_stale_flows(host: str, root_port: int = 10000) -> None:
        """RST the local data-plane TCP flows to `host` (ss -K,
        SOCK_DESTROY) — the fabric-manager nudge that turns a silent
        dead-host deadlock into an immediate, catchable connection abort.

        The version-fenced coordinator window is EXEMPT: killing a worker's
        link to the coordination service makes jaxlib's error-poll thread
        terminate the whole process (std::bad_cast from a C++ thread) —
        the agent connection is torn down by the worker's own recovery
        instead.  Best-effort: kernels without INET_DIAG_DESTROY (or no ss
        binary) just skip it and the stall deadline remains the backstop."""
        import shutil

        from ..peer import COORDINATOR_PORT_OFFSET, COORDINATOR_PORT_WINDOW

        if shutil.which("ss") is None:
            return
        lo = root_port + COORDINATOR_PORT_OFFSET
        hi = lo + COORDINATOR_PORT_WINDOW
        # both halves of a coordination-service connection are exempt: the
        # agent side addresses the window as dport, the service side sees
        # it as its OWN sport (the agent's end is ephemeral)
        r = subprocess.run(
            ["ss", "-K", "dst", host,
             "(", "dport", "lt", f":{lo}", "or", "dport", "gt", f":{hi}", ")",
             "and",
             "(", "sport", "lt", f":{lo}", "or", "sport", "gt", f":{hi}", ")"],
            capture_output=True, text=True)
        log.warning("killed stale TCP flows to vanished-epoch host %s (rc=%d)",
                    host, r.returncode)
        journal_event("stale_flows_killed", host=host)

    def _process_regrows(self) -> None:
        now = time.monotonic()
        for peer, due in list(self._regrow_at.items()):
            if now < due:
                continue
            got = self.client.poll_cluster()
            if got is None:
                return  # outage: retry on a later tick
            cluster, version = got
            if cluster.workers.rank(peer) is not None:
                del self._regrow_at[peer]  # someone already re-added it
                continue
            regrown = Cluster(
                runners=cluster.runners,
                workers=PeerList(tuple(cluster.workers) + (peer,)),
            )
            try:
                regrown.validate()
            except ValueError as e:  # host no longer in the runner set
                log.warning("cannot restart %s: %s", peer, e)
                del self._regrow_at[peer]
                continue
            if self.client.put_cluster(regrown, version=version):
                del self._regrow_at[peer]
                global_counters().inc_event("worker_restarts")
                journal_event("worker_restart", peer=str(peer),
                              size=regrown.size(), cluster_version=version + 1)
                log.info("RESTART: re-grew %s into the cluster (%d workers at v%d)",
                         peer, regrown.size(), version + 1)
            # CAS conflict: leave it scheduled; next tick re-reads

    def run(self, initial: Optional[Cluster] = None, timeout_s: float = 0.0) -> int:
        t0 = time.monotonic()
        try:
            # initial spawn inside the protected region: a SIGTERM during
            # startup must still terminate already-running workers
            if initial is not None:
                self.reconcile(initial, 0)
            while True:
                got = self.client.poll_cluster()
                if got is not None:
                    cluster, version = got
                    if version > self.version:
                        self.reconcile(cluster, version)
                if self.heal and self._regrow_at:
                    self._process_regrows()
                # remote-host judgment: runner heartbeat + partition-vs-death
                # sweep (kill_host leaves no local launcher to heal it)
                self._remote_tick()
                # hang detection: kill (at most) the stalest wedged worker so
                # its exit joins the ordinary dead-proc collection below
                stale = self._stalest_worker()
                if stale is not None:
                    age, speer, r = stale
                    log.error(
                        "worker %s heartbeat stale %.1fs > %.1fs; killing it",
                        speer, age, self.heartbeat_timeout_s,
                    )
                    journal_event("stall_kill", peer=str(speer),
                                  age_s=round(age, 1),
                                  timeout_s=self.heartbeat_timeout_s)
                    r.terminate(grace_s=0.5)
                    self._hb_amnesty_until = (
                        time.monotonic() + self.heartbeat_timeout_s
                    )
                # collect finished procs
                for peer, r in list(self.current.items()):
                    rc = r.popen.poll() if r.popen else None
                    if rc is None:
                        continue
                    r.wait()  # joins the output pump: don't lose tail lines
                    del self.current[peer]
                    if self.pool:
                        self.pool.put(self._chip_of.pop(peer, -1))
                    if rc != 0:
                        self._last_rc = rc
                        if self.heal:
                            self._heal_dead(peer, rc)
                            # survivors now recover + re-rendezvous: their
                            # heartbeats may pause at phase edges, so restart
                            # the staleness clock for everyone
                            self._hb_amnesty_until = (
                                time.monotonic() + self.heartbeat_timeout_s
                            )
                        elif not self.keep:
                            log.error("worker %s failed (%d); stopping job", peer, rc)
                            self.shutdown()
                            return rc
                if (self.heal and self._healed_to_zero
                        and not self.current and not self._regrow_at):
                    # healed the whole job away with no restarts pending:
                    # surface the last failure instead of idling forever
                    log.error("cluster healed to zero workers; job failed")
                    return self._last_rc or 1
                if not self.current and self.version >= 0:
                    if self._last_want > 0:
                        log.info("all workers exited")
                        return 0
                    # this host was shrunk to zero workers: the job continues
                    # elsewhere and a future version may regrow us (the
                    # reference watcher keeps waiting for Stage updates,
                    # watch.go:106-135).  The job's end is signalled by the
                    # config server going away (the runner embedding it stops
                    # it on exit); a long wall-clock threshold rides out
                    # transient restarts (which must not permanently remove
                    # this host) and is immune to how long each poll takes
                    # now that the client retries internally.
                    if got is None:
                        if self._idle_since is None:
                            self._idle_since = time.monotonic()
                        elif time.monotonic() - self._idle_since >= 60.0:
                            log.info("idle host: config server gone; exiting")
                            return 0
                    else:
                        self._idle_since = None
                if timeout_s and time.monotonic() - t0 > timeout_s:
                    log.error("watch timeout after %.0fs", timeout_s)
                    self.shutdown()
                    return 124
                time.sleep(self.poll_s)
        except KeyboardInterrupt:
            self.shutdown()
            return 130
        except Exception:
            self.shutdown()  # never leave workers orphaned
            raise

    def shutdown(self) -> None:
        for peer in list(self.current):
            self._kill(peer)
