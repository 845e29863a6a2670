"""Peer — process membership and lifecycle.

Re-design of the reference Peer (srcs/go/kungfu/peer/peer.go:27-48): a Peer
owns this process's identity, the current Cluster document + version, and the
current Session.  Where the reference Peer owns a TCP router/server, the TPU
Peer owns the `jax.distributed` runtime: on a multi-host pod each worker
process joins the coordination service, and the data plane is the compiled
XLA program over the global mesh.

Version fencing: the coordinator port is derived from the cluster version, so
peers on a stale cluster config cannot rendezvous with the new one — the
analog of the cluster-version token check on collective connections
(srcs/go/rchannel/connection/connection.go:81-87).
"""
from __future__ import annotations

import atexit
from typing import Optional

import jax

from . import env as kfenv
from .plan import Cluster, PeerID, PeerList, Strategy, make_mesh, make_hierarchical_mesh
from .session import Session
from .utils import get_logger, stall_detector
from .utils.trace import backend_devices

log = get_logger("kungfu.peer")

COORDINATOR_PORT_OFFSET = 20000
# versions cycle through a fixed window of ports: long-running elastic jobs
# bump the cluster version unboundedly, and port+20000+version would walk
# past 65535 (or into other services' ranges).  The window only needs to
# fence CONSECUTIVE versions from each other — a stale peer is at most a few
# versions behind — so a modest cycle is safe, and the wrap stays clear of
# the Linux ephemeral range (32768+) for default worker ports (10000-10999:
# coordinators at 30000-30999 + window).
COORDINATOR_PORT_WINDOW = 1000


def coordinator_port(root_port: int, cluster_version: int) -> int:
    """Version-fenced jax.distributed coordinator port, bounded and cyclic.

    The range check covers the WHOLE window, not the current version, so a
    borderline root port fails at startup instead of hours into an elastic
    job when the version modulo climbs.
    """
    if not (0 < root_port + COORDINATOR_PORT_OFFSET + COORDINATOR_PORT_WINDOW - 1 <= 65535):
        raise ValueError(
            f"worker port {root_port} leaves no room for the coordinator "
            f"window (+{COORDINATOR_PORT_OFFSET}+{COORDINATOR_PORT_WINDOW} "
            f"exceeds 65535); pick worker ports <= "
            f"{65535 - COORDINATOR_PORT_OFFSET - COORDINATOR_PORT_WINDOW + 1}"
        )
    return root_port + COORDINATOR_PORT_OFFSET + (cluster_version % COORDINATOR_PORT_WINDOW)


class Peer:
    def __init__(self, config: Optional[kfenv.Config] = None):
        self.config = config if config is not None else kfenv.parse_config_from_env()
        self.cluster_version = self.config.cluster_version
        self.detached = False
        self._session: Optional[Session] = None
        self._started = False
        self._dist_initialized = False
        self._store_server = None
        self._store_client = None
        self._monitor = None
        self._interference = None

    # -- identity (reference peer.go + python/__init__.py:36-103) ---------------------

    @property
    def self_id(self) -> PeerID:
        return self.config.self_id

    @property
    def rank(self) -> int:
        return self.config.rank

    @property
    def size(self) -> int:
        return len(self.config.peers)

    @property
    def local_rank(self) -> int:
        r = self.config.peers.local_rank(self.self_id)
        return 0 if r is None else r

    @property
    def local_size(self) -> int:
        return max(1, self.config.peers.local_size(self.self_id))

    @property
    def host_count(self) -> int:
        return max(1, self.config.peers.host_count())

    def uid(self) -> int:
        """(version << 32) | rank, reference libkungfu-comm/main.go uid."""
        return (self.cluster_version << 32) | self.rank

    # -- lifecycle --------------------------------------------------------------------

    def start(self) -> "Peer":
        if self._started:
            return self
        kfenv.apply_platform_override()
        if self.size > 1 and not self.config.single_machine:
            self._init_distributed()
        self._session = self._build_session()
        if self.size > 1:
            # eager store start: a faster peer must find our server listening
            # before our first save/request (its wait=False pull is a miss,
            # never a connection error)
            self._ensure_store()
        from .monitor import maybe_start_monitor
        from .monitor.journal import set_journal_context

        self._monitor = maybe_start_monitor(self.self_id.port, host=self._bind_host())
        # journal stamps follow the CURRENT incarnation: ranks shift across
        # resizes/heals and every event must say who emitted it *then*
        set_journal_context(rank=self.rank, cluster_version=self.cluster_version)
        self._started = True
        log.info(
            "peer up: rank %d/%d local %d/%d hosts %d version %d",
            self.rank, self.size, self.local_rank, self.local_size,
            self.host_count, self.cluster_version,
        )
        return self

    def _bind_host(self) -> str:
        """Listen address for this peer's servers (store, monitor).

        Loopback-alias "hosts" on one machine (127.0.0.1 vs 127.0.0.2, the
        multi-host test shape) must each bind their OWN alias — 0.0.0.0
        would collide on the shared port space.  Real deployments may list
        hosts by an address the machine cannot bind (NAT, Docker published
        port, LB DNS name), so everything else binds 0.0.0.0.
        """
        if self.config.single_machine:
            return "127.0.0.1"
        host = self.self_id.host
        return host if host.startswith("127.") else "0.0.0.0"

    def _coordinator_address(self) -> str:
        root = self.config.peers[0]
        return f"{root.host}:{coordinator_port(root.port, self.cluster_version)}"

    def _init_distributed(self) -> None:
        """Join the jax.distributed coordination service (multi-process).

        One JAX process per worker; the coordinator is worker rank 0.  The
        port encodes the cluster version (fencing, see module docstring).
        The runtime is built by kungfu_tpu.distributed so survivors of an
        unplanned peer death can tear it down without the all-tasks barrier.
        """
        from .distributed import init_distributed_runtime

        addr = self._coordinator_address()
        with stall_detector(f"jax.distributed.initialize({addr})", force=True):
            init_distributed_runtime(
                coordinator_address=addr,
                num_processes=self.size,
                process_id=self.rank,
            )
        self._dist_initialized = True

    def _build_session(self) -> Session:
        # hierarchical (ici x dcn) mesh whenever there are multiple hosts AND
        # multiple devices per host — the device count is what matters (one
        # process per host owning several chips is the standard TPU shape)
        devices_per_host = max(1, len(backend_devices()) // self.host_count)
        if self.host_count > 1 and devices_per_host > 1:
            mesh = make_hierarchical_mesh(self.host_count)
        else:
            mesh = make_mesh(dp=-1)
        return Session(mesh=mesh, strategy=self.config.strategy, host_count=self.host_count)

    def current_session(self) -> Session:
        if not self._started:
            self.start()
        assert self._session is not None
        return self._session

    def interference_detector(self):
        """Lazily-built detector bound to the current session
        (GoKungfuCheckInterference analog, libkungfu-comm/monitoring.go)."""
        from .monitor import InterferenceDetector

        sess = self.current_session()
        if self._interference is None or self._interference.session is not sess:
            self._interference = InterferenceDetector(sess)
        return self._interference

    # -- p2p blob store (reference peer/p2p.go Save/Request + handler/p2p.go) ---------

    def _ensure_store(self):
        from .store import StoreClient, StoreServer, store_port

        if self._store_server is None:
            self._store_server = StoreServer(
                host=self._bind_host(), port=store_port(self.self_id.port)
            ).start()
            self._store_client = StoreClient()
        return self._store_server, self._store_client

    def save(self, name: str, arr, version: str = "") -> None:
        """Publish a named blob in this peer's store (GoKungfuSave analog)."""
        import numpy as np

        srv, _ = self._ensure_store()
        srv.save(name, np.asarray(arr), version=version)

    def request(self, target_rank: int, name: str, version: str = "",
                wait: bool = True, timeout: float = 30.0):
        """Pull a named blob from peer `target_rank`'s store (GoKungfuRequest)."""
        from .store import poll_until
        import time as _time

        srv, client = self._ensure_store()
        if target_rank == self.rank:
            # honor wait semantics on the self path too: correct code must
            # not break only when the target happens to be self
            return poll_until(
                lambda: srv.get(name, version=version),
                wait=wait, deadline=_time.monotonic() + timeout,
            )
        return client.request(
            self.config.peers[target_rank], name, version=version,
            wait=wait, timeout=timeout,
        )

    def get_peer_latencies(self, timeout: float = 5.0):
        """RTT to every peer's store endpoint, seconds; 0 for self
        (reference GetPeerLatencies, tensorflow/ops/cpu/topology.cpp:84 over
        rchannel pings).  Feed into plan.minimum_spanning_tree + set_tree."""
        if self.size <= 1:
            return [0.0] * self.size
        _, client = self._ensure_store()
        return [
            0.0 if r == self.rank else client.ping(p, timeout=timeout)
            for r, p in enumerate(self.config.peers)
        ]

    def close_monitor(self) -> None:
        """Fully stop this peer's monitor endpoint (thread joined) so a
        rebuilt/healed worker can re-bind the port without racing it."""
        if getattr(self, "_monitor", None) is not None:
            self._monitor.close()
            self._monitor = None

    def close(self) -> None:
        self.close_monitor()
        if self._store_server is not None:
            self._store_server.close()
            self._store_server = None
        if self._store_client is not None:
            self._store_client.close()
            self._store_client = None
        if self._dist_initialized:
            try:
                jax.distributed.shutdown()
            except Exception as e:  # pragma: no cover
                log.warning("distributed shutdown: %s", e)
            self._dist_initialized = False
        self._started = False
        self._session = None

    # -- elasticity hooks (full protocol in kungfu_tpu/elastic/) ----------------------

    def update_cluster(self, cluster: Cluster, version: int) -> bool:
        """Adopt a new cluster config; returns False if self was removed.

        The reference equivalent is Peer.updateTo (peer/peer.go:144-166):
        reset connections with the new token, rebuild the Session, barrier.
        Here: tear down jax.distributed, adopt the new peer list, re-init
        with the version-fenced coordinator, rebuild mesh+Session.
        """
        if cluster.workers.rank(self.self_id) is None:
            self.detached = True
            log.info("detached from cluster at version %d", version)
            return False
        self.close()
        self.config = kfenv.Config(
            self_id=self.self_id,
            peers=cluster.workers,
            runners=cluster.runners,
            cluster_version=version,
            strategy=self.config.strategy,
            config_server=self.config.config_server,
            parent=self.config.parent,
            single_machine=self.config.single_machine,
        )
        self.cluster_version = version
        self.start()
        return True


# -- module singleton (reference src/python/init.cpp:12-41 _default_peer) -------------

_default_peer: Optional[Peer] = None


def default_peer() -> Peer:
    global _default_peer
    if _default_peer is None:
        _default_peer = Peer().start()
        atexit.register(finalize_default_peer)
    return _default_peer


def set_default_peer(p: Optional[Peer]) -> None:
    global _default_peer
    _default_peer = p


def finalize_default_peer() -> None:
    global _default_peer
    if _default_peer is not None:
        _default_peer.close()
        _default_peer = None
