"""Worker environment contract — how the launcher configures workers.

Mirrors the reference env-var tier (srcs/go/kungfu/env/envs.go:5-20, values
set by the launcher in srcs/go/kungfu/job/job.go:31-70, parsed by workers in
srcs/go/kungfu/env/config.go:24-56), renamed KFT_*:

  KFT_SELF_SPEC            "host:port" identity of this worker
  KFT_INIT_PEERS           comma-separated worker list (rank order)
  KFT_INIT_RUNNERS         comma-separated runner list
  KFT_INIT_CLUSTER_VERSION integer config version at spawn
  KFT_PARENT_ID            "host:port" of the spawning runner
  KFT_ALLREDUCE_STRATEGY   strategy name (plan/strategy.py)
  KFT_CONFIG_SERVER        URL of the elastic config service
  KFT_CONFIG_URLS          comma-separated replica URLs of a replicated
                           config ensemble (wins over KFT_CONFIG_SERVER;
                           single-URL form is identical to it)
  KFT_JOB_START            the launcher's real process start (wall clock):
                           both launchers stamp it always; the job clock
  KFT_PROC_START           wall-clock stamp of this worker's spawn
                           (run/launcher.py ProcRunner.start): where its
                           boot:interpreter phase starts

Tuning tier (KFT_CONFIG_*, reference srcs/go/kungfu/config/config.go:24-67):
  KFT_CONFIG_LOG_LEVEL, KFT_CONFIG_ENABLE_STALL_DETECTION,
  KFT_CONFIG_ENABLE_MONITORING, KFT_CONFIG_MONITORING_PERIOD_MS

Single-process fallback (no KFT_* set): one worker 127.0.0.1:10000, like the
reference's SingleMachineEnv (env/config.go:57-67).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional

from .plan import Cluster, PeerID, PeerList, Strategy, DEFAULT_STRATEGY

SELF_SPEC = "KFT_SELF_SPEC"
INIT_PEERS = "KFT_INIT_PEERS"
INIT_RUNNERS = "KFT_INIT_RUNNERS"
INIT_CLUSTER_VERSION = "KFT_INIT_CLUSTER_VERSION"
PARENT_ID = "KFT_PARENT_ID"
ALLREDUCE_STRATEGY = "KFT_ALLREDUCE_STRATEGY"
CONFIG_SERVER = "KFT_CONFIG_SERVER"
CONFIG_URLS = "KFT_CONFIG_URLS"
JOB_START = "KFT_JOB_START"
PROC_START = "KFT_PROC_START"

CONFIG_PREFIX = "KFT_CONFIG_"

ALL_WORKER_ENVS = [
    SELF_SPEC, INIT_PEERS, INIT_RUNNERS, INIT_CLUSTER_VERSION,
    PARENT_ID, ALLREDUCE_STRATEGY, CONFIG_SERVER, JOB_START, PROC_START,
]


@dataclasses.dataclass
class Config:
    self_id: PeerID
    peers: PeerList
    runners: PeerList
    cluster_version: int = 0
    strategy: Strategy = DEFAULT_STRATEGY
    config_server: str = ""
    parent: Optional[PeerID] = None
    single_machine: bool = False

    @property
    def rank(self) -> int:
        r = self.peers.rank(self.self_id)
        if r is None:
            raise RuntimeError(f"{self.self_id} not in peer list {self.peers}")
        return r

    def cluster(self) -> Cluster:
        return Cluster(runners=self.runners, workers=self.peers)


def apply_platform_override() -> None:
    """Apply the launcher's per-worker platform contract (``-platform cpu``
    sets KFT_PLATFORM).  Without it JAX_PLATFORMS alone decides, and JAX
    reads that itself.  Call before any backend use.
    """
    plat = os.environ.get("KFT_PLATFORM", "")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)


#: the one compile-cache location the program itself ever chooses: fixed
#: and inside the checkout, because the directory is part of JAX's cache key
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def starts_dir() -> str:
    """Where a process leaves its start record when KFT_TRACE_DUMP_DIR
    names no other place (monitor/boot.py): `starts/` beside the compiled
    programs.  Takes the constant, not JAX's setting, so that a launcher
    can ask without touching JAX."""
    return os.path.join(
        os.environ.get("JAX_COMPILATION_CACHE_DIR", "") or COMPILE_CACHE_DIR,
        "starts")


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and nothing is
    touched here; otherwise the cache goes to `<checkout>/.jax_cache`.
    Called from process entry points only, never at import, so importing
    the package (pytest) fills no cache.  A process that calls this is a
    program's entry point: it may write its start record.
    """
    from .utils.trace import arm_start_record

    arm_start_record(starts_dir())
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def _parse_peers(s: str) -> PeerList:
    return PeerList(PeerID.parse(x) for x in s.split(",") if x)


def parse_config_from_env(env: Optional[Dict[str, str]] = None) -> Config:
    e = dict(os.environ if env is None else env)
    if SELF_SPEC not in e:
        # single-process fallback (reference env/config.go:57-67)
        me = PeerID("127.0.0.1", 10000)
        return Config(
            self_id=me,
            peers=PeerList([me]),
            runners=PeerList(),
            single_machine=True,
            strategy=Strategy.parse(e.get(ALLREDUCE_STRATEGY, DEFAULT_STRATEGY.name)),
            config_server=e.get(CONFIG_URLS) or e.get(CONFIG_SERVER, ""),
        )
    return Config(
        self_id=PeerID.parse(e[SELF_SPEC]),
        peers=_parse_peers(e.get(INIT_PEERS, e[SELF_SPEC])),
        runners=_parse_peers(e.get(INIT_RUNNERS, "")),
        cluster_version=int(e.get(INIT_CLUSTER_VERSION, "0")),
        strategy=Strategy.parse(e.get(ALLREDUCE_STRATEGY, DEFAULT_STRATEGY.name)),
        config_server=e.get(CONFIG_URLS) or e.get(CONFIG_SERVER, ""),
        parent=PeerID.parse(e[PARENT_ID]) if e.get(PARENT_ID) else None,
    )


def worker_env(
    self_id: PeerID,
    cluster: Cluster,
    version: int,
    strategy: Strategy,
    parent: Optional[PeerID] = None,
    config_server: str = "",
) -> Dict[str, str]:
    """Env block the launcher injects into a worker (job/job.go:31-70)."""
    env = {
        SELF_SPEC: str(self_id),
        INIT_PEERS: ",".join(str(p) for p in cluster.workers),
        INIT_RUNNERS: ",".join(str(p) for p in cluster.runners),
        INIT_CLUSTER_VERSION: str(version),
        ALLREDUCE_STRATEGY: strategy.name,
    }
    if parent is not None:
        env[PARENT_ID] = str(parent)
    if config_server:
        # `config_server` may be the comma KFT_CONFIG_URLS form (replicated
        # ensemble); workers parse either var through the same splitter, so
        # the single-URL contract is unchanged and the list rides the
        # canonical var too
        env[CONFIG_SERVER] = config_server
        if "," in config_server:
            env[CONFIG_URLS] = config_server
    # forward the tuning tier (job/job.go:93-100); never clobber the
    # explicitly-set worker contract above (KFT_CONFIG_SERVER shares the prefix)
    for k, v in os.environ.items():
        if k.startswith(CONFIG_PREFIX) and k not in env and k not in ALL_WORKER_ENVS:
            env[k] = v
    return env
