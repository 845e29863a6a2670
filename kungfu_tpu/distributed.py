"""jax.distributed runtime bootstrap with failure-tolerant teardown.

`jax.distributed.initialize` hard-codes the coordination-service defaults
that make *unplanned* failures lethal to survivors:

  - the client's missed-heartbeat callback terminates the process
    (LOG(QFATAL) in the XLA client), so a dead peer eventually kills every
    survivor that still holds a client;
  - `shutdown()` runs an all-tasks barrier with a multi-minute timeout, so
    a survivor tearing down after a peer death blocks until the heartbeat
    timeout and then aborts (measured: SIGABRT ~100s after the death).

This module builds the same runtime (service on rank 0 + client everywhere,
installed into `jax._src.distributed.global_state` so every JAX consumer —
gloo KV store, run_barrier, preemption sync — sees it) but with a benign
missed-heartbeat callback, bounded shutdown timeouts, and a **dirty
teardown** path that drops the runtime without the all-tasks barrier.  The
self-healing elastic path (elastic/trainer.py) uses dirty teardown when it
suspects a dead peer and then re-rendezvouses at the next cluster version's
fenced port; the planned-resize path keeps the graceful barrier.

Tuning (env):
  KFT_HEARTBEAT_INTERVAL_S    } their product is the heartbeat timeout
  KFT_MAX_MISSING_HEARTBEATS  } after which a task is dead (default 10 x 10)
  KFT_INIT_TIMEOUT_S          rendezvous timeout              (default 300)
  KFT_SHUTDOWN_TIMEOUT_S      graceful-shutdown barrier cap   (default 15)

Multi-process CPU testing needs nothing here: with a distributed client
installed, the CPU backend builds its gloo collectives by default, and
without one it builds none.
"""
from __future__ import annotations

import os
import time

import jax

from .utils import get_logger

log = get_logger("kungfu.distributed")


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _global_state():
    from jax._src import distributed

    return distributed.global_state


def init_distributed_runtime(coordinator_address: str, num_processes: int,
                             process_id: int) -> None:
    """Join (and on rank 0, host) the coordination service at `address`.

    Equivalent to jax.distributed.initialize(address, num_processes,
    process_id) but with survivable failure semantics (module docstring).
    """
    from jax._src.lib import _jax

    # the runtime takes one heartbeat timeout: the interval times the
    # number of misses that used to declare a task dead
    hb_timeout = int(_env_float("KFT_HEARTBEAT_INTERVAL_S", 10)
                     * _env_float("KFT_MAX_MISSING_HEARTBEATS", 10))
    init_to = int(_env_float("KFT_INIT_TIMEOUT_S", 300))
    shutdown_to = int(_env_float("KFT_SHUTDOWN_TIMEOUT_S", 15))

    state = _global_state()
    if state.client is not None:
        raise RuntimeError("distributed runtime already initialized")
    port = coordinator_address.rsplit(":", 1)[1]
    if process_id == 0:
        state.service = _jax.get_distributed_runtime_service(
            f"[::]:{port}", num_processes,
            heartbeat_timeout=hb_timeout, shutdown_timeout=shutdown_to,
        )

    def _missed_heartbeat(status) -> None:
        # never QFATL the process: a vanished coordinator means a dead rank
        # 0, and the self-healing path (or the stall deadline) must get the
        # chance to act on it
        log.warning("coordination service heartbeat missed: %s", status)

    state.client = _jax.get_distributed_runtime_client(
        coordinator_address, process_id,
        init_timeout=init_to, shutdown_timeout=shutdown_to,
        heartbeat_timeout=hb_timeout,
        missed_heartbeat_callback=_missed_heartbeat,
        shutdown_on_destruction=False, use_compression=True,
    )
    global _client_connected
    _client_connected = False
    state.client.connect()
    _client_connected = True
    state.coordinator_address = coordinator_address
    state.num_processes = num_processes
    state.process_id = process_id
    # orbax's should_save calls reached_preemption, which requires this
    # manager in multi-process runs.  Initializing it registers XLA's own
    # SIGTERM notifier, which silently replaces any Python-level SIGTERM
    # handler — the elastic loop re-installs its checkpoint-and-detach
    # handler after every re-init (elastic/trainer.py)
    state.initialize_preemption_sync_manager()


# coordination services/clients parked by dirty teardowns.  NEVER shut down
# or destroyed — not even at exit: a service shutdown is broadcast through
# the error-poll channel and jaxlib's handler terminates the polling
# process from a C++ thread (std::bad_cast), including THIS process's own
# parked clients (observed: a worker finishing cleanly, then dying rc=-6
# inside an atexit flush).  The references are held until the OS reclaims
# everything at process death; the footprint is one idle listener + a few
# threads per heal, bounded by heals-per-process-lifetime.
_parked_services: list = []
_parked_clients: list = []
# did the CURRENT client's connect() complete?  shutdown() on a
# never-connected client blocks unboundedly (see teardown below)
_client_connected = False


def teardown_distributed_runtime(graceful: bool = True) -> None:
    """Drop the distributed runtime.

    graceful=True runs the normal all-tasks shutdown barrier (planned
    resize: every peer reaches it together).  graceful=False is the
    suspected-dead-peer path: barrier attempts are bounded by the client's
    shutdown timeout and failures are swallowed — the runtime references are
    dropped regardless so a fresh `init_distributed_runtime` can follow.
    """
    state = _global_state()
    if graceful:
        jax.distributed.shutdown()  # no-op when already torn down
        return
    t0 = time.perf_counter()
    if state.client is not None:
        # PARK the client as well — neither shutdown() nor destruction is
        # safe here.  shutdown() on a never-connected client blocks far
        # past its timeout (observed: 120s, into the stall deadline), and a
        # shutdown whose all-tasks barrier cannot complete (that is the
        # definition of this path — a peer is dead) makes the service
        # broadcast a barrier error to every OTHER still-connected agent,
        # which jaxlib's error-poll handler answers by terminating those
        # processes (std::bad_cast) — one rank's recovery must never
        # execute its healthy peers.  Parked clients idle (their heartbeats
        # against a parked/dead service hit the benign callback) and are
        # dropped at process exit.
        _parked_clients.append(state.client)
    state.client = None
    if state.service is not None:
        # PARK the coordination service instead of shutting it down: a
        # service shutdown is pushed to every still-connected agent through
        # the error-poll channel, and jaxlib's poll handler terminates the
        # whole process from a C++ thread (coordination_service_agent.cc
        # "Polled an error ..." -> std::bad_cast -> std::terminate).  A
        # peer blocked in a collective two ring hops from the dead rank has
        # seen NO error yet — killing it turns one host loss into a fleet
        # loss.  Parked services idle on their version-fenced port (the
        # next incarnation binds a different one) and are shut down at
        # process exit, when nobody is left to terminate.
        _parked_services.append(state.service)
        state.service = None
    state.preemption_sync_manager = None
    state.coordinator_address = None
    # back to the single-process defaults: the CPU backend factory and
    # orbax's barrier policy consult these, and stale values make a
    # healed-to-smaller rebuild believe it is still the old world size
    state.process_id = 0
    state.num_processes = 1
    dt = time.perf_counter() - t0
    # the teardown phase of every recovery-ladder climb: journal it so a
    # slow heal can be attributed to a wedged shutdown, not the ladder
    from .monitor.journal import journal_event

    journal_event("dirty_teardown", duration_s=round(dt, 4))
    log.info("dirty distributed teardown in %.2fs", dt)
