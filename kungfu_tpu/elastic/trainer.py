"""Elastic training loop — resize the cluster mid-training.

TPU re-design of the reference's signature flow (SURVEY.md §3.5; reference
peer/peer.go:227-263, experimental/hook/elastic.py:51-118):

  reference                             this module
  ---------                             -----------
  worker GETs config server             same (HTTP, elastic/config_client.py)
  BytesConsensus over own TCP           version consensus over the CURRENT
  collectives until all agree           mesh (compiled pmin/pmax) until agree
  notify runners via Control conns      runners poll the config server
  token-fenced reconnect + barrier      jax.distributed re-init at a
                                        version-derived coordinator port (the
                                        rendezvous IS the barrier; stale peers
                                        cannot reach the new port = fencing)
  allreduce-max trained samples +       one compiled sync program: pmax of the
  BroadcastGlobalVariables              offset + broadcast params/opt_state
                                        from global rank 0

The hard constraint (SURVEY.md §7 "hard parts"): jax.distributed is static,
so a resize means snapshot-to-host -> backend teardown -> re-init -> re-place.
Survivors keep their state; joiners enter with fresh init and receive rank
0's state in the sync program.  Worker 0 survives any shrink (Cluster.resize
keeps a prefix — the reference's "new root must be old worker" guard,
peer.go:211-222, holds by construction).

Self-healing (docs/fault_tolerance.md): under a `-heal` launcher the loop
also survives *unplanned* failures.  A collective that dies because a peer
vanished (or a consensus that times out) escalates to the suspected-dead-
peer path: pick a state source off the **recovery ladder**
(kungfu_tpu/resilience — buddy RAM tier first: live buffers, then this
rank's rolling snapshot, then a fetch from the buddy peer; verified disk
steps only when RAM has nothing), tear the backend down WITHOUT the
all-tasks barrier, wait for the healer's shrunk cluster document, and
re-rendezvous at the new version's fenced port — training continues at the
smaller size.  The chosen rung/source lands on the heal event
(`recovery_rung`, `recovery_source`) and in the counters.  SIGTERM is
treated as a preemption notice: final checkpoint with a bounded flush wait
(KFT_PREEMPT_FLUSH_DEADLINE_S), self-removal from the cluster document,
DETACHED announce, clean exit.  Failures are injectable via KFT_FAULT_PLAN
(kungfu_tpu.chaos), including checkpoint-integrity faults (corrupt_ckpt,
crash_in_save).
"""
from __future__ import annotations

import os
import dataclasses
import signal
import sys
import time
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import numpy as np

from ..monitor.journal import journal_event
from ..utils import get_logger, stall_detector
from ..utils import trace as tracing
from .config_client import ConfigClient
from .schedule import StepBasedSchedule

log = get_logger("kungfu.elastic")

# exit code when the suspected-dead-peer path finds no healed document in
# time: distinct from crash codes so the healer's logs show *why* we died
HEAL_WAIT_EXIT_CODE = 86


@dataclasses.dataclass
class ElasticConfig:
    total_samples: int
    batch_size: int  # per replica (device)
    schedule: str = ""  # "size:steps,..." -> rank 0 proposes resizes
    check_every: int = 5  # steps between config polls (resize latency knob)
    per_replica: bool = False
    consensus_timeout_s: float = 60.0
    # durable checkpointing (SURVEY §5: the gap the reference leaves open).
    # With a dir set, rank 0 saves every checkpoint_every steps and training
    # resumes from the latest checkpoint on restart — state now survives
    # even the disjoint-membership resize the reference only warns about.
    checkpoint_dir: str = ""
    checkpoint_every: int = 50
    # how long the suspected-dead-peer path waits for the healer to publish
    # a shrunk cluster document before giving up (exit 86, healer's move)
    heal_timeout_s: float = 120.0
    # heal-armed jobs keep a rolling host copy of the train state every this
    # many steps: the step whose collective dies poisons its output buffers
    # (their definition event is the failed allreduce), so recovery restarts
    # from the last good snapshot — losing at most this many steps.
    # 0 = auto (check_every).
    snapshot_every: int = 0


class _MeshPrograms:
    """Compiled helper programs bound to the current mesh."""

    def __init__(self, trainer):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from jax import shard_map

        from ..ops import collective as C

        self.trainer = trainer
        # heal-armed jobs run every consensus/sync collective under a forced
        # stall watchdog: its ticks refresh the launcher-facing heartbeat
        # (blocked-on-a-hung-peer must read as alive, not as a second hang)
        # and the hard deadline bounds a wedge inside the op itself
        self._stall_force = bool(os.environ.get("KFT_HEAL"))
        mesh = trainer.mesh
        axes = trainer.axis_name if isinstance(trainer.axis_name, tuple) else (trainer.axis_name,)
        axis = axes if len(axes) > 1 else axes[0]
        stacked = P(axes)

        def minmax(x):
            y = jnp.squeeze(x, 0)
            return jnp.stack([lax.pmin(y, axis), lax.pmax(y, axis)])[None]

        self._minmax = jax.jit(
            shard_map(minmax, mesh=mesh, in_specs=stacked, out_specs=stacked)
        )

        def sync(offset, tree):
            off = lax.pmax(jnp.squeeze(offset, 0), axis)
            out = jax.tree.map(
                lambda p: C.broadcast(jnp.squeeze(p, 0), axis, root=0)[None], tree
            )
            return off[None], out

        self._sync = jax.jit(
            shard_map(sync, mesh=mesh, in_specs=(stacked, stacked), out_specs=(stacked, stacked))
        )

        def collapse(tree):  # stacked (identical rows) -> replicated
            def one(p):
                y = jnp.squeeze(p, 0)
                if jnp.issubdtype(y.dtype, jnp.inexact):
                    return lax.pmean(y, axis)
                # integer leaves (e.g. EMA step counters in monitor optimizer
                # state) must keep their dtype: pmean would promote to float
                # and the next resize's sync program would then disagree with
                # a fresh joiner's int leaves (Gloo size-mismatch crash).
                # Rows are identical here, so pmax is a pure selection.
                return lax.pmax(y, axis)

            return jax.tree.map(one, tree)

        self._collapse = jax.jit(
            shard_map(collapse, mesh=mesh, in_specs=stacked, out_specs=P())
        )

        self._mesh = mesh
        self._axes = axes
        self._stacked_sharding = NamedSharding(mesh, stacked)

    def _stack_local(self, value: np.ndarray):
        """Every process contributes its copy for each of its local devices."""
        import jax

        n_local = jax.local_device_count()
        tiled = np.broadcast_to(value[None], (n_local,) + value.shape)
        if jax.process_count() == 1:
            world = len(jax.devices())
            full = np.broadcast_to(value[None], (world,) + value.shape)
            return jax.device_put(full, self._stacked_sharding)
        return jax.make_array_from_process_local_data(self._stacked_sharding, tiled)

    def agree_vec(self, values: Tuple[int, ...], timeout_s: float = 60.0,
                  refresh: Optional[Callable[[], Tuple[int, ...]]] = None) -> Tuple[int, ...]:
        """Block until every participant reports the same int vector.

        The BytesConsensus retry loop (peer.go:245-254) over the current
        mesh: elementwise pmin/pmax until they agree.  `refresh` re-reads the
        local values between attempts.  Values must fit int32 (pass digests
        masked to 31 bits).
        """
        t0 = time.monotonic()
        v = tuple(values)
        with stall_detector("elastic_consensus", force=self._stall_force):
            while True:
                arr = self._stack_local(np.asarray(v, np.int32))
                out = np.asarray(self._minmax(arr).addressable_shards[0].data)
                lo, hi = out[0, 0], out[0, 1]
                if (lo == hi).all():
                    return tuple(int(x) for x in lo)
                if time.monotonic() - t0 > timeout_s:
                    raise TimeoutError(f"no consensus: min={lo} max={hi}")
                time.sleep(0.05)
                if refresh is not None:
                    v = tuple(refresh())

    def agree_int(self, value: int, timeout_s: float = 60.0,
                  refresh: Optional[Callable[[], int]] = None) -> int:
        r = None if refresh is None else (lambda: (refresh(),))
        return self.agree_vec((value,), timeout_s, r)[0]

    def sync_state(self, counters: Tuple[int, ...], host_tree: Any) -> Tuple[Tuple[int, ...], Any]:
        """pmax the progress counters + broadcast state from global rank 0.

        counters: monotonic ints (trained-sample offset, step count, ...).
        host_tree: pytree of numpy arrays (this process's state).  Returns
        (synced counters, device state in the trainer's param layout).
        """
        import jax

        off = self._stack_local(np.asarray(list(counters), np.int64))
        stacked = jax.tree.map(self._stack_local, host_tree)
        if os.environ.get("KFT_DEBUG_SYNC"):
            sig = [(str(l.dtype), tuple(l.shape)) for l in jax.tree.leaves(stacked)]
            log.info("sync_state sig: off=%s %s tree=%s", off.dtype, off.shape, sig)
        with stall_detector("elastic_state_sync", force=self._stall_force):
            off_out, tree_out = self._sync(off, stacked)
            # rows are identical post-pmax; read this process's local shard
            row = np.asarray(off_out.addressable_shards[0].data).reshape(-1)
        counters_new = tuple(int(x) for x in row)
        if self.trainer.per_replica:
            return counters_new, tree_out
        return counters_new, self._collapse(tree_out)


def _snapshot(tree) -> Any:
    import jax

    return jax.tree.map(lambda x: np.asarray(x), tree)


def _snapshot_local_replica(tree) -> Any:
    from ..train import first_local_replica

    return first_local_replica(tree)


def _teardown_backend(graceful: bool = True, peer=None) -> None:
    """Tear down jax.distributed + the XLA backend for a rebuild.

    graceful=False is the suspected-dead-peer path: the all-tasks shutdown
    barrier would block on (and then be killed by) the very peer whose death
    we are recovering from, so the runtime references are dropped with
    bounded, error-swallowing shutdowns instead (kungfu_tpu.distributed).

    `peer` (when given) gets its monitor endpoint fully closed FIRST —
    MonitorServer.close now joins the server thread, and a healed worker
    re-binding the same monitor port must not race a still-draining one.
    """
    import jax
    import jax._src.xla_bridge as xb

    from ..distributed import teardown_distributed_runtime

    if peer is not None:
        try:
            peer.close_monitor()
        except Exception as e:  # noqa: BLE001 - teardown must not throw
            log.warning("monitor close during teardown: %s", e)
    t0 = time.perf_counter()
    try:
        teardown_distributed_runtime(graceful=graceful)
    except Exception as e:  # pragma: no cover
        log.warning("distributed shutdown: %s", e)
    t1 = time.perf_counter()
    jax.clear_caches()
    xb._clear_backends()
    # _clear_backends misses the lru-cached topology queries: a stale
    # process_count makes the rebuilt (smaller) world look like the old one
    # — orbax then demands a distributed client that a healed-to-one
    # process no longer has, and _stack_local miscounts contributors
    for fn in (jax.process_count, jax.local_devices):
        if hasattr(fn, "cache_clear"):
            fn.cache_clear()
    t2 = time.perf_counter()
    from ..checkpoint import reset_orbax_runtime_caches

    reset_orbax_runtime_caches()
    if os.environ.get("KFT_DEBUG_TEARDOWN"):
        log.info("teardown: shutdown=%.3fs clear=%.3fs orbax=%.3fs",
                 t1 - t0, t2 - t1, time.perf_counter() - t2)


def _suspected_peer_failure(e: BaseException) -> bool:
    """Does this exception look like a peer/runtime death rather than a bug?

    Gloo surfaces dead peers as ValueError("... Gloo allreduce failed ...
    Connection closed by peer"), the coordination service as RuntimeError/
    XlaRuntimeError with UNAVAILABLE/heartbeat text, and a consensus that
    never converges (a peer died holding a stale document) as TimeoutError.
    """
    if isinstance(e, TimeoutError):
        return True
    if isinstance(e, OSError):
        return True
    text = f"{type(e).__name__}: {e}"
    markers = (
        "Gloo", "gloo", "Connection", "connection closed", "closed by peer",
        "UNAVAILABLE", "DEADLINE_EXCEEDED", "heartbeat", "Heartbeat",
        "coordination", "Coordination", "Socket", "socket", "distributed_runtime",
        "preempted",
    )
    return isinstance(e, (RuntimeError, ValueError)) and any(m in text for m in markers)


def _touch(path: str) -> None:
    try:
        os.utime(path, None)
    except FileNotFoundError:
        try:
            with open(path, "w"):
                pass
        except OSError:  # pragma: no cover - unwritable heartbeat dir
            pass
    except OSError:  # pragma: no cover
        pass


def run_elastic(
    make_loss: Callable[[], Callable],
    init_params: Callable[[], Any],
    make_tx: Callable[[], Any],
    make_data: Callable[[int, int, int], Iterator],
    cfg: ElasticConfig,
) -> Dict[str, Any]:
    """Elastic data-parallel training under the launcher (watch mode).

    Args:
      make_loss: () -> loss_fn(params, batch) (rebuilt after each remesh).
      init_params: () -> params pytree; deterministic across processes.
      make_tx: () -> optax transform using axis name "dp".  Declare a
        parameter named `axes` (or `axis_name`) to receive the mesh's data
        axes — required for the hierarchical dcn x ici mesh on multi-host
        clusters — and optionally `impl` for the strategy-selected
        reduction schedule.
      make_data: (rank, size, offset_samples) -> iterator of LOCAL batches.
      cfg: ElasticConfig.

    Returns final metrics dict (on workers that survive to the end).
    """
    import kungfu_tpu
    from ..chaos import injector_from_env
    from ..chaos.inject import set_launch_rank
    from ..monitor.counters import global_counters
    from ..resilience import BuddySnapshots, buddy_enabled
    from ..resilience import ladder as _ladder
    from ..train import DataParallelTrainer, TrainState

    peer = kungfu_tpu.init()
    client = ConfigClient(peer.config.config_server) if peer.config.config_server else None
    schedule = StepBasedSchedule(cfg.schedule)
    resizes = 0
    # per-resize latency accounting (reference resize profiler,
    # experimental/hook/elastic.py:12-48 — it wraps the reconfig op the
    # same way).  Phases: snapshot -> ckpt_release -> teardown -> reinit
    # (jax.distributed rendezvous at the new version port) -> rebuild
    # (mesh + program construction) -> sync (compile + run of the state
    # broadcast) -> first_step (train-step recompile on the new mesh).
    resize_events: list = []
    _first_step_after_resize = False
    # end-to-end propose->new-mesh latency (verdict r4 weak #7): the phase
    # sums above start at the resize CHECK; the honest watch-mode number
    # also includes the config-server poll + consensus delay between rank
    # 0's propose and the resize starting.  Rank 0 stamps each propose;
    # the matching resize event carries propose_to_done_s.
    _last_propose: Dict[str, Any] = {}

    # -- self-healing state ----------------------------------------------------------
    # armed by the -heal launcher (job.py sets KFT_HEAL in the worker env):
    # without a healer publishing shrunk documents, waiting for one would
    # only delay the crash the supervisor needs to see.
    heal_armed = bool(os.environ.get("KFT_HEAL")) and client is not None
    heal_events: list = []
    _pending_heal: Optional[Dict[str, Any]] = None
    chaos = injector_from_env()
    # faults key on the LAUNCH rank: current ranks shift when the cluster
    # heals/resizes, and a drill's scripted victim must stay the same
    # process for the replay to be deterministic.  The save-path fault
    # (crash_in_save) fires inside the checkpoint manager, which has no
    # rank notion — register it once here.
    chaos_rank = peer.rank
    set_launch_rank(chaos_rank)
    hb_file = os.environ.get("KFT_HEARTBEAT_FILE", "")
    # SIGTERM = preemption notice (TPU maintenance, spot reclaim, planned
    # kill): finish the current step, then checkpoint + detach cleanly.
    # One-shot flag keeps the handler async-signal-trivial.
    _preempted = {"flag": False}

    def _on_sigterm(signum, frame):  # noqa: ARG001
        _preempted["flag"] = True
        log.warning("SIGTERM received: will checkpoint and detach at the step boundary")

    def _install_sigterm():
        """(Re-)take the SIGTERM handler.  Must run after EVERY distributed
        re-init: XLA's preemption notifier registers its own handler there,
        silently replacing this one."""
        try:
            return signal.signal(signal.SIGTERM, _on_sigterm)
        except ValueError:  # pragma: no cover - not the main thread (tests)
            return None

    _prev_sigterm = _install_sigterm()

    import inspect

    # opt-in by parameter NAME, not arity: a zero-arg-contract factory
    # written as `def make_tx(lr=0.1)` must never receive an axis tuple
    try:
        _tx_names = set(inspect.signature(make_tx).parameters)
    except (TypeError, ValueError):  # builtins / C callables
        _tx_names = set()
    _axes_kw = next((k for k in ("axes", "axis_name") if k in _tx_names), None)

    def call_make_tx(axes, impl):
        kw = {}
        if _axes_kw is not None:
            kw[_axes_kw] = axes
        if "impl" in _tx_names:
            kw["impl"] = impl
        return make_tx(**kw)

    def build():
        """Mesh + trainer for the CURRENT cluster shape.

        Mirrors Peer._build_session (peer.py): multi-host clusters with
        several devices per host get the hierarchical dcn x ici mesh so
        gradient collectives ride ICI within a host and only the cross-host
        phase touches DCN (reference cross-strategies, session/strategy.go:
        188-210).  The configured Strategy picks the in-step reduction
        schedule.  A make_tx that takes no axis argument can only reduce
        over "dp", so it pins the flat mesh (compatibility path).
        """
        import jax

        from ..plan import Impl, impl_of, make_mesh, make_hierarchical_mesh

        host_count = peer.host_count
        devices_per_host = max(1, len(jax.devices()) // host_count)
        if host_count > 1 and devices_per_host > 1 and _axes_kw is not None:
            mesh = make_hierarchical_mesh(host_count)
            axes: Any = ("dcn", "ici")
        else:
            mesh = make_mesh(dp=-1)
            axes = "dp"
        impl = {
            Impl.HIERARCHICAL: "hierarchical",
            Impl.RS_AG: "rs_ag",
            Impl.RING: "ring",
        }.get(impl_of(peer.config.strategy, host_count), "pmean")
        if impl == "hierarchical" and axes == "dp":
            impl = "pmean"  # no dcn/ici split on a flat mesh
        if impl == "ring" and isinstance(axes, tuple):
            impl = "rs_ag"
        trainer = DataParallelTrainer(
            make_loss(), call_make_tx(axes, impl), mesh=mesh, axis_name=axes,
            per_replica_params=cfg.per_replica,
        )
        return trainer, _MeshPrograms(trainer)

    trainer, programs = build()
    state = trainer.init(init_params())
    offset = 0

    def snap(state):
        if cfg.per_replica:
            return (
                _snapshot_local_replica(state.params),
                _snapshot_local_replica(state.opt_state),
            )
        return _snapshot(state.params), _snapshot(state.opt_state)

    step = 0  # monotonic optimizer-step count (survives resizes via sync)

    ckpt = None
    if cfg.checkpoint_dir:
        from ..checkpoint import CheckpointManager

        # save_interval_steps=1: the loop's modulo gate is the only cadence
        # (orbax's own interval gate would silently skip the first
        # post-resume save when the final forced step isn't a multiple)
        ckpt = CheckpointManager(
            cfg.checkpoint_dir,
            save_interval_steps=1,
            is_primary=peer.rank == 0,
        )
        if ckpt.latest_step() is not None:
            # durable resume: load on every process, then the initial sync
            # below re-establishes bit-identical state across the cluster.
            # The walk is the disk half of the recovery ladder — torn /
            # corrupt / manifest-less steps are demoted with a journaled
            # reason and the next older verified step is tried; a directory
            # with NO verified step starts fresh instead of trusting
            # unverified bytes.
            sp0, so0 = snap(state)
            got = ckpt.restore_latest_verified(like={"params": sp0, "opt": so0})
            if got is None:
                log.warning(
                    "checkpoint dir %s has steps but none verify; starting "
                    "from scratch (see checkpoint_demoted journal events)",
                    cfg.checkpoint_dir,
                )
                journal_event("checkpoint_resume_skipped",
                              directory=cfg.checkpoint_dir)
            else:
                restored, meta, ckpt_step, _ = got
                offset = int(meta.get("trained_samples", 0))
                step = int(meta.get("step", 0))
                state = trainer.place_state(restored["params"], restored["opt"], step)
                journal_event("resume", step=step, trained_samples=offset,
                              ckpt_step=ckpt_step)
                log.info("resumed from checkpoint: step %d, %d samples "
                         "(verified ckpt step %d)", step, offset, ckpt_step)

    # initial sync: identical at version 0, but a worker joining an already-
    # running cluster (spawned at version N) gets real state here
    sp, so = snap(state)
    (offset, step), synced = programs.sync_state((offset, step), {"params": sp, "opt": so})
    state.params, state.opt_state = synced["params"], synced["opt"]
    data = make_data(peer.rank, peer.size, offset)
    # the sync IS this step's rendezvous: nobody re-checks at this step, so
    # every participant's next collective is the train step (joiners and
    # survivors must issue identical collective sequences on the new mesh)
    skip_check_at = step

    t_start = time.monotonic()
    metrics: Dict[str, Any] = {"loss": np.float32(np.nan)}

    # the buddy tier: the step whose collective died poisons its output
    # buffers AND donated its inputs, so a live snapshot at failure time can
    # be impossible — heal-armed jobs refresh a rolling host copy every
    # snapshot_every steps AND ship it to a ring-offset buddy rank (another
    # host when one exists), making the state survive any single host loss
    # entirely in RAM.  Rebuilt on every membership change (ranks shift).
    _snapshot_every = cfg.snapshot_every or max(1, cfg.check_every)
    buddy: Optional[BuddySnapshots] = None

    def _rebuild_buddy(seed: bool) -> None:
        """(Re-)derive the buddy assignment for the CURRENT peer list; with
        `seed`, immediately stash+ship a snapshot so the recovery ladder
        never finds the tier empty."""
        nonlocal buddy
        if buddy is not None:
            buddy.close()
            buddy = None
        if not heal_armed:
            return
        buddy = BuddySnapshots(peer)
        if seed and buddy_enabled():
            sp_g, so_g = snap(state)
            buddy.update(step, offset, sp_g, so_g)

    _rebuild_buddy(seed=True)

    def save_ckpt(force: bool = False) -> None:
        if ckpt is None or not ckpt.writes:
            return
        sp_c, so_c = snap(state)
        ckpt.save(step, {"params": sp_c, "opt": so_c},
                  meta={"trained_samples": offset, "step": step,
                        "cluster_size": peer.size,
                        "cluster_version": peer.cluster_version}, force=force)

    def _detach_preempted() -> None:
        """SIGTERM path: durable checkpoint, self-removal from the cluster
        document (so survivors/healer see a *planned* detach, not a death),
        DETACHED announce, clean exit."""
        log.warning("preemption: final checkpoint + detach at step %d", step)
        # flush the span ring FIRST: even if the checkpoint wait eats the
        # whole grace window and we are SIGKILLed, the post-mortem timeline
        # keeps this rank's lane (the atexit dump would never run)
        tracing.flush_dump("preempt")
        flush_completed = None
        if ckpt is not None:
            # the flush wait is DEADLINE-BOUNDED: a hung async writer must
            # not eat the whole preemption grace window — better to detach
            # with a journaled durable-state gap than to be SIGKILLed
            # mid-everything when the grace period expires
            deadline = float(
                os.environ.get("KFT_PREEMPT_FLUSH_DEADLINE_S", "") or 30.0
            )
            try:
                save_ckpt(force=True)
                flush_completed = ckpt.wait(deadline_s=deadline)
                if flush_completed:
                    ckpt.close()
                else:
                    # close() would re-enter the unbounded wait; leave the
                    # daemon writer behind and let exit reap it
                    log.warning(
                        "preemption: checkpoint flush missed the %.0fs "
                        "deadline; detaching with a durable-state gap",
                        deadline,
                    )
            except Exception as e:  # noqa: BLE001 - exit path must not throw
                flush_completed = False
                log.warning("preemption checkpoint failed: %s", e)
        if client is not None:
            from ..plan import Cluster as _Cluster, PeerList as _PeerList

            try:
                got = client.get_cluster()
                if got is not None and got[0].workers.rank(peer.self_id) is not None:
                    cl, v = got
                    rest = _PeerList(p for p in cl.workers if p != peer.self_id)
                    client.put_cluster(
                        _Cluster(runners=cl.runners, workers=rest), version=v
                    )
            except OSError as e:
                log.warning("preemption self-removal failed: %s", e)
        global_counters().inc_event("preemptions")
        journal_event("preemption", step=step, trained_samples=offset,
                      flush_completed=flush_completed)
        print(f"DETACHED: preempted at step {step} ({offset} samples trained)",
              flush=True)
        sys.exit(0)

    def _put_suspect(reason: str, step: int) -> None:
        """Best-effort `suspect/<self>` KV report on entering recovery."""
        if client is None:
            return
        kv_put = getattr(client, "kv_put", None)
        if kv_put is None:
            return
        try:
            kv_put(f"suspect/{peer.self_id}",
                   {"reason": reason, "step": int(step),
                    "cluster_version": peer.cluster_version})
        except Exception as e:  # noqa: BLE001 - control-plane brownout
            log.debug("suspect report failed: %s", e)

    def _clear_suspect() -> None:
        if client is None:
            return
        kv_delete = getattr(client, "kv_delete", None)
        if kv_delete is None:
            return
        try:
            kv_delete(f"suspect/{peer.self_id}")
        except Exception as e:  # noqa: BLE001
            log.debug("suspect clear failed: %s", e)

    # progress beacon for the pod harness: step-keyed NETWORK faults
    # (partition/kill_host/degrade_link) are applied from the root namespace,
    # which cannot see any worker's step counter — rank 0 publishes it to
    # the config server's KV plane every check_every steps when armed.
    _beacon_armed = bool(os.environ.get("KFT_PROGRESS_BEACON")) and client is not None

    def _beacon(step: int) -> None:
        if not _beacon_armed or peer.rank != 0 or step % cfg.check_every:
            return
        kv_put = getattr(client, "kv_put", None)
        if kv_put is None:
            return
        try:
            kv_put("progress", {"step": int(step), "size": peer.size,
                                "cluster_version": peer.cluster_version})
        except Exception as e:  # noqa: BLE001
            log.debug("progress beacon failed: %s", e)

    def _recover(cause: BaseException) -> None:
        """Suspected-dead-peer path: checkpoint -> dirty teardown -> wait for
        the healer's shrunk document -> re-rendezvous -> re-sync state."""
        nonlocal trainer, programs, state, data, offset, step, skip_check_at
        nonlocal _pending_heal, metrics
        import gc

        t_detect = time.perf_counter()
        m_detect = time.monotonic()  # span/phase stamps stay NTP-immune
        old_size = peer.size
        log.warning("suspected peer failure (%s: %s); entering recovery",
                    type(cause).__name__, str(cause)[:200])
        journal_event("peer_failure_suspected", reason=type(cause).__name__,
                      detail=str(cause)[:200], step=step, old_size=old_size)
        # file a suspicion with the control plane: the launchers' remote-host
        # judgment (RemoteHostJudge) reads `suspect/` entries to distinguish
        # a partition (every runner heartbeat fresh -> partition_suspected,
        # reconvene nudges, NO shrink) from a host death.  Best-effort: the
        # judgment also works from runner heartbeats alone.
        _put_suspect(reason=type(cause).__name__, step=step)
        phases: Dict[str, float] = {}
        # climb the recovery ladder: buddy RAM tier (live buffers -> own
        # rolling snapshot -> fetch-back from the buddy peer) before any
        # disk read; verified disk steps (newest first, torn/corrupt ones
        # demoted) only when RAM has nothing.  Every demotion is journaled.
        outcome = _ladder.climb(
            live_fn=lambda: snap(state), buddy=buddy, ckpt=ckpt,
            step=step, offset=offset,
        )
        if outcome is None:
            # the job has genuinely lost its state (in-memory tier disabled
            # or empty AND no verified checkpoint): surface the original
            # failure rather than silently restoring unverified bytes
            journal_event("recovery_exhausted", step=step,
                          reason=type(cause).__name__)
            log.critical("recovery ladder exhausted; re-raising the failure")
            raise cause
        snap_params, snap_opt = outcome.params, outcome.opt
        if outcome.source != "live":
            log.warning(
                "recovering from %s/%s: rolling back to step %d (%d samples)",
                outcome.rung, outcome.source, outcome.step, outcome.offset,
            )
        step, offset = outcome.step, outcome.offset
        phases["state_source_s"] = outcome.elapsed_s
        if ckpt is not None:
            try:
                # best-effort durable point for the chosen snapshot:
                # primary-only, single-member barriers — safe to run with
                # dead peers in the cluster.  A disk-sourced state is
                # already durable; re-saving it would be a wasted flush.
                if ckpt.writes and not outcome.already_durable:
                    ckpt.save(step, {"params": snap_params, "opt": snap_opt},
                              meta={"trained_samples": offset, "step": step,
                                    "cluster_size": peer.size,
                                    "cluster_version": peer.cluster_version},
                              force=True)
                ckpt.release()
            except Exception as e:  # noqa: BLE001
                log.warning("recovery checkpoint failed: %s", e)
        # drop every reference into the wounded backend BEFORE teardown:
        # live arrays keep the old XLA client (and its gloo sockets) alive
        # past _clear_backends, and a still-open socket means the peers
        # blocked opposite us never see a connection reset — they hang in
        # their collective instead of entering their own recovery
        state = data = trainer = programs = None
        metrics = {"loss": np.float32(np.nan)}
        gc.collect()
        m_td0 = time.monotonic()
        tracing.record_span("heal:detect", m_detect, m_td0, cat="heal",
                            args={"reason": type(cause).__name__})
        phases["detect_s"] = round(m_td0 - m_detect, 4)
        # the teardown's bounded shutdown waits run for seconds with no
        # step-loop heartbeat touch — under the watchdog the ticker keeps
        # the launcher-facing liveness fresh (a worker mid-heal must read
        # as slow-but-alive, never as frozen)
        with stall_detector("heal_teardown", force=True):
            _teardown_backend(graceful=False, peer=peer)
        m_rdv0 = time.monotonic()
        tracing.record_span("heal:teardown", m_td0, m_rdv0, cat="heal")
        phases["teardown_s"] = round(m_rdv0 - m_td0, 4)
        while True:
            deadline = time.monotonic() + cfg.heal_timeout_s
            got = None
            while time.monotonic() < deadline:
                if _preempted["flag"]:
                    _detach_preempted()
                if hb_file:
                    _touch(hb_file)  # waiting on the healer is liveness too
                g = client.poll_cluster()
                if g is not None and g[1] > peer.cluster_version:
                    got = g
                    break
                time.sleep(0.25)
            if got is None:
                log.critical("no healed cluster document within %.0fs; exiting so "
                             "the supervisor can act", cfg.heal_timeout_s)
                sys.exit(HEAL_WAIT_EXIT_CODE)
            cluster, version = got
            try:
                try:
                    with stall_detector("heal_re_rendezvous", force=True):
                        joined = peer.update_cluster(cluster, version)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:  # noqa: BLE001 - re-init is retryable
                    # the re-rendezvous includes peers that may be dead or
                    # unreachable (a partition mid-heal surfaces as opaque
                    # C++ client errors, e.g. std::bad_cast from a connect
                    # that cannot reach the coordinator) — ANY init failure
                    # here means "this document didn't convene"; tear down
                    # and wait for a newer one (reconvene bumps keep coming
                    # while the partition lasts)
                    raise TimeoutError(
                        f"re-rendezvous at v{version} failed: "
                        f"{type(e).__name__}: {str(e)[:200]}") from e
                if not joined:
                    # the healer decided WE were the dead one (e.g. a hang
                    # that un-wedged after the heartbeat timeout): bow out
                    print(f"DETACHED: rank left cluster at version {version}",
                          flush=True)
                    sys.exit(0)
                _install_sigterm()
                trainer, programs = build()
                if ckpt is not None:
                    ckpt.set_primary(peer.rank == 0)
                m_sync0 = time.monotonic()
                # the re-rendezvous phase spans teardown end -> new-mesh
                # rebuild, INCLUDING failed attempts chasing newer documents
                tracing.record_span("heal:re_rendezvous", m_rdv0, m_sync0,
                                    cat="heal", args={"version": version})
                phases["re_rendezvous_s"] = round(m_sync0 - m_rdv0, 4)
                (offset, step), synced = programs.sync_state(
                    (offset, step), {"params": snap_params, "opt": snap_opt}
                )
                m_sync1 = time.monotonic()
                tracing.record_span("heal:resync", m_sync0, m_sync1, cat="heal")
                phases["resync_s"] = round(m_sync1 - m_sync0, 4)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:  # noqa: BLE001 - vetted below
                if not _suspected_peer_failure(e):
                    raise
                # another peer died between the healer's PUT and our
                # rendezvous/sync (update_cluster already advanced
                # peer.cluster_version, so the wait above only accepts a
                # strictly newer document)
                log.warning(
                    "recovery attempt at v%d failed (%s: %s); waiting for a "
                    "newer cluster document", version, type(e).__name__,
                    str(e)[:200],
                )
                # re-file the suspicion at the version that just failed:
                # suspects older than the current document carry no
                # partition evidence (a membership change answered them),
                # so a live partition must keep its evidence fresh for the
                # leader's reconvene nudges to continue
                _put_suspect(reason=type(e).__name__, step=step)
                trainer = programs = None
                gc.collect()
                m_rt0 = time.monotonic()
                with stall_detector("heal_teardown", force=True):
                    _teardown_backend(graceful=False, peer=peer)
                tracing.record_span("heal:teardown", m_rt0, cat="heal",
                                    args={"retry": True})
                continue
            break
        from ..monitor.counters import counters_if_enabled

        c = counters_if_enabled()
        if c is not None:
            # latency/rate distributions measured against the dead world
            # would pollute the healed one's throughput + interference vote
            c.reset_for_reinit()
        if anomaly is not None:
            # the healed (smaller) world's step time is legitimately
            # different — judging it against the old baseline would alarm
            anomaly.reset()
        tracing.record_span("heal", m_detect, cat="heal", args={
            "version": version, "old_size": old_size, "new_size": peer.size,
            "reason": type(cause).__name__,
        })
        state = TrainState(synced["params"], synced["opt"], step)
        data = make_data(peer.rank, peer.size, offset)
        skip_check_at = step
        # the healed membership has new ranks: re-derive the buddy ring and
        # seed it so a back-to-back second failure still finds the RAM tier
        _rebuild_buddy(seed=True)
        _clear_suspect()  # recovered: withdraw the partition-evidence report
        _pending_heal = {
            "version": version, "old_size": old_size, "new_size": peer.size,
            "reason": type(cause).__name__, "t_detect": t_detect,
            "recovery_rung": outcome.rung, "recovery_source": outcome.source,
            "recovery_demotions": len(outcome.demotions),
            "phases": dict(phases),
        }
        log.info("recovered onto %d-worker cluster at v%d from %s/%s; "
                 "resuming at step %d", peer.size, version, outcome.rung,
                 outcome.source, step)

    def step_once() -> None:
        nonlocal trainer, programs, state, data, offset, step, skip_check_at
        nonlocal resizes, metrics, _first_step_after_resize, _last_propose, _pending_heal

        if _preempted["flag"]:
            _detach_preempted()
        if hb_file:
            _touch(hb_file)  # liveness signal for the healer's hang detection
        _beacon(step)
        if chaos is not None:
            # ckpt_dir arms the checkpoint-integrity faults (corrupt_ckpt)
            chaos.on_step(step, chaos_rank, ckpt_dir=cfg.checkpoint_dir)

        # -- schedule-driven proposal (rank 0, reference hooks/elastic.py:14-88)
        if client is not None and schedule and peer.rank == 0:
            want = schedule.size_at(step)
            if want is not None and want != peer.size:
                from .config_client import propose_new_size

                if propose_new_size(peer, want):
                    _last_propose = {"t": time.perf_counter(), "size": want}

        # -- resize check (every check_every steps)
        if client is not None and step % cfg.check_every == 0 and step != skip_check_at:
            last_got: Dict[str, Any] = {}

            def observe() -> Tuple[int, int]:
                """(version, 31-bit doc digest) — consensus is on BOTH, the
                reference's consensus-on-cluster-bytes semantics: all workers
                are guaranteed to hold the *same document*, not just the same
                version number, before anyone acts."""
                got = client.poll_cluster()  # outage -> None: keep training
                if got is None:
                    return peer.cluster_version, 0
                last_got["cluster"], last_got["version"] = got
                digest = int(got[0].digest()[:7], 16) & 0x7FFFFFFF
                return got[1], digest

            version, _ = programs.agree_vec(
                observe(), timeout_s=cfg.consensus_timeout_s, refresh=observe
            )
            if version > peer.cluster_version:
                if last_got.get("version") == version:
                    cluster = last_got["cluster"]
                    log.info("resizing to version %d: %d workers", version, cluster.size())
                    if cluster.workers.rank(peer.self_id) is None:
                        # announce detachment BEFORE the slow teardown: the
                        # watcher reconciles off the config server and may
                        # SIGTERM this (now-removed) worker at any moment
                        print(f"DETACHED: rank left cluster at version {version}",
                              flush=True)
                    ev = {"version": version, "old_size": peer.size,
                          "new_size": cluster.size(), "phases": {}}
                    if _last_propose.get("size") == cluster.size():
                        ev["propose_to_start_s"] = round(
                            time.perf_counter() - _last_propose["t"], 4
                        )
                    # cleared on EVERY applied resize: a non-matching one
                    # means the proposed doc was overwritten (operator
                    # PUT), and a stale stamp would mis-attribute a later
                    # coincidental same-size resize
                    _last_propose = {}

                    def _phase(name, _t=[time.perf_counter()]):
                        now = time.perf_counter()
                        ev["phases"][name] = round(now - _t[0], 4)
                        _t[0] = now

                    m_resize0 = time.monotonic()
                    snap_params, snap_opt = snap(state)
                    _phase("snapshot")
                    if ckpt is not None:
                        # flush queued async saves and drop the orbax manager
                        # BEFORE the runtime it is bound to is torn down (a
                        # detaching primary must not abandon queued saves)
                        ckpt.release()
                        _phase("ckpt_release")
                    _teardown_backend(peer=peer)
                    _phase("teardown")
                    if not peer.update_cluster(cluster, version):
                        sys.exit(0)
                    _install_sigterm()
                    _phase("reinit")
                    trainer, programs = build()
                    _phase("rebuild")
                    if ckpt is not None:
                        # primariness follows the POST-resize rank: the new
                        # rank 0 re-acquires a manager bound to the NEW runtime
                        ckpt.set_primary(peer.rank == 0)
                    (offset, step), synced = programs.sync_state(
                        (offset, step), {"params": snap_params, "opt": snap_opt}
                    )
                    _phase("sync")
                    state = TrainState(synced["params"], synced["opt"], step)
                    data = make_data(peer.rank, peer.size, offset)
                    skip_check_at = step
                    # membership changed: the buddy ring is stale (ranks
                    # shifted, peers joined/left) — re-derive and re-seed
                    _rebuild_buddy(seed=True)
                    resizes += 1
                    resize_events.append(ev)
                    if step_counters is not None:
                        step_counters.set_gauge("cluster_size",
                                                float(peer.size))
                    if anomaly is not None:
                        anomaly.reset()  # new world, new step-time baseline
                    tracing.record_span("resize", m_resize0, cat="elastic",
                                        args={"version": version,
                                              "old_size": ev["old_size"],
                                              "new_size": ev["new_size"]})
                    _first_step_after_resize = True
                else:  # unreachable given digest consensus; log if it ever is
                    log.warning("agreed version %d but no matching doc cached", version)

        with tracing.trace_scope("step:data", cat="train", args={"step": step}):
            batch = trainer.shard_batch(next(data))
        if _first_step_after_resize or _pending_heal is not None:
            import jax

            t_fs = time.perf_counter()
            with stall_detector("elastic_train_step", force=heal_armed):
                with tracing.trace_scope("step:train", cat="train",
                                         args={"step": step, "recompile": True}):
                    state, metrics = trainer.train_step(state, batch)
                    jax.block_until_ready(metrics)  # force the recompile into the timing
            if _first_step_after_resize:
                ev = resize_events[-1]
                ev["phases"]["first_step"] = round(time.perf_counter() - t_fs, 4)
                ev["total_s"] = round(sum(ev["phases"].values()), 4)
                if "propose_to_start_s" in ev:
                    # the full watch-mode story: schedule propose -> config
                    # server -> poll -> consensus -> resize -> first new step
                    ev["propose_to_done_s"] = round(
                        ev["propose_to_start_s"] + ev["total_s"], 4
                    )
                journal_event("resize", version=ev["version"],
                              old_size=ev["old_size"], new_size=ev["new_size"],
                              phases=ev["phases"], total_s=ev["total_s"])
                _first_step_after_resize = False
            if _pending_heal is not None:
                # MTTR: failure detection -> first completed post-heal step
                hev = dict(_pending_heal)
                hev["mttr_s"] = round(time.perf_counter() - hev.pop("t_detect"), 4)
                hev.setdefault("phases", {})["first_step_s"] = round(
                    time.perf_counter() - t_fs, 4
                )
                heal_events.append(hev)
                global_counters().inc_event("heals")
                global_counters().set_gauge("heal_mttr_s", hev["mttr_s"])
                global_counters().set_gauge("cluster_size", float(peer.size))
                rung = hev.get("recovery_rung")
                if rung:
                    # per-rung MTTR: the ladder's value proposition is the
                    # buddy-vs-disk gap, so keep both visible in /metrics
                    global_counters().inc_event(f"heals_rung_{rung}")
                    global_counters().set_gauge(f"heal_mttr_{rung}_s",
                                                hev["mttr_s"])
                journal_event("heal", **hev)
                log.info("healed %d -> %d workers from %s/%s: mttr %.2fs",
                         hev["old_size"], hev["new_size"], rung,
                         hev.get("recovery_source"), hev["mttr_s"])
                _pending_heal = None
        else:
            with stall_detector("elastic_train_step", force=heal_armed):
                with tracing.trace_scope("step:train", cat="train",
                                         args={"step": step}):
                    state, metrics = trainer.train_step(state, batch)
        offset += cfg.batch_size * trainer.world
        step += 1

        if buddy is not None and buddy_enabled() and step % _snapshot_every == 0:
            sp_b, so_b = snap(state)
            buddy.update(step, offset, sp_b, so_b)
        if ckpt is not None and ckpt.writes:
            if step % max(1, cfg.checkpoint_every) == 0:
                with tracing.trace_scope("step:checkpoint", cat="train",
                                         args={"step": step}):
                    save_ckpt()
            else:
                # commit integrity manifests for async saves orbax finalized
                # since the last drain — no-op when nothing is pending
                ckpt.finalize_manifests()

    from ..monitor.counters import counters_if_enabled

    step_counters = counters_if_enabled()
    # anomaly watchdog (monitor.straggler): online step-time regression
    # detection against a rolling baseline — journaled anomaly_regression /
    # anomaly_cleared + anomaly_step_ratio/anomaly_active gauges.  Reset on
    # every resize/heal (the new world's step time is a new baseline).
    anomaly = None
    if step_counters is not None:
        from ..monitor.straggler import AnomalyWatchdog

        anomaly = AnomalyWatchdog(counters=step_counters)
        # cluster_size as a gauge: the time-series sampler turns it into
        # the fleet's resize/heal history (`gauge:cluster_size` series)
        step_counters.set_gauge("cluster_size", float(peer.size))
    while offset < cfg.total_samples:
        m_step0 = time.monotonic()
        step_before = step
        try:
            step_once()
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:  # noqa: BLE001 - vetted below
            if not (heal_armed and _suspected_peer_failure(e)):
                raise
            _recover(e)
        else:
            # the honest per-step number: a step that absorbed a resize or
            # poll is reported as-is — the histogram tail IS that story
            tracing.record_span("step", m_step0, cat="train",
                                args={"step": step_before})
            if step_counters is not None:
                dt_ms = (time.monotonic() - m_step0) * 1e3
                step_counters.observe_hist("step_latency_ms", dt_ms)
                anomaly.observe(dt_ms)

    if _prev_sigterm is not None:
        signal.signal(signal.SIGTERM, _prev_sigterm)

    if ckpt is not None:
        ckpt.wait()  # settle queued async saves; latest_step lists only finalized
        if ckpt.writes and ckpt.latest_step() != step:  # avoid double-save when the loop just did
            save_ckpt(force=True)
        ckpt.close()

    loss = float(np.asarray(metrics["loss"]))
    dt = time.monotonic() - t_start  # monotonic: NTP steps must not skew run duration
    totals = sorted(e.get("total_s", sum(e["phases"].values()))
                    for e in resize_events)

    def _pct(p: float) -> Optional[float]:
        if not totals:
            return None
        import math

        # nearest-rank percentile: ceil(p*n)-1 (int(p*n) is upper-biased —
        # with 2 resizes it would report the max as the median)
        return round(totals[max(0, math.ceil(p * len(totals)) - 1)], 4)

    return {
        "loss": loss,
        "trained_samples": offset,
        "resizes": resizes,
        "final_size": peer.size,
        "seconds": dt,
        "resize_events": resize_events,
        "resize_p50_s": _pct(0.50),
        "resize_p95_s": _pct(0.95),
        "heals": len(heal_events),
        "heal_events": heal_events,
        "mttr_s": heal_events[-1]["mttr_s"] if heal_events else None,
        "state": state,
        "trainer": trainer,
    }
