"""Data-parallel trainer: the compiled SPMD train step + host loop.

This is the user-facing analog of the reference's "wrap your optimizer and
train" pattern (examples/tf2_mnist_gradient_tape.py): build a loss, pick a
distributed optimizer transform from kungfu_tpu.optimizers, and get a jitted
step function over the mesh.  The gradient collectives compile into the step
(no scheduler, no hooks) and XLA overlaps them with the backward pass — the
role of the reference's NCCL scheduler (srcs/cpp/src/nccl/scheduler.cpp) is
played by XLA's latency-hiding scheduler.

Two parameter modes, matching the optimizer families:

  replicated   (S-SGD): every replica applies the same averaged update, so
               params/opt_state live replicated (PartitionSpec ()) — one copy
               semantics, zero per-step divergence.
  per_replica  (SMA, PairAveraging, AdaptiveSGD before its switch): each
               replica owns its own model; params/opt_state carry a leading
               device dim sharded over the data axis — the single-controller
               representation of the reference's "every worker has its own
               model" state.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map
from .plan import make_mesh
from .utils import get_logger

log = get_logger("kungfu.train")


def _put_global(x, sharding: NamedSharding):
    """Place a GLOBAL-shaped array (every process holds the full value).

    Multi-controller: each process contributes its addressable shards via
    make_array_from_callback, indexing into the full array.
    """
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    arr = np.asarray(x)
    return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])


def _put_local_shard(x, sharding: NamedSharding):
    """Place a batch from per-process LOCAL shards (data-pipeline path)."""
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    return jax.make_array_from_process_local_data(sharding, np.asarray(x))


def first_local_replica(tree):
    """Host copy of each leaf's FIRST locally-addressable replica row.

    Per-replica leaves are (world, ...) sharded on dim 0; the first
    addressable shard is (1, ...) on this process — readable even when the
    global array spans other processes' devices.
    """

    def first(x):
        shards = getattr(x, "addressable_shards", None)
        if shards:
            return np.asarray(shards[0].data)[0]
        return np.asarray(x)[0]

    return jax.tree.map(first, tree)


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: int = 0
    # non-trainable model state (e.g. BatchNorm running statistics) threaded
    # through the step when the trainer is built with has_aux=True
    model_state: Any = None


class DataParallelTrainer:
    """Compiles loss+optimizer into one SPMD step over the mesh's data axis.

    Args:
      loss_fn: (params, batch) -> scalar loss for ONE replica's batch shard.
      tx: optax transform; kungfu_tpu.optimizers.* reduce/gossip inside.
      mesh: device mesh; defaults to 1-D "dp" over all devices.
      axis_name: the data axis the optimizer reduces over.
      per_replica_params: see module docstring.
      donate: donate params/opt_state buffers (halves HBM traffic per step).
      has_aux: loss_fn is (params, model_state, batch) -> (loss, new_model_state)
        and TrainState.model_state is threaded through every step.  This is
        how BatchNorm running statistics (flax `mutable=["batch_stats"]`)
        train for real instead of being baked in as compile-time constants.
        In replicated mode the new model_state is pmean'd across the data
        axis each step (cross-replica BN stat sync); in per_replica mode
        each replica keeps its own.
      accum_steps: gradient accumulation — the batch's leading dim splits
        into `accum_steps` microbatches, grads average over a lax.scan, and
        the optimizer applies once.  Trains global batches whose activations
        don't fit HBM; the distributed reduce still happens once per step
        (inside tx), exactly like fused-gradient S-SGD.
    """

    def __init__(
        self,
        loss_fn: Callable,
        tx: optax.GradientTransformation,
        mesh: Optional[Mesh] = None,
        axis_name: str = "dp",
        per_replica_params: bool = False,
        donate: bool = True,
        has_aux: bool = False,
        accum_steps: int = 1,
    ):
        self.loss_fn = loss_fn
        self.tx = tx
        self.mesh = mesh if mesh is not None else make_mesh(dp=-1)
        self.axis_name = axis_name
        self.per_replica = per_replica_params
        self.has_aux = has_aux
        self.accum_steps = accum_steps
        self._donate = donate
        self._step_fn = self._build_step(donate)

    @property
    def world(self) -> int:
        axes = self.axis_name if isinstance(self.axis_name, tuple) else (self.axis_name,)
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n

    # -- step construction ------------------------------------------------------------

    def _step_body(self, params, opt_state, model_state, batch):
        """One replica-local step: grads -> distributed tx -> apply.

        Returns (params, opt_state, model_state, loss), all in the same
        (possibly per-replica-stacked) layout they came in with.
        """
        axis = self.axis_name
        if self.per_replica:  # each shard carries leading dim 1: unstack
            unstack = lambda x: jnp.squeeze(x, 0)
            params = jax.tree.map(unstack, params)
            opt_state = jax.tree.map(unstack, opt_state)
            model_state = jax.tree.map(unstack, model_state)
        def sync_model_state(ms):
            # cross-replica sync of e.g. BN running stats so replicated
            # state stays identical on every device; non-float leaves
            # (counters, PRNG keys) must not be averaged
            return jax.tree.map(
                lambda x: jax.lax.pmean(x, axis)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
                else x,
                ms,
            )

        if self.accum_steps > 1:
            loss, model_state, grads = self._accum_grads(
                params, model_state, batch
            )
            if self.has_aux and not self.per_replica:
                model_state = sync_model_state(model_state)
        elif self.has_aux:
            (loss, model_state), grads = jax.value_and_grad(
                self.loss_fn, has_aux=True
            )(params, model_state, batch)
            if not self.per_replica:
                model_state = sync_model_state(model_state)
        else:
            loss, grads = jax.value_and_grad(self.loss_fn)(params, batch)
        updates, opt_state = self.tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        loss = jax.lax.pmean(loss, axis)
        if self.per_replica:
            stack = lambda x: x[None]
            params = jax.tree.map(stack, params)
            opt_state = jax.tree.map(stack, opt_state)
            model_state = jax.tree.map(stack, model_state)
        return params, opt_state, model_state, loss

    def _accum_grads(self, params, model_state, batch):
        """Microbatch scan: mean loss/grads over accum_steps slices of the
        replica-local batch; model_state (BN stats) threads sequentially."""
        a = self.accum_steps

        def split(x):
            n = x.shape[0]
            if n % a:
                raise ValueError(
                    f"replica-local batch dim {n} not divisible by "
                    f"accum_steps={a}"
                )
            return x.reshape((a, n // a) + x.shape[1:])

        micro = jax.tree.map(split, batch)
        gzero = jax.tree.map(jnp.zeros_like, params)

        def body(carry, mb):
            ms, gsum, lsum = carry
            if self.has_aux:
                (loss, ms), g = jax.value_and_grad(self.loss_fn, has_aux=True)(
                    params, ms, mb
                )
            else:
                loss, g = jax.value_and_grad(self.loss_fn)(params, mb)
            gsum = jax.tree.map(jnp.add, gsum, g)
            return (ms, gsum, lsum + loss.astype(jnp.float32)), None

        (model_state, gsum, lsum), _ = jax.lax.scan(
            body, (model_state, gzero, jnp.zeros((), jnp.float32)), micro
        )
        inv = 1.0 / a
        grads = jax.tree.map(lambda g: g * inv, gsum)
        return lsum * inv, model_state, grads

    def _build_step(self, donate: bool) -> Callable:
        state_spec = P(self.axis_name) if self.per_replica else P()
        data_spec = P(self.axis_name)

        def step(params, opt_state, model_state, batch):
            params, opt_state, model_state, loss = self._step_body(
                params, opt_state, model_state, batch
            )
            return params, opt_state, model_state, {"loss": loss}

        fn = _shard_map(
            step,
            mesh=self.mesh,
            in_specs=(state_spec, state_spec, state_spec, data_spec),
            out_specs=(state_spec, state_spec, state_spec, P()),
            check_vma=False,  # monitor/gossip states mix varying+invariant leaves
        )
        # observatory: the elastic train step promises ONE compiled
        # signature per incarnation — every rebuild re-declares the budget,
        # so a resize's legitimate recompile starts a fresh count while a
        # mid-incarnation shape change journals sig_budget_exceeded
        from .monitor.programs import track

        return track(
            "train_step",
            jax.jit(fn, donate_argnums=(0, 1, 2) if donate else ()),
            budget=1,
        )

    def _build_multi_step(self, n: int) -> Callable:
        """One compiled program running `n` steps (lax.scan) on a fixed batch.

        A single dispatch per n steps: on high-latency runtimes the
        per-dispatch round trip otherwise dominates step time.
        Used by benchmarks and tight loops where the batch is device-resident.
        """
        state_spec = P(self.axis_name) if self.per_replica else P()
        data_spec = P(self.axis_name)

        def many(params, opt_state, model_state, batch):
            def body(carry, _):
                p, o, m = carry
                p, o, m, loss = self._step_body(p, o, m, batch)
                return (p, o, m), loss

            (params, opt_state, model_state), losses = jax.lax.scan(
                body, (params, opt_state, model_state), None, length=n
            )
            return params, opt_state, model_state, {"loss": losses[-1]}

        fn = _shard_map(
            many,
            mesh=self.mesh,
            in_specs=(state_spec, state_spec, state_spec, data_spec),
            out_specs=(state_spec, state_spec, state_spec, P()),
            check_vma=False,
        )
        return jax.jit(fn, donate_argnums=(0, 1, 2) if self._donate else ())

    # -- host API ---------------------------------------------------------------------

    def init(self, params: Any, model_state: Any = None) -> TrainState:
        """Build TrainState; in per_replica mode, replicas start identical
        (the BroadcastGlobalVariables-at-init semantics,
        reference initializer/__init__.py:13-99)."""
        return self.place_state(params, self.tx.init(params), model_state=model_state)

    def place_state(
        self, params: Any, opt_state: Any, step: int = 0, model_state: Any = None
    ) -> TrainState:
        """Place host (params, opt_state) onto the mesh as a TrainState —
        also the checkpoint-restore path (single-replica snapshots are
        re-broadcast in per_replica mode)."""
        if model_state is None:
            if self.has_aux:
                raise ValueError(
                    "has_aux=True requires model_state (e.g. the model's "
                    "batch_stats collection) at init/place_state time"
                )
            model_state = {}
        if self.per_replica:
            n = self.world

            def stack(x):
                x = jnp.asarray(x)
                return jnp.broadcast_to(x[None], (n,) + x.shape)

            params = jax.tree.map(stack, params)
            opt_state = jax.tree.map(stack, opt_state)
            model_state = jax.tree.map(stack, model_state)
            sharding = NamedSharding(self.mesh, P(self.axis_name))
        else:
            sharding = NamedSharding(self.mesh, P())

        # always copy: the step donates its buffers, and returning the
        # caller's own arrays here would let donation delete them
        def place(x):
            return _put_global(jnp.copy(jnp.asarray(x)), sharding)

        params = jax.tree.map(place, params)
        opt_state = jax.tree.map(place, opt_state)
        model_state = jax.tree.map(place, model_state)
        return TrainState(
            params=params, opt_state=opt_state, step=step, model_state=model_state
        )

    def shard_batch(self, batch: Any) -> Any:
        """Place a batch sharded over the data axis.

        Single-controller: `batch` is the global batch.  Multi-controller
        (one process per host): `batch` is this process's LOCAL shard and is
        assembled into the global array (the per-worker data pipeline of the
        reference maps to exactly this).
        """
        sharding = NamedSharding(self.mesh, P(self.axis_name))
        return jax.tree.map(lambda x: _put_local_shard(x, sharding), batch)

    def train_steps(self, state: TrainState, batch: Any, n: int) -> Tuple[TrainState, Dict]:
        """Run `n` steps on one device-resident batch in a single dispatch
        (compiled lax.scan; cached per n)."""
        if not hasattr(self, "_multi"):
            self._multi: Dict[int, Callable] = {}
        fn = self._multi.get(n)
        if fn is None:
            fn = self._multi[n] = self._build_multi_step(n)
        ms = state.model_state if state.model_state is not None else {}
        params, opt_state, ms, metrics = fn(state.params, state.opt_state, ms, batch)
        return TrainState(params, opt_state, state.step + n, ms), metrics

    def train_step(self, state: TrainState, batch: Any) -> Tuple[TrainState, Dict]:
        ms = state.model_state if state.model_state is not None else {}
        params, opt_state, ms, metrics = self._step_fn(
            state.params, state.opt_state, ms, batch
        )
        return TrainState(params, opt_state, state.step + 1, ms), metrics

    def eval_params(self, state: TrainState, replica: int = 0) -> Any:
        """Materialize one replica's params (for eval/checkpoint).

        Multi-controller: returns this process's first LOCAL replica (the
        global row may not be addressable here).
        """
        if not self.per_replica:
            return state.params
        if jax.process_count() > 1:
            if replica != 0:
                raise ValueError(
                    "multi-controller eval_params can only read this "
                    "process's first local replica (pass replica=0)"
                )
            return jax.tree.map(jnp.asarray, first_local_replica(state.params))
        return jax.tree.map(lambda x: x[replica], state.params)

    def eval_model_state(self, state: TrainState, replica: int = 0) -> Any:
        """model_state analog of eval_params (BN stats at eval/checkpoint)."""
        if state.model_state is None:
            return None
        if not self.per_replica:
            return state.model_state
        if jax.process_count() > 1:
            if replica != 0:
                raise ValueError(
                    "multi-controller eval_model_state can only read this "
                    "process's first local replica (pass replica=0)"
                )
            return jax.tree.map(jnp.asarray, first_local_replica(state.model_state))
        return jax.tree.map(lambda x: x[replica], state.model_state)

    def fit(
        self,
        state: TrainState,
        data_iter,
        steps: int,
        log_every: int = 50,
        policies=None,
    ) -> Tuple[TrainState, Dict]:
        """Train for `steps`; `policies` is an optional sequence of
        BasePolicy hooks (reference PolicyHook, policy/policy_hook.py) or an
        already-configured PolicyRunner."""
        runner = None
        if policies is not None:
            from .policy import PolicyRunner

            runner = (
                policies
                if isinstance(policies, PolicyRunner)
                else PolicyRunner(policies, batch_size=0)
            )
            runner.begin()
        t0 = time.perf_counter()
        samples = 0
        metrics: Dict[str, Any] = {}
        for i in range(steps):
            if runner is not None:
                runner.before_step()
            batch = self.shard_batch(next(data_iter))
            n = int(jax.tree.leaves(batch)[0].shape[0])
            samples += n
            state, metrics = self.train_step(state, batch)
            if runner is not None:
                runner.after_step(n, metrics)
            if log_every and (i + 1) % log_every == 0:
                log.info("step %d loss %.4f", state.step, float(metrics["loss"]))
        if runner is not None:
            runner.end()
        jax.block_until_ready(metrics)
        dt = time.perf_counter() - t0
        metrics = dict(metrics)
        metrics["samples_per_sec"] = samples / dt
        return state, metrics
