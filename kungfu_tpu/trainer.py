"""MeshTrainer — one public trainer for multi-axis (dp x sp x tp x ep) models.

The reference is DP-only; this is the TPU-first capability layer promoted to
a product surface (VERDICT r1: multi-axis parallelism was proven only by the
hand-rolled step in __graft_entry__).  It follows the scaling-book recipe:

  1. the model annotates params/activations with LOGICAL axis names
     (flax.linen.spmd / nn.with_logical_partitioning);
  2. a rules table maps logical names onto mesh axes
     (parallel/sharding.py, auto-derived from the mesh by default);
  3. the step is one jit over the mesh — XLA's sharding propagation
     inserts every collective: gradient psums across the data axes,
     Megatron-style TP reductions, EP all_to_alls.

Optimizer composition: under pjit the gradient all-reduce IS the sharding
propagation, so S-SGD == any plain optax transform (the synchronous_sgd
wrapper's explicit pmean is the shard_map-trainer spelling of the same
thing).  Algorithms that need per-replica divergent models (SMA,
PairAveraging, AdaptiveSGD) express replica state explicitly — use
DataParallelTrainer(per_replica_params=True) for those; this trainer owns
the sharded-model families.

Ring attention composes through the model config: TransformerConfig(
attention="ring", mesh=...) runs its own shard_map island inside the jit.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
import optax
import flax.linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .parallel.sharding import param_shardings, rules_for_mesh
from .plan import make_mesh
from .train import TrainState, _put_local_shard
from .monitor import boot, programs
from .utils.trace import BOOT_CAT, record_span, trace_scope


class MeshTrainer:
    """Sharded-model trainer over an arbitrary parallelism mesh.

    Args:
      model: flax module whose params carry logical-axis metadata.
      loss_fn: (model, params, batch) -> scalar loss on the GLOBAL batch
        (per-example mean; XLA handles the cross-shard reduction).  A loss
        with a FOURTH required positional param — (model, params, batch,
        rng) — receives a fresh per-step PRNG key (derived from the init
        rng + step counter) for dropout / in-step data corruption.
      tx: optax transform (plain optimizers; see module docstring).
      mesh: the device mesh (dp/sp/tp/ep/fsdp axes).  An `fsdp` axis
        activates GSPMD fully-sharded parameters via the default rules
        (embed dims shard over fsdp, batch over dp AND fsdp), and
        composes with tp/sp/ep axes through the same rules table.
      rules: logical->mesh axis rules; default derives from the mesh.
      batch_axes: mesh axes the batch dim shards over (default: the axes
        the rules map "batch" to — dp, plus fsdp when present).
    """

    def __init__(
        self,
        model: nn.Module,
        loss_fn: Callable[[nn.Module, Any, Any], jax.Array],
        tx: optax.GradientTransformation,
        mesh: Optional[Mesh] = None,
        rules=None,
        batch_axes: Optional[Tuple[str, ...]] = None,
        donate: bool = True,
    ):
        self.model = model
        self.loss_fn = loss_fn
        # a loss with FOUR required positional params (model, params, batch,
        # rng) gets a per-step PRNG key — dropout, stochastic depth, MLM
        # corruption inside the step.  Only required positionals count:
        # optional kwargs (lm_loss_with_aux's aux_weight/z_loss) must not
        # flip the calling convention.
        import inspect

        required = [
            p for p in inspect.signature(loss_fn).parameters.values()
            if p.default is inspect.Parameter.empty
            and p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
        ]
        self._loss_takes_rng = len(required) >= 4
        self._base_rng = jax.random.PRNGKey(0)
        self.tx = tx
        self.mesh = mesh if mesh is not None else make_mesh(dp=-1)
        self.rules = rules if rules is not None else rules_for_mesh(self.mesh)
        names = self.mesh.axis_names
        # default batch axes follow the rules' "batch" mapping (dp, plus
        # fsdp when the mesh has one): placement matches the in-model
        # constraint, so no per-step resharding — and multi-controller
        # local batches assemble under the true global sharding
        if batch_axes is not None:
            self.batch_axes = batch_axes
        else:
            mapped = dict(self.rules).get("batch")
            if mapped is None:
                mapped = ()
            elif isinstance(mapped, str):
                mapped = (mapped,)
            self.batch_axes = tuple(a for a in mapped if a in names)
        self._donate = donate
        self._shardings = None
        self._step_fn = None
        self._booted = False
        programs.listen()  # the compile ledger of this trainer's start

    # -- init -------------------------------------------------------------------------

    def init(self, rng, sample_batch) -> TrainState:
        """Initialize params under the logical rules and place them sharded.

        `sample_batch` is a (host) global batch used only for shapes.  A
        boot phase (`train:init`), which ends when the state is placed.

        The optimizer state is created where it will live: a sub-tree of it
        that mirrors the parameters (the same tree and shapes: Adam's mu
        and nu, a momentum trace, an EMA) takes the parameters' shardings
        leaf for leaf, every other leaf (a step count, a schedule's scalar,
        a factored statistic) is replicated on the mesh.  Sharding
        propagation cannot do this: `tx.init` is `zeros_like` and fresh
        scalars, which depend on no VALUE of the parameters, so nothing
        propagates and every leaf would come back replicated, each chip
        holding and updating whole moments of tensors it owns a share of.
        The span's `args` say what was placed (`opt_state_sharded_leaves`,
        `opt_state_replicated_leaves`, `opt_state_bytes_a_chip`).
        """
        span_args: Dict[str, Any] = {}  # filled before the span closes
        with trace_scope("train:init", cat=BOOT_CAT, args=span_args):
            self._base_rng = jax.random.fold_in(rng, 0x5eed)  # loss-rng stream
            self._multi = {}  # compiled multi-step fns capture the base rng
            with nn.logical_axis_rules(self.rules):
                boxed = self.model.init(rng, *_as_args(sample_batch))["params"]
            self._shardings = param_shardings(self.mesh, boxed, self.rules)
            params = nn.meta.unbox(boxed)
            with self.mesh:
                placed = jax.jit(
                    lambda p: p, out_shardings=self._shardings)(params)
                # every leaf under an explicit sharding comes back COMMITTED
                # on the mesh: jit keys its cache on committedness, and the
                # step returns committed arrays, so an uncommitted initial
                # state compiled the step a second time
                opt_state = jax.jit(
                    self.tx.init,
                    out_shardings=self._opt_state_shardings(
                        jax.eval_shape(self.tx.init, placed), placed),
                )(placed)
            # the step hands its state back under exactly these shardings.
            # Left to the compiler, equal shardings come back spelled
            # differently (P() vs P(None, None)), which misses jit's cache:
            # the second step compiled the whole program again
            self._state_shardings = jax.tree.map(
                lambda x: x.sharding, (placed, opt_state)
            )
            self._step_fn = self._build_step()
            self._booted = False  # the next train_step is this state's first
            jax.block_until_ready((placed, opt_state))
            span_args.update(_opt_state_placement(opt_state))
        return TrainState(params=placed, opt_state=opt_state, step=0)

    def _opt_state_shardings(self, abstract, params):
        """Where each leaf of `tx.init`'s result (`abstract`: its shapes)
        lives: under `self._shardings` for every sub-tree that mirrors
        `params`, replicated on the mesh for every other leaf."""
        treedef = jax.tree.structure(params)
        shapes = [x.shape for x in jax.tree.leaves(params)]

        def mirrors(node):
            return (jax.tree.structure(node) == treedef
                    and [x.shape for x in jax.tree.leaves(node)] == shapes)

        replicated = NamedSharding(self.mesh, P())
        return jax.tree.map(
            lambda node: self._shardings if mirrors(node) else replicated,
            abstract, is_leaf=mirrors,
        )

    def _step_body(self, params, opt_state, batch, rng):
        """One step under the logical rules: shared by the single-step jit
        and the train_steps scan so the two can never diverge.

        Traced under `with self.mesh` so bare-PartitionSpec
        lax.with_sharding_constraint calls resolve.  Note this does NOT
        activate flax's ambient with_logical_constraint on the pinned
        versions (flax.core.meta.global_mesh_defined() stays false —
        verified against the lowered HLO); model constraints must pass the
        mesh explicitly via parallel.sharding.logical_constraint, which is
        why the rules context alone is not enough.
        """
        with self.mesh, nn.logical_axis_rules(self.rules):
            if self._loss_takes_rng:
                fn = lambda p: self.loss_fn(self.model, p, batch, rng)
            else:
                fn = lambda p: self.loss_fn(self.model, p, batch)
            loss, grads = jax.value_and_grad(fn)(params)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def _build_step(self):
        def step(params, opt_state, batch, rng):
            params, opt_state, loss = self._step_body(
                params, opt_state, batch, rng
            )
            return params, opt_state, {"loss": loss}

        return self._jit(step)

    def _jit(self, fn):
        """jit a (params, opt_state, ...) -> (params, opt_state, metrics) fn
        that hands the state back under the shardings it was placed with."""
        return jax.jit(
            fn, donate_argnums=(0, 1) if self._donate else (),
            out_shardings=(*self._state_shardings, None),
        )

    # -- host API ---------------------------------------------------------------------

    def shard_batch(self, batch: Any) -> Any:
        """Place a batch with its leading dim sharded over the batch axes.

        Single-controller: `batch` is global.  Multi-controller: this
        process's local shard.
        """
        spec = P(self.batch_axes if self.batch_axes else None)
        sharding = NamedSharding(self.mesh, spec)
        with trace_scope("train:shard_batch", cat="train"):
            return jax.tree.map(lambda x: _put_local_shard(x, sharding), batch)

    def _step_rng(self, step: int):
        """Per-step loss rng: the init key folded with the step counter —
        deterministic across restarts at the same step."""
        return jax.random.fold_in(self._base_rng, step)

    def train_step(self, state: TrainState, batch: Any) -> Tuple[TrainState, Dict]:
        if self._step_fn is None:
            raise RuntimeError("call init() before train_step()")
        # the host's part of a step: the rng fold and the jitted call until
        # it returns (the device runs on after it)
        t0 = time.monotonic()
        with trace_scope("train:step", cat="train",
                         args={"step": state.step}), self.mesh:
            params, opt_state, metrics = self._step_fn(
                state.params, state.opt_state, batch,
                self._step_rng(state.step),
            )
        if not self._booted:
            # the first step of a state traces, lowers and loads or compiles
            # the step program: the trainer's first call, and boot complete
            self._booted = True
            record_span("boot:first_call", t0, cat=BOOT_CAT,
                        args={"program": "train:step"})
            boot.complete()
        return TrainState(params, opt_state, state.step + 1), metrics

    def lower_step(self, state: TrainState, batch: Any):
        """The train step lowered for `state` and a placed `batch`
        (`jax.stages.Lowered`): `.as_text()` is the program handed to the
        compiler, `.compile().as_text()` the one it produced."""
        if self._step_fn is None:
            raise RuntimeError("call init() before lower_step()")
        with trace_scope("train:lower", cat=BOOT_CAT), self.mesh:
            return self._step_fn.lower(
                state.params, state.opt_state, batch,
                self._step_rng(state.step),
            )

    def _build_multi_step(self, n: int):
        base = self._base_rng

        def many(params, opt_state, batch, step0):
            def body(carry, i):
                p, o = carry
                # same per-step key formula as train_step: fold_in(base,
                # absolute step) — the two paths can never diverge
                p, o, loss = self._step_body(
                    p, o, batch, jax.random.fold_in(base, step0 + i)
                )
                return (p, o), loss

            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), jnp.arange(n)
            )
            return params, opt_state, {"loss": losses[-1]}

        return self._jit(many)

    def train_steps(self, state: TrainState, batch: Any, n: int) -> Tuple[TrainState, Dict]:
        """Run `n` steps on one device-resident batch in a single dispatch
        (compiled lax.scan; cached per n) — same contract as
        DataParallelTrainer.train_steps."""
        if self._step_fn is None:
            raise RuntimeError("call init() before train_steps()")
        if not hasattr(self, "_multi"):
            self._multi: Dict[int, Any] = {}
        fn = self._multi.get(n)
        if fn is None:
            fn = self._multi[n] = self._build_multi_step(n)
        with self.mesh:
            params, opt_state, metrics = fn(
                state.params, state.opt_state, batch,
                jnp.asarray(state.step, jnp.int32),
            )
        return TrainState(params, opt_state, state.step + n), metrics

    def eval_params(self, state: TrainState) -> Any:
        """Host copy of the fully materialized params.

        Multi-controller: sharded leaves span other hosts' devices, which
        np.asarray cannot fetch — re-place replicated first (every process
        then holds an addressable replica).
        """
        params = state.params
        if jax.process_count() > 1:
            rep = NamedSharding(self.mesh, P())
            with self.mesh:
                params = jax.jit(
                    lambda p: p,
                    out_shardings=jax.tree.map(lambda _: rep, params),
                )(params)
        return jax.tree.map(lambda x: np.asarray(x), params)


def _as_args(batch):
    return batch if isinstance(batch, tuple) else (batch,)


def _opt_state_placement(opt_state) -> Dict[str, int]:
    """What `init` placed, for the `train:init` span: the optimizer state's
    array leaves by whether a device holds a share or the whole, and the
    bytes one device holds of them (its addressable shards)."""
    leaves = jax.tree.leaves(opt_state)
    sharded = sum(not x.sharding.is_fully_replicated for x in leaves)
    return {
        "opt_state_sharded_leaves": sharded,
        "opt_state_replicated_leaves": len(leaves) - sharded,
        "opt_state_bytes_a_chip": sum(
            x.addressable_shards[0].data.nbytes for x in leaves),
    }
