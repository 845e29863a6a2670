"""FSDP — fully-sharded data parallelism over the `fsdp` mesh axis.

The reference has no parameter sharding at all (its optimizers replicate the
model on every worker); this is TPU-native capability backing the `fsdp`
axis declared in plan/mesh.py.  The design is ZeRO-3 re-expressed the XLA
way, inside the same shard_map-manual train step the DataParallelTrainer
uses:

  storage   every param / optimizer-state leaf lives as a flat, padded
            chunk: logically `(n_fsdp, chunk)` sharded on dim 0, so each
            device persistently holds 1/n of the model + optimizer state.
  compute   the step all_gathers each param's chunks (tiled all_gather on
            the fsdp axis rides ICI), reshapes to the original shape, and
            runs forward/backward on full params.
  gradients reduce_scatter (lax.psum_scatter) brings each device exactly
            its chunk of the summed gradient — half the bytes of a full
            all_reduce — then a pmean over `dp` if a replicated data axis
            coexists (hybrid sharded DP).
  update    the inner optax transform runs element-wise on chunks, so any
            element-wise optimizer (sgd, momentum, adam, ...) works
            unchanged and its state is sharded for free.

The fsdp axis is also a data axis: each shard consumes a different slice of
the batch (DATA_AXES in plan/mesh.py).  `FSDPTrainer` mirrors the
DataParallelTrainer API so the two are drop-in interchangeable.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map
from .plan import make_mesh
from .train import TrainState, _put_global
from .utils import get_logger

log = get_logger("kungfu.fsdp")


def _chunk(x: np.ndarray, n: int) -> np.ndarray:
    """Flatten + zero-pad to a multiple of n -> (n, chunk)."""
    flat = np.asarray(x).reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, flat.dtype)])
    return flat.reshape(n, -1)


def _unchunk(c: np.ndarray, shape: Tuple[int, ...]) -> np.ndarray:
    size = int(np.prod(shape)) if shape else 1
    return np.asarray(c).reshape(-1)[:size].reshape(shape)


class FSDPTrainer:
    """Fully-sharded data-parallel trainer (same surface as DataParallelTrainer).

    Args:
      loss_fn: (params, batch) -> scalar loss for one shard's batch slice.
      tx: element-wise optax transform (its state shards with the params).
      mesh: mesh containing an `fsdp` axis (default: 1-D fsdp over all
            devices); an additional `dp` axis gives hybrid sharded DP.
      remat: rematerialize the forward so gathered full params are freed
             after forward and re-gathered in backward (true ZeRO-3 memory;
             costs one extra forward).
      compression: wire format for the cross-replica `dp` gradient mean
             (kungfu_tpu.compression config or registered name).  In hybrid
             sharded DP the dp axis is the replica (often cross-host/DCN)
             hop while fsdp rides ICI — so this compresses exactly the slow
             leg and leaves the reduce_scatter/all_gather fsdp traffic in
             full precision.  Ignored when the mesh has no dp axis.
      bucket_bytes: chunk the dp-leg gradient reduction into size-bucketed
             groups (optimizers/sync.py's packing), one collective per
             bucket over a flat buffer, instead of the per-leaf stream
             XLA's combiner fuses into a single block behind the last
             gradient — independent buckets are what the latency-hiding
             scheduler / Pallas ring kernels can overlap with the rest of
             the step.  Element-wise (uncompressed) reduction is
             numerically identical bucketed or not; a quantized dp wire
             re-aligns its block boundaries to the bucket buffer (within
             the documented error bound).  "auto" defers the size to the
             compute tuner's footprint table, resolved per model at
             trace time (optimizers/sync._resolve_bucket_bytes).
             Ignored without a dp axis.
      dma_collectives: route the fsdp-axis unshard/scatter through the
             Pallas DMA gather/scatter pair (ops/fused_matmul.py
             dma_all_gather / dma_reduce_scatter): the forward weight
             unshard rides the double-buffered DMA ring, and — because
             the pair is each other's custom VJP — the backward gradient
             reduce-scatter rides it too, overlapping hop h's transfer
             with the compute consuming hop h-1 instead of serializing
             the unshard against the matmuls.  Off by default: the
             remote-DMA kernels have not yet compiled on a chip
             (ROADMAP S8), so None/False run the lax.all_gather/
             psum_scatter program.  True selects the kernels; they still
             gate per call on shape/VMEM (compat.pallas_mode), and on a
             TPU a kernel that cannot compile raises the compiler's
             error.
      analyze: arm the kf-lint trace-time hook (kungfu_tpu.analysis): the
             compiled step is statically checked at its first train_step,
             raising AnalysisError before dispatch on error-severity
             findings.  None defers to KUNGFU_ANALYZE=1.
    """

    def __init__(
        self,
        loss_fn: Callable,
        tx: optax.GradientTransformation,
        mesh: Optional[Mesh] = None,
        remat: bool = False,
        donate: bool = True,
        compression=None,
        analyze: Optional[bool] = None,
        bucket_bytes: Optional[int] = None,
        dma_collectives: Optional[bool] = None,
    ):
        from . import compression as _compression_mod
        from .utils.envflag import analyze_enabled

        if isinstance(compression, dict):
            # eager key validation (compression/config.py): a typo'd axis
            # key would silently run the dp leg at full precision
            mesh_axes = (mesh.axis_names if mesh is not None else ("fsdp",))
            _compression_mod.validate_axis_keys(compression, mesh_axes,
                                                context="FSDPTrainer")
            compression = compression.get("dp")
        self._analyze = analyze_enabled(analyze)
        self._linted = False
        self.compression = (
            _compression_mod.resolve(compression) if compression is not None else None
        )
        # "auto" stays symbolic until the real gradient leaves exist
        # (dp_reduce resolves it through the tuner's footprint table)
        self.bucket_bytes = (
            bucket_bytes if bucket_bytes == "auto"
            else int(bucket_bytes) if bucket_bytes else None
        )
        self.dma_collectives = bool(dma_collectives)
        self._donate = donate
        self.loss_fn = loss_fn
        self.tx = tx
        self.mesh = mesh if mesh is not None else make_mesh(fsdp=-1)
        if "fsdp" not in self.mesh.axis_names:
            raise ValueError(f"mesh {self.mesh.axis_names} has no 'fsdp' axis")
        self.n_shard = self.mesh.shape["fsdp"]
        self.has_dp = "dp" in self.mesh.axis_names
        self.data_axes = ("dp", "fsdp") if self.has_dp else ("fsdp",)
        self.remat = remat
        self._shapes: Any = None  # pytree of original param shapes
        self._compiled_step: Optional[Callable] = None
        self._build_step(donate)  # installs self._build

    @property
    def world(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n

    # -- chunk layout -----------------------------------------------------------------

    def _spec_for(self, leaf) -> P:
        """Chunked leaves (n_fsdp, chunk) shard dim 0; scalars replicate."""
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[:1] == (self.n_shard,):
            return P("fsdp")
        return P()

    def _state_specs(self, tree):
        return jax.tree.map(self._spec_for, tree)

    # -- step construction ------------------------------------------------------------

    def _gather_params(self, chunks):
        """Per-device chunk views -> full params: the tiled all_gather on
        fsdp, riding the Pallas DMA ring when armed (dma_collectives) —
        whose custom VJP puts the backward reduce-scatter on the same
        data plane — and the plain lax lowering otherwise."""
        shapes = self._shapes
        use_dma = self.dma_collectives

        def gather(c, shape):
            flat = c.reshape(-1)
            if use_dma:
                from .ops.fused_matmul import dma_all_gather

                full = dma_all_gather(flat, "fsdp")
            else:
                full = lax.all_gather(flat, "fsdp", tiled=True)
            size = int(np.prod(shape)) if shape else 1
            return full[:size].reshape(shape)

        return jax.tree.map(gather, chunks, shapes)

    def _scatter_grads(self, grads):
        """Full grads -> this device's summed chunk (reduce_scatter on the
        DMA ring when armed, lax.psum_scatter otherwise)."""
        n = self.n_shard
        use_dma = self.dma_collectives

        def scatter(g):
            flat = g.reshape(-1)
            pad = (-flat.size) % n
            if pad:
                flat = jnp.concatenate([flat, jnp.zeros(pad, flat.dtype)])
            if use_dma:
                from .ops.fused_matmul import dma_reduce_scatter

                chunk = dma_reduce_scatter(flat, "fsdp")
            else:
                chunk = lax.psum_scatter(flat, "fsdp", scatter_dimension=0,
                                         tiled=True)
            chunk = chunk / n
            if self.has_dp:
                chunk = lax.pmean(chunk, "dp")
            return chunk

        return jax.tree.map(scatter, grads)

    def _make_step_body(self, opt_spec) -> Callable:
        """Per-device (inside-shard_map) step: (params, opt, batch) ->
        (params, opt, loss), all in the sharded (1, chunk) leaf layout.

        NOTE on gradients: value_and_grad differentiates w.r.t. the chunk
        inputs THROUGH the all_gather — the autodiff transpose of a tiled
        all_gather is exactly psum_scatter, so grads arrive already
        reduce_scattered to this device's chunk; _scatter_grads is only
        exposed for callers composing manually.  The transpose SUMS the
        per-shard loss grads; S-SGD semantics average them (each shard's
        loss is the mean over its own batch slice), hence the /n below.
        """
        n_shard = self.n_shard

        def squeeze_opt(o):
            # sharded opt leaves arrive (1, chunk) per device; scalars whole
            return jax.tree.map(
                lambda l, s: jnp.squeeze(l, 0) if s == P("fsdp") else l,
                o, opt_spec,
            )

        def expand_opt(o):
            return jax.tree.map(
                lambda l, s: l[None] if s == P("fsdp") else l, o, opt_spec
            )

        def dp_mean(g):
            if self.compression is not None:
                from . import compression as Comp

                return Comp.all_reduce(g, "dp", self.compression, op="mean")
            return lax.pmean(g, "dp")

        def dp_reduce(grads):
            """Cross-replica mean of the (already reduce_scattered) chunk
            grads: per-leaf by default, one collective per size bucket
            with bucket_bytes — the dp-leg overlap knob."""
            if not self.has_dp:
                return grads
            if not self.bucket_bytes:
                return jax.tree.map(dp_mean, grads)
            from .optimizers.sync import (
                _bucketed_reduce, _pack_buckets, _record_bucket_layout,
                _resolve_bucket_bytes,
            )

            leaves, treedef = jax.tree.flatten(grads)
            bb = _resolve_bucket_bytes(self.bucket_bytes, leaves)
            if not bb:
                return jax.tree.map(dp_mean, grads)
            buckets = _pack_buckets(leaves, bb)
            _record_bucket_layout(leaves, buckets)
            return jax.tree.unflatten(treedef, _bucketed_reduce(
                leaves, buckets, lambda flat, _bi: dp_mean(flat)))

        def step(params, opt_state, batch):
            chunks = jax.tree.map(lambda c: jnp.squeeze(c, 0), params)
            opt_state = squeeze_opt(opt_state)

            def compute_loss(ch, b):
                return self.loss_fn(self._gather_params(ch), b)

            f = jax.checkpoint(compute_loss) if self.remat else compute_loss
            loss, grads = jax.value_and_grad(f)(chunks, batch)
            grads = dp_reduce(jax.tree.map(lambda g: g / n_shard, grads))
            updates, opt_state = self.tx.update(grads, opt_state, chunks)
            chunks = optax.apply_updates(chunks, updates)
            loss = lax.pmean(loss, self.data_axes)
            return (
                jax.tree.map(lambda c: c[None], chunks),
                expand_opt(opt_state),
                loss,
            )

        return step

    def _build_step(self, donate: bool) -> Callable:
        def build(params_template, opt_template):
            param_spec = jax.tree.map(lambda _: P("fsdp", None), params_template)
            opt_spec = self._state_specs(opt_template)
            single = self._make_step_body(opt_spec)

            def step(params, opt_state, batch):
                params, opt_state, loss = single(params, opt_state, batch)
                return params, opt_state, {"loss": loss}

            fn = _shard_map(
                step,
                mesh=self.mesh,
                in_specs=(param_spec, opt_spec, P(self.data_axes)),
                out_specs=(param_spec, opt_spec, P()),
                check_vma=False,
            )
            return jax.jit(fn, donate_argnums=(0, 1) if donate else ())

        self._build = build
        return None

    # -- host API ---------------------------------------------------------------------

    def init(self, params: Any) -> TrainState:
        """Chunk + shard host params, init sharded optimizer state."""
        n = self.n_shard
        self._shapes = jax.tree.map(lambda x: tuple(np.asarray(x).shape), params)
        chunked = jax.tree.map(lambda x: _chunk(np.asarray(x), n), params)
        opt_state = self.tx.init(
            jax.tree.map(lambda c: jnp.asarray(c), chunked)
        )
        return self._place(chunked, opt_state)

    def _place(self, chunked, opt_state, step: int = 0) -> TrainState:
        pspec = NamedSharding(self.mesh, P("fsdp", None))

        def place_param(c):
            return _put_global(jnp.asarray(c), pspec)

        def place_opt(leaf):
            spec = self._spec_for(np.asarray(leaf))
            return _put_global(jnp.asarray(leaf), NamedSharding(self.mesh, spec))

        params = jax.tree.map(place_param, chunked)
        opt_state = jax.tree.map(place_opt, opt_state)
        if self._compiled_step is None:
            self._compiled_step = self._build(params, opt_state)
        return TrainState(params=params, opt_state=opt_state, step=step)

    def place_state(self, params: Any, opt_state_full: Any = None, step: int = 0) -> TrainState:
        """Checkpoint-restore path: full host params (+ optionally full
        opt_state whose leaves mirror param shapes) -> sharded TrainState."""
        n = self.n_shard
        self._shapes = jax.tree.map(lambda x: tuple(np.asarray(x).shape), params)
        chunked = jax.tree.map(lambda x: _chunk(np.asarray(x), n), params)
        if opt_state_full is None:
            opt_state = self.tx.init(jax.tree.map(lambda c: jnp.asarray(c), chunked))
        else:
            def conv(leaf):
                a = np.asarray(leaf)
                return _chunk(a, n) if a.ndim >= 1 else a

            opt_state = jax.tree.map(conv, opt_state_full)
        return self._place(chunked, opt_state, step)

    def shard_batch(self, batch: Any) -> Any:
        from .train import _put_local_shard

        sharding = NamedSharding(self.mesh, P(self.data_axes))
        return jax.tree.map(lambda x: _put_local_shard(x, sharding), batch)

    def _lint_step(self, state: TrainState, batch: Any) -> None:
        """kf-lint the compiled step before its first dispatch (pure
        tracing on abstract inputs; runs once per trainer)."""
        from . import analysis

        comp = None
        if (self.has_dp and self.compression is not None
                and self.compression.scheme != "none"):
            comp = {"dp": self.compression}
        args = analysis.abstractify((state.params, state.opt_state, batch))
        analysis.check_and_raise(
            self._compiled_step, *args, mesh=self.mesh, compression=comp,
            context="FSDPTrainer.train_step",
        )
        self._linted = True

    def train_step(self, state: TrainState, batch: Any) -> Tuple[TrainState, Dict]:
        if self._analyze and not self._linted:
            self._lint_step(state, batch)
        params, opt_state, metrics = self._compiled_step(
            state.params, state.opt_state, batch
        )
        return TrainState(params, opt_state, state.step + 1), metrics

    def train_steps(self, state: TrainState, batch: Any, n: int) -> Tuple[TrainState, Dict]:
        """Run `n` steps on one device-resident batch in a single dispatch
        (compiled lax.scan; cached per n) — DataParallelTrainer parity."""
        if not hasattr(self, "_multi"):
            self._multi: Dict[int, Callable] = {}
        fn = self._multi.get(n)
        if fn is None:
            fn = self._multi[n] = self._build_multi(state.params, state.opt_state, n)
        params, opt_state, metrics = fn(state.params, state.opt_state, batch)
        return TrainState(params, opt_state, state.step + n), metrics

    def _build_multi(self, params_template, opt_template, n: int) -> Callable:
        param_spec = jax.tree.map(lambda _: P("fsdp", None), params_template)
        opt_spec = self._state_specs(opt_template)
        single = self._make_step_body(opt_spec)

        def many(params, opt_state, batch):
            def body(carry, _):
                p, o = carry
                p, o, loss = single(p, o, batch)
                return (p, o), loss

            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), None, length=n
            )
            return params, opt_state, {"loss": losses[-1]}

        fn = _shard_map(
            many,
            mesh=self.mesh,
            in_specs=(param_spec, opt_spec, P(self.data_axes)),
            out_specs=(param_spec, opt_spec, P()),
            check_vma=False,
        )
        return jax.jit(fn, donate_argnums=(0, 1) if self._donate else ())

    def eval_params(self, state: TrainState) -> Any:
        """Reassemble full params on host from the sharded chunks."""
        return jax.tree.map(
            lambda c, shape: _unchunk(np.asarray(c), shape),
            state.params, self._shapes,
        )
