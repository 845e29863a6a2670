"""Synchronous distributed optimizers as optax gradient transformations.

Reference: srcs/python/kungfu/tensorflow/optimizers/{core,sync_sgd,sma_sgd}.py.
The reference wraps a TF optimizer and splices collective ops into
apply_gradients; here each algorithm is an `optax.GradientTransformation`
meant to run *inside* a shard_map/pjit train step with a data-parallel mesh
axis in scope — the collectives compile into the step program, so there is
no scheduler and no op ordering problem (replacing the entire NCCL
scheduler, srcs/cpp/src/nccl/scheduler.cpp).

The real scheduling story (an earlier docstring claimed "XLA overlaps them
with compute" unconditionally — it does not): the per-leaf tree-map below
emits one collective per gradient leaf and XLA's all-reduce *combiner*
merges them into essentially ONE fused block scheduled after the last
gradient is produced — all communication serializes behind the end of
backprop.  `bucket_bytes` changes that: the gradient pytree is chunked
into size-bucketed groups (leaves packed in traversal order, per dtype)
and each bucket is reduced by its OWN collective over one flat buffer.
Independent collectives are exactly what XLA's latency-hiding scheduler
needs to hoist a bucket's AllReduce over compute that doesn't depend on
it — the fused computation-collective-ops placement (arXiv 2305.06942) —
and what the Pallas ring kernels (ops/pallas_collectives.py) need to
stream bucket k's DMA while bucket k+1 is still being produced.  Bucketed
and unbucketed reductions are numerically identical for the default pmean
path (element-wise mean is layout-independent); bucket layouts land in
the `collective_overlap` telemetry histogram at trace time.

Composition follows optax convention:

    tx = synchronous_sgd(optax.sgd(0.1), axis_name="dp",
                         bucket_bytes=4 << 20)
    # inside shard_map over mesh axis "dp":
    updates, state = tx.update(local_grads, state, params)
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Union, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import optax

from ..ops import collective as C
from .. import compression as Comp
from ..utils.envflag import analyze_enabled as _analyze_enabled

AxisName = Union[str, Tuple[str, ...]]


def _axes_tuple(axis_name: AxisName) -> Tuple[str, ...]:
    return tuple(axis_name) if isinstance(axis_name, (tuple, list)) else (axis_name,)


def _tree_pmean(tree, axis_name: AxisName):
    return jax.tree.map(lambda g: lax.pmean(g, axis_name), tree)


def _mean_reducer(axis_name: AxisName, impl: str):
    """Gradient-mean over the data axes using a named strategy implementation.

    The runtime-strategy analog inside the compiled step (the Session handles
    host-level ops; this handles the in-step gradient path): "pmean" lets
    XLA pick, "rs_ag"/"ring" force the phased/ring schedules, "pallas_ring"
    the hand-scheduled Pallas DMA ring (lax-ring fallback off-TPU), and
    "hierarchical" needs axis_name == (dcn, ici) — ici reduce-scatter, dcn
    psum, ici all-gather (ops/collective.py:115-135).
    """
    if impl == "pmean":
        return lambda g: lax.pmean(g, axis_name)

    def world():
        return C._axis_size(axis_name)

    if impl == "hierarchical":
        if not (isinstance(axis_name, (tuple, list)) and len(axis_name) == 2):
            raise ValueError(
                f"hierarchical reduction needs (dcn, ici) axes, got {axis_name!r}"
            )
        dcn, ici = axis_name
        return lambda g: C.hierarchical_all_reduce(g, ici, dcn) / world()
    if impl == "rs_ag":
        return lambda g: C.rs_ag_all_reduce(g, axis_name) / world()
    if impl in ("ring", "pallas_ring"):
        if isinstance(axis_name, (tuple, list)):
            raise ValueError("ring reduction needs a single axis")
        if impl == "pallas_ring":
            from ..ops import pallas_collectives as PC

            return lambda g: PC.ring_all_reduce(g, axis_name, op="mean")
        return lambda g: C.ring_all_reduce(g, axis_name) / world()
    raise ValueError(f"unknown reduce impl {impl!r}")


def default_bucket_bytes(total_grad_bytes: int) -> Optional[int]:
    """The `bucket_bytes="auto"` resolution: small gradient trees keep
    XLA's single fused collective (bucketing them only adds launch
    overhead); past ~2 buckets' worth the 4 MiB bucket layout wins by
    overlapping with backprop (docs/pallas.md)."""
    bucket = 4 << 20
    if total_grad_bytes <= 2 * bucket:
        return None
    return bucket


def _resolve_bucket_bytes(bucket_bytes, leaves) -> int:
    """The bucket size a sync layout actually runs with (0 = unbucketed).

    "auto" is `default_bucket_bytes`: small gradient trees keep
    XLA's single fused collective, larger ones get the 4 MiB overlap
    layout.  Resolved at trace time from the real leaves, so the same
    transform does the right thing for every model it's reused on.
    """
    if bucket_bytes == "auto":
        total = sum(int(g.size) * jnp.dtype(g.dtype).itemsize
                    for g in leaves)
        return default_bucket_bytes(total) or 0
    return int(bucket_bytes) if bucket_bytes else 0


def _pack_buckets(leaves, bucket_bytes: int):
    """Greedy in-traversal-order packing of leaf indices into size buckets.

    A bucket holds consecutive same-dtype leaves totalling at most
    `bucket_bytes` (one oversized leaf gets its own bucket) — preserving
    order keeps bucketed/unbucketed reductions element-aligned.
    """
    buckets, cur, cur_bytes, cur_dtype = [], [], 0, None
    for i, g in enumerate(leaves):
        b = int(g.size) * jnp.dtype(g.dtype).itemsize
        if cur and (g.dtype != cur_dtype or cur_bytes + b > bucket_bytes):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += b
        cur_dtype = g.dtype
    if cur:
        buckets.append(cur)
    return buckets


def _bucketed_reduce(leaves, buckets, reduce_flat):
    """Apply `reduce_flat(flat_1d, bucket_index)` over each bucket's
    concatenated leaves; single-leaf buckets skip the concat/split copies.
    Returns the reduced leaves in original order."""
    out = [None] * len(leaves)
    for bi, idxs in enumerate(buckets):
        if len(idxs) == 1:
            g = leaves[idxs[0]]
            out[idxs[0]] = reduce_flat(g.reshape(-1), bi).reshape(g.shape)
            continue
        flat = jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
        red = reduce_flat(flat, bi)
        off = 0
        for i in idxs:
            sz = int(leaves[i].size)
            out[i] = red[off:off + sz].reshape(leaves[i].shape)
            off += sz
    return out


def _record_bucket_layout(leaves, buckets) -> None:
    """Trace-time telemetry: per-bucket payload MiB into the
    `collective_overlap` histogram + a bucket-count gauge, so the PR-4
    scrape shows the gradient-sync layout the compiled step runs with
    (runs once per trace — host side effects do not retrace)."""
    from ..monitor.counters import counters_if_enabled

    c = counters_if_enabled()
    if c is None:
        return
    c.set_gauge("grad_sync_buckets", len(buckets))
    for idxs in buckets:
        mib = sum(int(leaves[i].size) * jnp.dtype(leaves[i].dtype).itemsize
                  for i in idxs) / float(1 << 20)
        c.observe_hist("collective_overlap", mib, label="grad_sync_mib")


def all_reduce_gradients(
    axis_name: AxisName = "dp",
    impl: str = "pmean",
    compression: Comp.AxisCompression = None,
    seed: int = 0,
    analyze: Optional[bool] = None,
    bucket_bytes: Union[int, str, None] = None,
) -> optax.GradientTransformation:
    """Gradient-averaging transform: the core of S-SGD (sync_sgd.py:81-112).

    Equivalent to the reference's group_all_reduce(grads) + /np.  Stateless
    when uncompressed.  `impl` selects the collective schedule (see
    _mean_reducer) — the in-step analog of the reference's swappable
    allreduce strategies.

    `compression` selects the wire format (kungfu_tpu.compression): a
    CompressionConfig / registered name applies to the whole reduction; a
    dict maps axis names to per-axis configs — with impl="hierarchical"
    and axis_name=(dcn, ici), {"dcn": "int8"} quantizes only the slow DCN
    leg.  Quantized configs with error_feedback=True keep an EF residual
    pytree in the transform state (error_feedback.py), so compression error
    re-enters the next step's gradients instead of being lost.

    `bucket_bytes` chunks the gradient pytree into size-bucketed groups
    (consecutive same-dtype leaves, at most bucket_bytes each) and reduces
    each bucket with its OWN collective over one flat buffer, instead of
    one per-leaf collective stream that XLA's combiner fuses into a single
    block behind the last gradient.  Independent per-bucket collectives
    are what the latency-hiding scheduler / Pallas DMA kernels can overlap
    with the rest of the step (module docstring has the full scheduling
    story).  Element-wise reductions (pmean, the default) are numerically
    IDENTICAL bucketed or not; chunked schedules (ring/rs_ag) and block-
    quantized wires re-align their chunk/block boundaries to the bucket
    buffer, which reorders fp32 adds / block scales within the documented
    error bounds.  None (default) keeps the single fused tree.

    `analyze` (or KUNGFU_ANALYZE=1) arms the kf-lint trace-time hook: at
    every trace of the update the declared axes are checked against the
    surrounding mesh scope and per-axis compression keys against the bound
    axes, raising analysis.AnalysisError before anything dispatches.
    """
    # eager per-axis key validation: a typo'd key would otherwise silently
    # run this reduction at full precision (compression/config.py)
    Comp.validate_axis_keys(compression, _axes_tuple(axis_name),
                            context="all_reduce_gradients")
    analyze_on = _analyze_enabled(analyze)

    def _lint_scope():
        if analyze_on:
            from .. import analysis

            analysis.check_axes_in_scope(axis_name, compression=compression,
                                         context="all_reduce_gradients")

    if compression is None:
        reducer = _mean_reducer(axis_name, impl)

        def init_fn(params):
            del params
            return optax.EmptyState()

        def update_fn(updates, state, params=None):
            del params
            _lint_scope()
            if bucket_bytes:
                leaves, treedef = jax.tree.flatten(updates)
                bb = _resolve_bucket_bytes(bucket_bytes, leaves)
                if bb:
                    buckets = _pack_buckets(leaves, bb)
                    _record_bucket_layout(leaves, buckets)
                    reduced = _bucketed_reduce(
                        leaves, buckets, lambda flat, _bi: reducer(flat))
                    return jax.tree.unflatten(treedef, reduced), state
            return jax.tree.map(reducer, updates), state

        return optax.GradientTransformation(init_fn, update_fn)

    return _compressed_all_reduce_gradients(axis_name, impl, compression,
                                            seed, _lint_scope, bucket_bytes)


class CompressedGradState(NamedTuple):
    ef: Comp.EFState
    key: jax.Array


def _compressed_reducer(axis_name: AxisName, impl: str,
                        compression: Comp.AxisCompression):
    """Per-leaf compressed mean-reduction for the selected schedule."""
    if impl == "hierarchical":
        if not (isinstance(axis_name, (tuple, list)) and len(axis_name) == 2):
            raise ValueError(
                f"hierarchical reduction needs (dcn, ici) axes, got {axis_name!r}"
            )
        dcn, ici = axis_name
        ici_cfg = Comp.resolve_for_axis(compression, ici)
        dcn_cfg = Comp.resolve_for_axis(compression, dcn)

        def reduce_leaf(g, key):
            return Comp.hierarchical_all_reduce(
                g, ici, dcn, ici_cfg, dcn_cfg, op="mean", key=key
            )

        # the residual tracks the error of the leg that quantizes first
        local_cfg = ici_cfg if ici_cfg.is_quantized else dcn_cfg
        return reduce_leaf, local_cfg

    # flat axis (or axis tuple): one wire format for the whole reduction
    cfg = Comp.resolve_for_axis(compression, axis_name)

    if impl == "pallas_ring" and not isinstance(axis_name, (tuple, list)):
        from ..ops import pallas_collectives as PC

        def reduce_leaf(g, key):
            # codec fused into the ring kernel; PC falls back to the
            # three-op XLA schedule (with the key) where it can't run
            return PC.fused_ring_all_reduce(g, axis_name, cfg, op="mean",
                                            key=key)

        return reduce_leaf, cfg

    def reduce_leaf(g, key):
        return Comp.all_reduce(g, axis_name, cfg, op="mean", key=key)

    return reduce_leaf, cfg


def _compressed_all_reduce_gradients(
    axis_name: AxisName, impl: str, compression: Comp.AxisCompression,
    seed: int, lint_scope=lambda: None,
    bucket_bytes: Union[int, str, None] = None,
) -> optax.GradientTransformation:
    reduce_leaf, local_cfg = _compressed_reducer(axis_name, impl, compression)
    use_ef = local_cfg.error_feedback and local_cfg.scheme != "none"

    def init_fn(params):
        return CompressedGradState(
            ef=Comp.error_feedback.init(params),
            key=jax.random.PRNGKey(seed),
        )

    def update_fn(updates, state, params=None):
        del params
        lint_scope()
        key, sub = jax.random.split(state.key)
        corrected = (
            Comp.error_feedback.correct(updates, state.ef) if use_ef else updates
        )
        leaves, treedef = jax.tree.flatten(corrected)
        if bucket_bytes and _resolve_bucket_bytes(bucket_bytes, leaves):
            buckets = _pack_buckets(
                leaves, _resolve_bucket_bytes(bucket_bytes, leaves))
            _record_bucket_layout(leaves, buckets)
            keys = jax.random.split(sub, len(buckets) + 1)
            reduced = jax.tree.unflatten(treedef, _bucketed_reduce(
                leaves, buckets,
                lambda flat, bi: reduce_leaf(flat, keys[bi])))
        else:
            keys = jax.random.split(sub, len(leaves) + 1)
            reduced = jax.tree.unflatten(
                treedef, [reduce_leaf(g, k) for g, k in zip(leaves, keys)]
            )
        # keep the inner optimizer's expected dtype
        reduced = jax.tree.map(
            lambda r, u: r.astype(jnp.asarray(u).dtype), reduced, updates
        )
        ef = (
            Comp.error_feedback.residual_update(corrected, local_cfg, keys[-1])
            if use_ef
            else state.ef
        )
        return reduced, CompressedGradState(ef=ef, key=key)

    return optax.GradientTransformation(init_fn, update_fn)


def synchronous_sgd(
    inner: optax.GradientTransformation,
    axis_name: AxisName = "dp",
    impl: str = "pmean",
    compression: Comp.AxisCompression = None,
    analyze: Optional[bool] = None,
    bucket_bytes: Union[int, str, None] = None,
) -> optax.GradientTransformation:
    """SynchronousSGDOptimizer: average grads across the mesh, then `inner`.

    Reference semantics (optimizers/sync_sgd.py:15-112, Horovod-equivalent):
    every worker applies the same averaged gradient, so parameters stay
    bitwise identical across replicas.  `compression` selects the gradient
    wire format and `bucket_bytes` the bucketed-overlap sync layout (see
    all_reduce_gradients) — the reduced result is still identical on every
    replica, so the invariant survives quantization and bucketing.
    `analyze` (or KUNGFU_ANALYZE=1) arms the kf-lint trace-time checks.
    """
    return optax.chain(
        all_reduce_gradients(axis_name, impl=impl, compression=compression,
                             analyze=analyze, bucket_bytes=bucket_bytes),
        inner,
    )


class SMAState(NamedTuple):
    inner: optax.OptState


def synchronous_averaging(
    inner: optax.GradientTransformation,
    axis_name: AxisName = "dp",
    alpha: float = 0.1,
) -> optax.GradientTransformation:
    """SynchronousAveragingOptimizer (SMA / EA-SGD).

    Reference (optimizers/sma_sgd.py:46-76): each step, every worker pulls
    its parameters toward the cluster average, v <- (1-a)v + a*avg(v), then
    applies its *local* gradients.  Folded into one optax update:

        updates = inner(local_grads) + a * (pmean(params) - params)

    Workers' models differ between steps (that's the point — SMA tolerates
    larger batch sizes than S-SGD, cf. the 16-worker ImageNet result in
    BASELINE.md), and consensus distance is controlled by alpha (=0.1 as the
    reference's fixed constant).
    """

    def init_fn(params):
        return SMAState(inner=inner.init(params))

    def update_fn(updates, state, params):
        if params is None:
            raise ValueError("synchronous_averaging requires params")
        u, inner_state = inner.update(updates, state.inner, params)
        avg = _tree_pmean(params, axis_name)
        u = jax.tree.map(lambda ui, p, av: ui + alpha * (av - p), u, params, avg)
        return u, SMAState(inner=inner_state)

    return optax.GradientTransformation(init_fn, update_fn)
