"""AdaptiveSGDOptimizer — SMA early, S-SGD late, broadcast at the switch.

Reference: srcs/python/kungfu/tensorflow/optimizers/ada_sgd.py:27-84.  The
reference runs SMA (loose consensus, good for early exploration) until a
configured step, then broadcasts rank 0's model to everyone (AdaSGDHook) and
continues with synchronous SGD (tight consensus).  Here the phase switch is a
`lax.cond` inside the compiled step — no hook, no separate graph.
"""
from __future__ import annotations

from typing import NamedTuple, Union, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import optax

from ..ops import collective as C

AxisName = Union[str, Tuple[str, ...]]


def _tree_pvary(tree, axis_name: AxisName):
    return jax.tree.map(lambda x: lax.pcast(x, axis_name, to="varying"), tree)


class AdaptiveSGDState(NamedTuple):
    step: jax.Array
    inner: optax.OptState


def adaptive_sgd(
    inner: optax.GradientTransformation,
    switch_step: int,
    axis_name: AxisName = "dp",
    alpha: float = 0.1,
) -> optax.GradientTransformation:
    """SMA for step < switch_step, S-SGD after; rank-0 broadcast at the switch."""

    def init_fn(params):
        return AdaptiveSGDState(step=jnp.zeros((), jnp.int32), inner=inner.init(params))

    def update_fn(updates, state, params):
        if params is None:
            raise ValueError("adaptive_sgd requires params")

        def sma_branch(args):
            g, istate, p = args
            u, s = inner.update(g, istate, p)
            avg = jax.tree.map(lambda x: lax.pmean(x, axis_name), p)
            u = jax.tree.map(lambda ui, pi, av: ui + alpha * (av - pi), u, p, avg)
            return u, s

        def ssgd_branch(args):
            g, istate, p = args
            g = jax.tree.map(lambda x: lax.pmean(x, axis_name), g)
            u, s = inner.update(g, istate, p)
            # pmean makes this branch's outputs replicated; mark them varying
            # so both cond branches have identical vma types
            return _tree_pvary((u, s), axis_name)

        # pmax-fold the step counter: every replica increments it in
        # lockstep, so this is the identity — but it makes the phase-switch
        # predicates replicated by construction, so all devices provably
        # take the same cond branch (the branches issue different
        # collective sequences; a device-varying predicate there would
        # hang real TPUs — kf-lint's deadlock rule)
        step = lax.pmax(state.step, axis_name)

        u, inner_state = lax.cond(
            step < switch_step, sma_branch, ssgd_branch,
            (updates, state.inner, params),
        )

        # at the switch step, snap every replica to rank 0's model
        # (AdaSGDHook broadcast, ada_sgd.py:61-84)
        def sync(u_):
            return jax.tree.map(
                lambda ui, p: ui + (C.broadcast(p, axis_name, root=0) - p), u_, params
            )

        u = lax.cond(step == switch_step, sync, lambda u_: u_, u)
        return u, AdaptiveSGDState(step=state.step + 1, inner=inner_state)

    return optax.GradientTransformation(init_fn, update_fn)


class NoiseAdaptiveCompressionState(NamedTuple):
    inner: optax.OptState
    g_ema: "Tuple[jax.Array, jax.Array]"  # monitor._EMAState fields
    s_ema: "Tuple[jax.Array, jax.Array]"
    noise_scale: jax.Array   # last step's bias-corrected GNS (the monitor metric)
    compressed: jax.Array    # bool: wire format chosen THIS step
    key: jax.Array


def noise_adaptive_compression(
    inner: optax.GradientTransformation,
    local_batch_size: int,
    axis_name: AxisName = "dp",
    gns_threshold: float = 0.0,
    compression="int8",
    axis_size: int = None,
    alpha: float = 0.6,
    seed: int = 0,
) -> optax.GradientTransformation:
    """S-SGD whose gradient wire format follows the gradient noise scale.

    Rationale: when the GNS is large, per-step gradients are dominated by
    sampling noise, so quantization error (bounded by absmax/127 per block)
    is far below the noise floor and compression is free; when the GNS
    drops (late training / large batches), gradients are informative and
    the wire goes back to full precision.  This is the compression analog
    of AdaptiveSGD's SMA->S-SGD consensus switch, driven by the SAME
    monitor (optimizers/monitor.py GNS estimator).

    The switch is a `lax.cond` inside the compiled step: both wire formats
    are compiled once, the replicated GNS EMA picks the branch each step —
    no recompilation, no host round-trip.  The decision uses the PREVIOUS
    step's EMA (one-step lag) so the collective choice never depends on
    bytes it is about to move.  gns_threshold <= 0 means "always compress"
    (the cond still exists but the predicate is constant-true after step 0).

    Read the monitored metric from the state via
    `optimizers.monitor._find_state(opt_state, NoiseAdaptiveCompressionState)`
    or the `get_compression_state` helper below.
    """
    from .monitor import _ema_init, _ema_update
    from .. import compression as Comp

    cfg = Comp.resolve(compression)
    if not (cfg.is_quantized or cfg.scheme == "bf16"):
        raise ValueError(
            f"noise_adaptive_compression needs a dense wire format, got {cfg.scheme!r}"
        )

    def init_fn(params):
        return NoiseAdaptiveCompressionState(
            inner=inner.init(params),
            g_ema=_ema_init(),
            s_ema=_ema_init(),
            noise_scale=jnp.zeros((), jnp.float32),
            compressed=jnp.zeros((), jnp.bool_),
            key=jax.random.PRNGKey(seed),
        )

    def update_fn(updates, state, params=None):
        from .monitor import _global_sq_norm

        n = axis_size if axis_size is not None else C._axis_size(axis_name)
        key, sub = jax.random.split(state.key)

        # ---- choose the wire from LAST step's EMA (replicated scalar; the
        # pmin fold makes "all replicas agree to compress" structural, so
        # the wire-format cond is provably uniform across devices) ----
        use_comp = state.noise_scale >= jnp.float32(gns_threshold)
        use_comp = lax.pmin(use_comp.astype(jnp.int32), axis_name) > 0

        leaves, treedef = jax.tree.flatten(updates)
        keys = jax.random.split(sub, len(leaves))

        def comp_branch(ls):
            return [
                Comp.all_reduce(g, axis_name, cfg, op="mean", key=k)
                for g, k in zip(ls, keys)
            ]

        def full_branch(ls):
            return _tree_pvary(
                [lax.pmean(g, axis_name) for g in ls], axis_name
            )

        avg_leaves = lax.cond(use_comp, comp_branch, full_branch, leaves)
        avg = jax.tree.unflatten(treedef, avg_leaves)

        # ---- GNS estimator on this step's gradients (monitor.py math) ----
        if n > 1:
            b_small = jnp.float32(local_batch_size)
            b_big = jnp.float32(local_batch_size * n)
            g_small_sq = lax.pmean(_global_sq_norm(updates), axis_name)
            g_big_sq = _global_sq_norm(avg)
            g_biased = (b_big * g_big_sq - b_small * g_small_sq) / (b_big - b_small)
            s_biased = (g_small_sq - g_big_sq) / (1.0 / b_small - 1.0 / b_big)
            g_val, g_ema = _ema_update(state.g_ema, g_biased, alpha)
            s_val, s_ema = _ema_update(state.s_ema, s_biased, alpha)
            gns = s_val / jnp.where(jnp.abs(g_val) > 1e-30, g_val, 1e-30)
        else:
            g_ema, s_ema = state.g_ema, state.s_ema
            gns = jnp.zeros((), jnp.float32)

        u, inner_state = inner.update(avg, state.inner, params)
        return u, NoiseAdaptiveCompressionState(
            inner=inner_state, g_ema=g_ema, s_ema=s_ema,
            noise_scale=gns, compressed=use_comp, key=key,
        )

    return optax.GradientTransformation(init_fn, update_fn)


def get_compression_state(opt_state) -> NoiseAdaptiveCompressionState:
    from .monitor import _find_state

    s = _find_state(opt_state, NoiseAdaptiveCompressionState)
    if s is None:
        raise ValueError("no noise_adaptive_compression in this optimizer chain")
    return s
