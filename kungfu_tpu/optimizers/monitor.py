"""In-step training monitors: gradient noise scale and gradient variance.

Reference: the GNS estimator (srcs/python/kungfu/tensorflow/ops/monitor.py:
6-18 global_noise_scale + the EMA'd NoiseScale kernel, srcs/cpp/src/
tensorflow/ops/cpu/collective.cpp:212-258) and the gradient-variance monitor
(optimizers/grad_variance.py:38-75).  Both are optax wrappers that pass
gradients through unchanged and write scalar metrics into their state, the
analog of the reference's named global variables
(tensorflow/variables.py:96-118); read them from opt_state after each step.

GNS math (McCandlish et al., "An Empirical Model of Large-Batch Training",
same estimator the reference implements):

    |G_small|^2 = squared norm of one worker's gradient  (batch b)
    |G_big|^2   = squared norm of the averaged gradient  (batch B = n*b)
    G_biased = (B*|G_big|^2 - b*|G_small|^2) / (B - b)     ~ |true grad|^2
    S_biased = (|G_small|^2 - |G_big|^2) / (1/b - 1/B)     ~ trace of noise cov
    gns      = ema(S) / ema(G)        (bias-corrected EMAs, alpha=0.6)
"""
from __future__ import annotations

from typing import NamedTuple, Union, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import optax


AxisName = Union[str, Tuple[str, ...]]


def _global_sq_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)


class _EMAState(NamedTuple):
    value: jax.Array
    count: jax.Array


def _ema_init() -> _EMAState:
    return _EMAState(value=jnp.zeros((), jnp.float32), count=jnp.zeros((), jnp.int32))


def _ema_update(s: _EMAState, x: jax.Array, alpha: float) -> Tuple[jax.Array, _EMAState]:
    """Bias-corrected EMA (reference include/kungfu/utils/ema.hpp)."""
    count = s.count + 1
    value = (1 - alpha) * s.value + alpha * x
    corrected = value / (1 - (1 - alpha) ** count.astype(jnp.float32))
    return corrected, _EMAState(value=value, count=count)


class NoiseScaleState(NamedTuple):
    inner: optax.OptState
    g_ema: _EMAState
    s_ema: _EMAState
    noise_scale: jax.Array  # the monitored metric


def gradient_noise_scale(
    inner: optax.GradientTransformation,
    local_batch_size: int,
    axis_name: AxisName = "dp",
    axis_size: int = None,
    alpha: float = 0.6,
) -> optax.GradientTransformation:
    """MonitorGradientNoiseScaleOptimizer (grad_noise_scale.py:42-90).

    Wraps `inner` (typically synchronous_sgd); estimates GNS from the
    local-vs-averaged gradient norms each step.  Read via
    `get_noise_scale(opt_state)`.
    """

    def init_fn(params):
        return NoiseScaleState(
            inner=inner.init(params),
            g_ema=_ema_init(),
            s_ema=_ema_init(),
            noise_scale=jnp.zeros((), jnp.float32),
        )

    def update_fn(updates, state, params=None):
        n = axis_size if axis_size is not None else lax.axis_size(axis_name)
        if n <= 1:
            # single worker: B == b makes the estimator 0/0 — pass through
            # with noise_scale pinned at 0 rather than poisoning the EMA
            u, inner_state = inner.update(updates, state.inner, params)
            return u, NoiseScaleState(
                inner=inner_state, g_ema=state.g_ema, s_ema=state.s_ema,
                noise_scale=jnp.zeros((), jnp.float32),
            )
        b_small = jnp.float32(local_batch_size)
        b_big = jnp.float32(local_batch_size * n)
        # cluster-mean of the per-worker norms: a lower-variance estimate of
        # E|G_small|^2 than any single worker's (and it keeps the monitor
        # state replica-invariant, so it composes with replicated params)
        g_small_sq = lax.pmean(_global_sq_norm(updates), axis_name)
        avg = jax.tree.map(lambda g: lax.pmean(g, axis_name), updates)
        g_big_sq = _global_sq_norm(avg)

        g_biased = (b_big * g_big_sq - b_small * g_small_sq) / (b_big - b_small)
        s_biased = (g_small_sq - g_big_sq) / (1.0 / b_small - 1.0 / b_big)

        g_val, g_ema = _ema_update(state.g_ema, g_biased, alpha)
        s_val, s_ema = _ema_update(state.s_ema, s_biased, alpha)
        gns = s_val / jnp.where(jnp.abs(g_val) > 1e-30, g_val, 1e-30)

        u, inner_state = inner.update(updates, state.inner, params)
        return u, NoiseScaleState(
            inner=inner_state, g_ema=g_ema, s_ema=s_ema, noise_scale=gns
        )

    return optax.GradientTransformation(init_fn, update_fn)


class GradVarianceState(NamedTuple):
    inner: optax.OptState
    variance: jax.Array


def gradient_variance(
    inner: optax.GradientTransformation,
    axis_name: AxisName = "dp",
) -> optax.GradientTransformation:
    """MonitorGradientVarianceOptimizer (grad_variance.py:38-75).

    variance = E|g_i|^2 - |E g_i|^2 across workers, one scalar per step.
    """

    def init_fn(params):
        return GradVarianceState(inner=inner.init(params), variance=jnp.zeros((), jnp.float32))

    def update_fn(updates, state, params=None):
        sq = _global_sq_norm(updates)
        mean_sq = lax.pmean(sq, axis_name)
        avg = jax.tree.map(lambda g: lax.pmean(g, axis_name), updates)
        sq_mean = _global_sq_norm(avg)
        var = jnp.maximum(mean_sq - sq_mean, 0.0)
        u, inner_state = inner.update(updates, state.inner, params)
        return u, GradVarianceState(inner=inner_state, variance=var)

    return optax.GradientTransformation(init_fn, update_fn)


# -- metric getters (analog of kungfu.tensorflow.variables getters) -------------------


def _find_state(opt_state, cls):
    found = []

    def visit(s):
        if isinstance(s, cls):
            found.append(s)
        if isinstance(s, (tuple, list)) and not hasattr(s, "_fields"):
            for x in s:
                visit(x)
        elif hasattr(s, "_fields"):
            for x in s:
                visit(x)

    visit(opt_state)
    return found[0] if found else None


def get_noise_scale(opt_state) -> jax.Array:
    s = _find_state(opt_state, NoiseScaleState)
    if s is None:
        raise ValueError("no gradient_noise_scale in this optimizer chain")
    return s.noise_scale


def get_gradient_variance(opt_state) -> jax.Array:
    s = _find_state(opt_state, GradVarianceState)
    if s is None:
        raise ValueError("no gradient_variance in this optimizer chain")
    return s.variance
