"""Pipeline parallelism — GPipe and circular (interleaved) schedules as
differentiable shard_map scans.

Absent from the reference (DP-only).  TPU-first design: each device on the
"pp" mesh axis holds its stages' parameters (stage-stacked leading dims,
sharded over pp).  A `lax.scan` runs the schedule in lockstep ticks; every
tick each device applies one layer-group to its current activation and the
result rotates one hop along the ring (`ppermute` on ICI neighbors).
Because the schedule is pure lax ops, `jax.grad` through it yields the
reverse (backward) pipeline automatically — no hand-written 1F1B needed.

Two schedules, one engine:

  GPipe (repeats=1): S groups, one per device.  M microbatches flow once
  around the ring; total ticks M + S - 1, bubble (S-1)/(M+S-1), each tick
  costing 1/S of the model.

  Circular (repeats=R>1): the model is cut into S*R groups; device s holds
  groups {r*S + s : r < R} stacked on a leading round dim.  Microbatch i
  starts round r at device 0 on tick r*M + i: fresh microbatches are
  injected every tick for the first M ticks, and an activation finishing
  round r parks in a storage buffer at device 0 until its round-(r+1) turn
  (the maxtext/praxis circular-pipeline scheme).  Total ticks R*M + S - 1
  at 1/(S*R) of the model each => bubble (S-1)/(R*M+S-1), a factor-R
  reduction for the same microbatch count.  Requires M >= S.

Shapes (global): group_params leaves [S, R, ...] sharded P("pp"); x
[M, mb, ...]; out [M, mb, ...].  A "dp" axis, if present in the mesh,
rides along: each dp row runs an independent pipeline on its batch shard.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..plan.graph import validate_permutation


def pipeline_spmd(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    group_params: Any,
    xs: jax.Array,
    axis_name: str = "pp",
    repeats: int = 1,
    remat: bool = False,
):
    """The per-device (manual / inside-shard_map) pipeline schedule.

    Args (all per-device views):
      stage_fn: (group_params_r, h) -> h' — one layer-group's computation;
        h and h' share shape/dtype (the activation flowing through the pipe).
      group_params: pytree, leaves [R, ...] — this device's R rounds.
      xs: [M, mb, ...] microbatches (replicated across the pp axis).
    Returns [M, mb, ...] (pp-invariant: the last stage's outputs, psum-
    selected across the ring).
    """
    S = lax.axis_size(axis_name)
    M = xs.shape[0]
    R = repeats
    if R > 1 and M < S:
        raise ValueError(
            f"circular pipeline needs microbatches >= stages (M={M} < S={S})"
        )
    stage = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % S) for i in range(S)]
    # trace-time sanity on the ring wiring (plan.graph's bijection check,
    # shared with kf-lint): a non-bijective hop pattern hangs real TPUs
    validate_permutation(perm, S, what=f"pipeline ring over {axis_name!r}")

    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    # zeros_like inherits xs's vma (it may vary over dp when a data axis
    # rides along); pcast adds the pp axis the carries rotate over.  Under
    # shard_map(check_vma=False) nothing is typed as varying (not even
    # axis_index) and pcast's transpose, a psum of the cotangent, refuses
    # the untyped value: there the cast is left out
    typed = axis_name in jax.typeof(stage).vma

    def vary(x):
        return lax.pcast(x, axis_name, to="varying") if typed else x

    h0 = vary(jnp.zeros_like(xs[0]))
    out0 = vary(jnp.zeros_like(xs))
    store0 = vary(jnp.zeros_like(xs))

    def tick(carry, t):
        h, store, out = carry
        # device 0: park the activation arriving off the ring (it finished a
        # round at the last stage S ticks after starting it) for its next-
        # round turn; other devices never park
        if R > 1:
            park_slot = jnp.maximum(t - S, 0) % M
            parked = lax.dynamic_update_index_in_dim(store, h, park_slot, 0)
            store = jnp.where(jnp.logical_and(stage == 0, t >= S), parked, store)
        # device 0 input: fresh microbatch t while t < M, else the parked
        # activation whose next round starts now (slot t % M)
        fresh = lax.dynamic_index_in_dim(xs, jnp.minimum(t, M - 1), 0, keepdims=False)
        fresh = vary(fresh)
        if R > 1:
            recirc = lax.dynamic_index_in_dim(store, t % M, 0, keepdims=False)
            feed = jnp.where(t < M, fresh, recirc)
        else:
            feed = jnp.where(t < M, fresh, jnp.zeros_like(fresh))
        h = jnp.where(stage == 0, feed, h)
        # this device processes (mb i, round r) at tick t = r*M + i + stage
        r = jnp.clip((t - stage) // M, 0, R - 1)
        params_r = jax.tree.map(
            lambda p: lax.dynamic_index_in_dim(p, r, 0, keepdims=False),
            group_params,
        )
        h = stage_fn(params_r, h)
        # last stage emits mb i after its final round at t = (R-1)*M + i + S-1
        te = t - (S - 1)
        is_emit = jnp.logical_and(stage == S - 1, te >= (R - 1) * M)
        out = lax.cond(
            is_emit,
            lambda o: lax.dynamic_update_index_in_dim(
                o, h, jnp.maximum(te - (R - 1) * M, 0), 0
            ),
            lambda o: o,
            out,
        )
        h = lax.ppermute(h, axis_name, perm)
        return (h, store, out), None

    total = R * M + S - 1
    (h, store, out), _ = lax.scan(tick, (h0, store0, out0), jnp.arange(total))
    # only the last stage's out buffer is populated; psum selects it and
    # makes the result pp-invariant
    contrib = jnp.where(stage == S - 1, out, jnp.zeros_like(out))
    return lax.psum(contrib, axis_name)


def pipeline_apply_grouped(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    group_params: Any,
    x: jax.Array,
    mesh: Mesh,
    axis_name: str = "pp",
    repeats: int = 1,
    remat: bool = False,
) -> jax.Array:
    """Run x through S*repeats pipelined layer-groups over the mesh.

    group_params: pytree, leaves stacked [S, R, ...] — device s's round-r
    group at [s, r].  x: [M, mb, ...] microbatches.  Returns [M, mb, ...].
    """
    def inner(params, xs):
        params = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
        return pipeline_spmd(
            stage_fn, params, xs, axis_name=axis_name, repeats=repeats,
            remat=remat,
        )

    fn = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
    )
    return fn(group_params, x)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    axis_name: str = "pp",
) -> jax.Array:
    """GPipe over S = mesh.shape[axis_name] single-group stages.

    stage_params: pytree, leaves stacked [S, ...] (stage i's slice on dim 0).
    x: [M, mb, ...] microbatches.  (Compatibility surface over
    pipeline_apply_grouped with repeats=1.)
    """
    grouped = jax.tree.map(lambda p: p[:, None], stage_params)
    return pipeline_apply_grouped(
        stage_fn, grouped, x, mesh, axis_name=axis_name, repeats=1
    )


def stack_stage_params(params_list) -> Any:
    """Stack per-stage pytrees into the [S, ...] layout pipeline_apply wants."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def stack_group_params(params_lists) -> Any:
    """Stack a [S][R] nested list of group pytrees into [S, R, ...] leaves."""
    per_stage = [stack_stage_params(rounds) for rounds in params_lists]
    return stack_stage_params(per_stage)
