"""In-program collective primitives over mesh axes.

TPU-native replacement for the reference's entire data plane: the Go
message-passing engine (srcs/go/kungfu/session/session.go:218-313 runGraphs/
runStrategies) and the NCCL controller (srcs/cpp/src/nccl/*).  Everything
here runs *inside* jit/shard_map: XLA compiles the collectives onto ICI/DCN,
which also dissolves the reference's NCCL arrival-order scheduler
(srcs/cpp/src/nccl/scheduler.cpp) — ordering is fixed at trace time.

Functions take an `axis_name` (or a tuple) and must be called under
`shard_map`/`pjit` with that mesh axis in scope.  Four allreduce
implementations back the strategy enum (plan/strategy.py):

  psum_all_reduce          STAR/TREE/BINARY_TREE
  rs_ag_all_reduce         CLIQUE/MULTI_STAR (phased, bandwidth-optimal)
  ring_all_reduce          RING (explicit chunked ppermute ring)
  hierarchical_all_reduce  BINARY_TREE_STAR (ici reduce-scatter -> dcn psum
                           -> ici all-gather; the GenBinaryTreeStar analog,
                           cf. srcs/cpp/src/nccl/controller.cpp:8-40)
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

AxisName = Union[str, Tuple[str, ...]]

# --- reduce ops (reference srcs/go/kungfu/base/op.go:20-37: SUM/MIN/MAX/PROD) --------

_REDUCE_FNS: Dict[str, Callable] = {
    "sum": lax.psum,
    "max": lax.pmax,
    "min": lax.pmin,
}


def all_reduce(x: jax.Array, axis_name: AxisName, op: str = "sum") -> jax.Array:
    """One-shot allreduce; XLA picks the ICI algorithm. op in {sum,min,max,prod,mean}."""
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "prod":
        # no pprod primitive: exp/sum/log trick is lossy, so gather+reduce
        g = lax.all_gather(x, axis_name)
        return jnp.prod(g, axis=0)
    return _REDUCE_FNS[op](x, axis_name)


psum_all_reduce = all_reduce


def rs_ag_all_reduce(x: jax.Array, axis_name: AxisName, op: str = "sum") -> jax.Array:
    """reduce_scatter + all_gather phased allreduce.

    Spreads every byte over all links — the analog of the reference's
    multi-graph chunk spreading (session/session.go:288-313) done natively.
    Only SUM is phased; other ops fall back to one-shot.
    """
    if op != "sum":
        return all_reduce(x, axis_name, op)
    n = _axis_size(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    scat = lax.psum_scatter(flat.reshape(n, -1), axis_name, scatter_dimension=0, tiled=False)
    out = lax.all_gather(scat, axis_name, tiled=False)
    return out.reshape(-1)[: x.size].reshape(x.shape)


def ring_all_reduce(x: jax.Array, axis_name: str, op: str = "sum") -> jax.Array:
    """Explicit chunked ring allreduce via ppermute (RING strategy).

    Standard 2(n-1)-step schedule: reduce-scatter ring then all-gather ring.
    Mirrors the reference's GenCircularGraphPair routing
    (srcs/go/plan/topology.go:149-177) expressed as XLA ppermute, which lands
    on the ICI torus neighbors.
    """
    if op != "sum":
        return all_reduce(x, axis_name, op)
    n = _axis_size(axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def rs_step(ch, s):
        send_i = (idx - s) % n
        buf = jnp.take(ch, send_i, axis=0)
        recv = lax.ppermute(buf, axis_name, perm)
        recv_i = (idx - s - 1) % n
        return ch.at[recv_i].add(recv), None

    chunks, _ = lax.scan(rs_step, chunks, jnp.arange(n - 1))

    def ag_step(ch, s):
        send_i = (idx + 1 - s) % n
        buf = jnp.take(ch, send_i, axis=0)
        recv = lax.ppermute(buf, axis_name, perm)
        recv_i = (idx - s) % n
        return ch.at[recv_i].set(recv), None

    chunks, _ = lax.scan(ag_step, chunks, jnp.arange(n - 1))
    return chunks.reshape(-1)[: x.size].reshape(x.shape)


def hierarchical_all_reduce(
    x: jax.Array, ici_axis: str, dcn_axis: str, op: str = "sum"
) -> jax.Array:
    """Two-level allreduce: ici reduce-scatter -> dcn allreduce -> ici all-gather.

    The reference ships local NCCL reduce -> single-master CPU cross-host
    allreduce -> local NCCL bcast (nccl/controller.cpp:8-40, gpu/collective.cpp:
    105-156).  Here every local rank carries 1/L of the cross-host traffic
    instead of staging through one master — strictly more bandwidth.
    """
    if op != "sum":
        return all_reduce(all_reduce(x, ici_axis, op), dcn_axis, op)
    n = _axis_size(ici_axis)
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    scat = lax.psum_scatter(flat.reshape(n, -1), ici_axis, scatter_dimension=0, tiled=False)
    cross = lax.psum(scat, dcn_axis)
    out = lax.all_gather(cross, ici_axis, tiled=False)
    return out.reshape(-1)[: x.size].reshape(x.shape)


def cross_all_reduce(x: jax.Array, dcn_axis: str, op: str = "sum") -> jax.Array:
    """Cross-host-only allreduce (reference session/allreduce.go:38
    CrossAllReduce): reduce over the DCN axis alone, leaving intra-host
    values un-mixed.  Where the reference runs it among one local root per
    host, here every local rank reduces with its same-ici-coordinate
    counterparts on the other hosts — same cross-host semantics, L-way more
    cross-host bandwidth."""
    return all_reduce(x, dcn_axis, op)


# --- derived collectives --------------------------------------------------------------


def broadcast(x: jax.Array, axis_name: AxisName, root: int = 0) -> jax.Array:
    """Broadcast root's value: mask + psum (no p2p tree needed under SPMD).

    Replaces KungfuBroadcast (srcs/cpp/src/tensorflow/ops/cpu/collective.cpp:185).
    """
    idx = _flat_axis_index(axis_name)
    # select, don't multiply: x*mask would turn a non-root inf/NaN into NaN
    # and psum would propagate it, losing root's good values
    return lax.psum(jnp.where(idx == root, x, jnp.zeros_like(x)), axis_name)


def all_gather(x: jax.Array, axis_name: AxisName, tiled: bool = False) -> jax.Array:
    """Direct-exchange allgather (reference session/allgather.go:17-45)."""
    return lax.all_gather(x, axis_name, tiled=tiled)


def reduce_scatter(x: jax.Array, axis_name: AxisName) -> jax.Array:
    return lax.psum_scatter(x, axis_name, tiled=True)


def reduce(x: jax.Array, axis_name: AxisName, root: int = 0, op: str = "sum") -> jax.Array:
    """Reduce-to-root; non-roots get zeros (SPMD programs are symmetric)."""
    s = all_reduce(x, axis_name, op)
    idx = _flat_axis_index(axis_name)
    return jnp.where(idx == root, s, jnp.zeros_like(s))


def gather(x: jax.Array, axis_name: AxisName, root: int = 0) -> jax.Array:
    """Gather-to-root: root holds every peer's slice stacked on a new
    leading dim; non-roots get zeros (reference root-gather,
    session/session.go:185-207).  SPMD has no asymmetric receive, so the
    gather is an all_gather with non-root results masked — the wire cost is
    higher than a true root-gather but it rides ICI, and XLA drops the
    dead branches when the non-root outputs are unused."""
    g = lax.all_gather(x, axis_name)
    idx = _flat_axis_index(axis_name)
    return jnp.where(idx == root, g, jnp.zeros_like(g))


def barrier(axis_name: AxisName) -> jax.Array:
    """Tiny allreduce as a rendezvous (reference session/session.go:98-109)."""
    return lax.psum(jnp.ones((), jnp.int32), axis_name)


def consensus(x: jax.Array, axis_name: AxisName) -> jax.Array:
    """True iff every participant holds identical bytes.

    The reference allreduces MIN and MAX and compares (session/session.go:
    120-151); identical trick in XLA.  Works on any numeric dtype.
    """
    xf = x.astype(jnp.float32) if x.dtype == jnp.bool_ else x
    lo = lax.pmin(xf, axis_name)
    hi = lax.pmax(xf, axis_name)
    return jnp.all(lo == hi)


def group_all_reduce(
    xs: Sequence[jax.Array],
    axis_name: AxisName,
    op: str = "sum",
    impl: Callable = all_reduce,
    fuse: bool = False,
) -> List[jax.Array]:
    """Allreduce a list of tensors (reference ops/collective.py:70-72).

    With fuse=True, flattens all tensors into one buffer first — the analog
    of the reference's NCCL fusion path (optimizers/sync_sgd.py:81-112).
    Under XLA fusion rarely helps (collectives are already coalesced), but
    it is kept for strategy parity and benchmarks.
    """
    xs = list(xs)
    if not xs:
        return []
    if fuse:
        shapes = [x.shape for x in xs]
        sizes = [int(x.size) for x in xs]
        dt = jnp.result_type(*[x.dtype for x in xs])
        flat = jnp.concatenate([x.astype(dt).reshape(-1) for x in xs])
        red = impl(flat, axis_name, op) if impl is not all_reduce else all_reduce(flat, axis_name, op)
        out, off = [], 0
        for shp, sz, x in zip(shapes, sizes, xs):
            out.append(red[off : off + sz].reshape(shp).astype(x.dtype))
            off += sz
        return out
    return [impl(x, axis_name, op) for x in xs]


def ppermute_pair_exchange(
    x: jax.Array, axis_name: str, partner_perm: Sequence[Tuple[int, int]]
) -> jax.Array:
    """Exchange tensors along an explicit pairing permutation (gossip support)."""
    return lax.ppermute(x, axis_name, list(partner_perm))


# --- helpers --------------------------------------------------------------------------


def _axis_size(axis_name: AxisName) -> int:
    """Static size of one mesh axis, or the product over a tuple of axes."""
    return lax.axis_size(axis_name)


def _flat_axis_index(axis_name: AxisName) -> jax.Array:
    """Row-major flat index over one or several axes."""
    if isinstance(axis_name, (tuple, list)):
        idx = jnp.zeros((), jnp.int32)
        for a in axis_name:
            idx = idx * lax.axis_size(a) + lax.axis_index(a)
        return idx
    return lax.axis_index(axis_name)
