"""Seeded-bad collective programs — the kf-verify negative corpus.

Five traced programs, one per jaxpr rule, each minimal enough that exactly
its target rule fires (the test suite asserts the findings list is
precisely the expected one), plus one seeded-bad chunk-level Schedule per
schedule-oracle rule (`BAD_SCHEDULES`).  `python -m kungfu_tpu.analysis
--module kungfu_tpu.testing.bad_programs` runs both and is the canonical
non-zero CLI run.

Every case here is a real bug class we either hit or dodged on TPUs: the
axis typo and the divergent cond both compile cleanly and then hang a
multi-minute SPMD launch; the single-shared-recv-slot ring is the credit
deadlock PR 9's 2-slot handshake designed around; the rest silently
corrupt results.
"""
from __future__ import annotations

import dataclasses
from typing import List

from ..analysis.findings import (
    RULE_AXIS,
    RULE_DEADLOCK,
    RULE_PERMUTATION,
    RULE_REPLICATION,
    RULE_SCHED_DATAFLOW,
    RULE_SCHED_DEADLOCK,
    RULE_SCHED_SLOT,
    RULE_WIRE_DTYPE,
)
from ..analysis.programs import Program, _mesh, _sds
from ..analysis.schedule import (
    REDUCE,
    REDUCE_SCATTER,
    Schedule,
    Transfer,
    binary_tree_all_reduce,
    ring_reduce_scatter,
)


def _b_axis_typo():
    def build():
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        mesh = _mesh({"dp": 8})

        def body(x):
            return lax.psum(x, "dp ")  # trailing space: the classic typo

        fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P(),
                       check_vma=False)
        return fn, (_sds((8, 128)),), {"mesh": mesh}

    return build


def _b_cond_divergent():
    def build():
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        mesh = _mesh({"dp": 8})

        def body(x):
            i = lax.axis_index("dp")
            # devices disagree on the branch; only one branch psums -> hang
            return lax.cond(i % 2 == 0,
                            lambda v: lax.psum(v, "dp"),
                            lambda v: v, x)

        fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                       check_vma=False)
        return fn, (_sds((8, 128)),), {"mesh": mesh}

    return build


def _b_bad_ppermute():
    def build():
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        mesh = _mesh({"dp": 8})
        # rank 1 receives twice, rank 0 never: double-write + starvation
        perm = [(0, 1), (1, 1)] + [(i, i) for i in range(2, 8)]

        def body(x):
            return lax.ppermute(x, "dp", perm)

        fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                       check_vma=False)
        return fn, (_sds((8, 128)),), {"mesh": mesh}

    return build


def _b_raw_psum_on_int8_axis():
    def build():
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        mesh = _mesh({"dp": 8})

        def body(x):
            # full-precision words on an axis deployed with an int8 wire
            return lax.psum(x, "dp")

        fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P(),
                       check_vma=False)
        return fn, (_sds((8, 4096)),), {"mesh": mesh,
                                        "compression": {"dp": "int8"}}

    return build


def _b_unreduced_gradient():
    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        mesh = _mesh({"dp": 8})

        def loss(p, b):
            return jnp.mean((b @ p) ** 2)

        def body(p, b):
            g = jax.grad(loss)(p, b)  # per-device grads, never psummed
            return p - 0.01 * g       # ...flowing into replicated params

        fn = shard_map(body, mesh=mesh, in_specs=(P(), P("dp")), out_specs=P(),
                       check_vma=False)
        return fn, (_sds((16, 4)), _sds((32, 16))), {"mesh": mesh}

    return build


#: program name -> the one rule it must trip (the test contract)
EXPECTED_RULE = {
    "bad-axis-typo": RULE_AXIS,
    "bad-cond-divergent-psum": RULE_DEADLOCK,
    "bad-nonbijective-ppermute": RULE_PERMUTATION,
    "bad-raw-psum-on-int8-axis": RULE_WIRE_DTYPE,
    "bad-unreduced-gradient": RULE_REPLICATION,
}

def _s_wrong_ownership() -> Schedule:
    """Ring RS whose declared owner map is rotated one rank off the
    routing: rank c+1 claims chunk c but the hops deliver it to rank c."""
    s = ring_reduce_scatter(4, 64, name="bad-sched-wrong-ownership")
    return dataclasses.replace(
        s, owners={str(c): (c + 1) % 4 for c in range(4)})


def _s_credit_cycle() -> Schedule:
    """Ring RS through ONE shared recv slot: hop s+1 into every rank
    waits on that rank's hop-s+1 send draining the slot — an n-cycle.
    The per-hop slot layout in ops/ring_kernels.py exists to break it."""
    s = ring_reduce_scatter(4, 64, name="bad-sched-credit-cycle")
    rounds = tuple(tuple(dataclasses.replace(t, slot="s0") for t in rnd)
                   for rnd in s.rounds)
    return dataclasses.replace(s, rounds=rounds)


def _s_double_writer() -> Schedule:
    """Two concurrent DMAs into the same scratch slot in one round; the
    dataflow still sums correctly, so only the race rule can catch it."""
    e = 64
    return Schedule(
        name="bad-sched-double-writer", world=3, collective=REDUCE_SCATTER,
        lax_equivalent="psum_scatter(scatter_dimension=0)", elems=e,
        chunk_elems={"0": e}, owners={"0": 2},
        rounds=((Transfer(0, 2, "0", "in", REDUCE, e),
                 Transfer(1, 2, "0", "in", REDUCE, e)),))


def _s_dropped_contribution() -> Schedule:
    """Heap-tree allreduce with one leaf's up-send deleted: the root
    reduces without rank 3's contribution and broadcasts the hole."""
    s = binary_tree_all_reduce(4, 64)
    rounds = tuple(tuple(t for t in rnd if t.src != 3) for rnd in s.rounds)
    return dataclasses.replace(s, name="bad-sched-dropped-contribution",
                               rounds=tuple(r for r in rounds if r))


def _s_double_count() -> Schedule:
    """A partial re-sent after it was already accumulated: rank 1's
    second arrival reduces contribution 0 twice (gradient counted 2x)."""
    e = 64
    return Schedule(
        name="bad-sched-double-count", world=2, collective="all_reduce",
        lax_equivalent="psum", elems=e, chunk_elems={"0": e}, owners={},
        rounds=((Transfer(0, 1, "0", "a", REDUCE, e),
                 Transfer(1, 0, "0", "b", REDUCE, e)),
                (Transfer(0, 1, "0", "a2", REDUCE, e),)))


#: schedule name -> the one oracle rule it must trip (the test contract)
EXPECTED_SCHEDULE_RULE = {
    "bad-sched-wrong-ownership": RULE_SCHED_DATAFLOW,
    "bad-sched-credit-cycle": RULE_SCHED_DEADLOCK,
    "bad-sched-double-writer": RULE_SCHED_SLOT,
    "bad-sched-dropped-contribution": RULE_SCHED_DATAFLOW,
    "bad-sched-double-count": RULE_SCHED_DATAFLOW,
}

BAD_SCHEDULES: List[Schedule] = [
    _s_wrong_ownership(),
    _s_credit_cycle(),
    _s_double_writer(),
    _s_dropped_contribution(),
    _s_double_count(),
]

SCHEDULES = BAD_SCHEDULES  # the CLI's --module hook picks this name up

PROGRAMS: List[Program] = [
    Program("bad-axis-typo", ("bad", RULE_AXIS), _b_axis_typo(),
            "psum over 'dp ' (trailing space) — unbound axis"),
    Program("bad-cond-divergent-psum", ("bad", RULE_DEADLOCK),
            _b_cond_divergent(),
            "cond on axis_index parity; one branch psums, one doesn't"),
    Program("bad-nonbijective-ppermute", ("bad", RULE_PERMUTATION),
            _b_bad_ppermute(),
            "ppermute where rank 1 is written twice and rank 0 starves"),
    Program("bad-raw-psum-on-int8-axis", ("bad", RULE_WIRE_DTYPE),
            _b_raw_psum_on_int8_axis(),
            "raw fp32 psum on an axis configured for an int8 wire"),
    Program("bad-unreduced-gradient", ("bad", RULE_REPLICATION),
            _b_unreduced_gradient(),
            "per-device gradient applied to replicated params, no psum"),
]
