"""The built-in program corpus `python -m kungfu_tpu.analysis` lints.

Each Program lazily builds one representative collective program — the
shipped optimizers in the same harnesses the trainers run them in, the
Session collectives for every registered Strategy, the fsdp/pipeline
parallel schedules, and the example/benchmark train steps — plus the
check() arguments (mesh, compression) it is deployed with.  Tests assert
the whole corpus is error-free; the CLI re-checks it on demand, which is
what makes refactors of the collective layers cheap to trust.

Programs build against the CPU backend's virtual devices (conftest-style
`--xla_force_host_platform_device_count=8`); construction only traces —
nothing here dispatches to hardware.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence, Tuple


class ProgramUnavailable(Exception):
    """Raised by a build() whose prerequisites are absent (device count,
    optional dtypes); the CLI reports these as skipped, not failed."""


@dataclasses.dataclass
class Program:
    """One lintable program: name, tags, and a lazy builder returning
    (fn, example_args, check_kwargs).

    `suppress` names rule ids (findings.ALL_RULES) this program opts out
    of — the suppression surface for intentional violations; every entry
    must be justified in the program's description."""

    name: str
    tags: Tuple[str, ...]
    build: Callable[[], Tuple[Callable, tuple, dict]]
    description: str = ""
    suppress: Tuple[str, ...] = ()


def _devices(n: int):
    import jax

    devs = jax.devices()
    if len(devs) < n:
        raise ProgramUnavailable(
            f"needs {n} devices, have {len(devs)} (run with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    return devs[:n]


def _mesh(shape: Dict[str, int]):
    import numpy as np
    from jax.sharding import Mesh

    sizes = list(shape.values())
    total = 1
    for s in sizes:
        total *= s
    devs = _devices(total)
    return Mesh(np.asarray(devs).reshape(sizes), tuple(shape))


def _sds(shape, dtype="float32"):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _abstract(tree):
    from .check import abstractify

    return abstractify(tree)


# -- optimizer harnesses (the trainers' step shapes, specs under our control) ---------


def _toy_params():
    import numpy as np

    return {"w": np.zeros((32, 16), np.float32)}


def _toy_loss(p, b):
    import jax.numpy as jnp

    return jnp.mean(jnp.tanh(b @ p["w"]) ** 2)


def _replicated_opt_program(tx, mesh, axes, compression=None):
    """S-SGD-family harness: params/opt_state replicated, batch sharded —
    DataParallelTrainer's replicated mode with per-leaf specs honest."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    params = _toy_params()
    opt_state = tx.init(params)

    def step(p, s, batch):
        loss, g = jax.value_and_grad(_toy_loss)(p, batch)
        u, s = tx.update(g, s, p)
        p = optax.apply_updates(p, u)
        return p, s, lax.pmean(loss, axes)

    fn = shard_map(step, mesh=mesh, in_specs=(P(), P(), P(axes)), out_specs=(P(), P(), P()),
        check_vma=False,
    )
    world = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        world *= mesh.shape[a]
    batch = _sds((world * 4, 32))
    args = (_abstract(params), _abstract(opt_state), batch)
    return fn, args, {"mesh": mesh, "compression": compression}


def _per_replica_opt_program(tx, mesh, axis):
    """Gossip/SMA/adaptive harness: every state leaf carries a leading
    device dim sharded over the data axis (each replica owns its model)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    n = mesh.shape[axis]
    params = _toy_params()
    opt_state = tx.init(params)

    def stack(leaf):
        a = np.asarray(leaf)
        return np.broadcast_to(a[None], (n,) + a.shape)

    params_s = jax.tree.map(stack, params)
    opt_s = jax.tree.map(stack, opt_state)

    def step(p, s, batch):
        p = jax.tree.map(lambda x: jnp.squeeze(x, 0), p)
        s = jax.tree.map(lambda x: jnp.squeeze(x, 0), s)
        loss, g = jax.value_and_grad(_toy_loss)(p, batch)
        u, s = tx.update(g, s, p)
        p = optax.apply_updates(p, u)
        stack_ = lambda x: x[None]  # noqa: E731 - local lambda mirrors train.py
        return (jax.tree.map(stack_, p), jax.tree.map(stack_, s),
                lax.pmean(loss, axis))

    fn = shard_map(step, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P()), check_vma=False,
    )
    batch = _sds((n * 4, 32))
    args = (_abstract(params_s), _abstract(opt_s), batch)
    return fn, args, {"mesh": mesh}


# -- individual builders ----------------------------------------------------------------


def _b_ssgd(impl="pmean", axes="dp", mesh_shape=None, compression=None):
    def build():
        import optax

        from ..optimizers import synchronous_sgd

        mesh = _mesh(mesh_shape or {"dp": 8})
        tx = synchronous_sgd(optax.sgd(0.1), axis_name=axes, impl=impl,
                             compression=compression)
        return _replicated_opt_program(tx, mesh, axes, compression=compression)

    return build


def _b_sma():
    def build():
        import optax

        from ..optimizers import synchronous_averaging

        mesh = _mesh({"dp": 8})
        tx = synchronous_averaging(optax.sgd(0.1), axis_name="dp")
        return _per_replica_opt_program(tx, mesh, "dp")

    return build


def _b_gossip(selector):
    def build():
        import optax

        from ..optimizers import pair_averaging

        mesh = _mesh({"dp": 8})
        tx = pair_averaging(optax.sgd(0.1), axis_name="dp", selector=selector)
        return _per_replica_opt_program(tx, mesh, "dp")

    return build


def _b_adaptive():
    def build():
        import optax

        from ..optimizers import adaptive_sgd

        mesh = _mesh({"dp": 8})
        tx = adaptive_sgd(optax.sgd(0.1), switch_step=5, axis_name="dp")
        return _per_replica_opt_program(tx, mesh, "dp")

    return build


def _b_noise_adaptive():
    def build():
        import optax

        from ..optimizers import noise_adaptive_compression

        mesh = _mesh({"dp": 8})
        tx = noise_adaptive_compression(
            optax.sgd(0.1), local_batch_size=4, axis_name="dp",
            gns_threshold=1.0,
        )
        return _replicated_opt_program(tx, mesh, "dp",
                                       compression={"dp": "int8"})

    return build


def _b_session(strategy_name, mesh_shape, host_count, compression=None):
    def build():
        from ..plan import Strategy
        from ..session import Session

        mesh = _mesh(mesh_shape)
        sess = Session(mesh, host_count=host_count)
        strategy = Strategy.parse(strategy_name)
        impl = sess._impl(strategy)
        cfg = None
        comp_kw = None
        if compression is not None:
            from .. import compression as Comp

            cfg = Comp.resolve(compression)
            leg = "dcn" if sess._hierarchical_axes is not None else \
                mesh.axis_names[0]
            comp_kw = {leg: cfg}
        fn = sess._build("all_reduce", "sum", impl, compression=cfg)
        x = _sds((sess.size, 4, 64))
        return fn, (x,), {"mesh": mesh, "compression": comp_kw}

    return build


def _b_session_group():
    """The fused group-allreduce program (Session.group_all_reduce)."""

    def build():
        from ..plan import Impl
        from ..session import Session

        mesh = _mesh({"dp": 8})
        sess = Session(mesh)
        shapes = [(sess.size, 4, 32), (sess.size, 7), (sess.size, 3, 3, 5)]
        xs = tuple(_sds(s) for s in shapes)
        signature = tuple((x.shape, str(x.dtype)) for x in xs)
        fn = sess._fused_group_fn(signature, "sum", Impl.RS_AG)
        return fn, xs, {"mesh": mesh}

    return build


def _b_fsdp(hybrid: bool):
    """MeshTrainer's step over an fsdp mesh: the trainer the benchmark's
    four-chip cell runs (GSPMD: the collectives are the compiler's, the
    lint sees the program handed to it)."""
    def build():
        import jax
        import numpy as np
        import optax

        from ..models.transformer import TransformerConfig, TransformerLM, lm_loss
        from ..trainer import MeshTrainer

        mesh = _mesh({"dp": 2, "fsdp": 4} if hybrid else {"fsdp": 8})
        cfg = TransformerConfig(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128,
            max_len=32, mesh=mesh,
        )
        trainer = MeshTrainer(
            TransformerLM(cfg),
            lambda m, p, t: lm_loss(m.apply({"params": p}, t), t),
            optax.adam(1e-3), mesh=mesh)
        state = trainer.init(jax.random.PRNGKey(0),
                             np.zeros((16, 32), np.int32))
        args = (_abstract(state.params), _abstract(state.opt_state),
                _sds((16, 32), "int32"), _abstract(trainer._step_rng(0)))
        return trainer._step_fn, args, {"mesh": mesh}

    return build


def _b_pipeline(repeats: int):
    def build():
        import jax.numpy as jnp

        from ..parallel.pp import pipeline_apply_grouped

        mesh = _mesh({"pp": 4})
        S, R, M, mb, d = 4, repeats, 4, 2, 16

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"])

        group_params = {"w": _sds((S, R, d, d))}
        xs = _sds((M, mb, d))

        def fn(gp, x):
            return pipeline_apply_grouped(
                stage_fn, gp, x, mesh, axis_name="pp", repeats=R,
            )

        return fn, (group_params, xs), {"mesh": mesh}

    return build


def _b_mnist_slp():
    """The examples/mnist_slp.py train step (DataParallelTrainer + S-SGD)."""

    def build():
        import jax
        import jax.numpy as jnp
        import optax

        from ..models.slp import SLP, softmax_cross_entropy
        from ..optimizers import synchronous_sgd
        from ..train import DataParallelTrainer

        mesh = _mesh({"dp": 8})
        model = SLP()

        def loss_fn(params, batch):
            images, labels = batch
            return softmax_cross_entropy(
                model.apply({"params": params}, images), labels
            )

        tx = synchronous_sgd(optax.sgd(0.1))
        trainer = DataParallelTrainer(loss_fn, tx, mesh=mesh)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 28, 28), jnp.float32))["params"]
        opt_state = tx.init(params)
        batch = (_sds((32, 28, 28)), _sds((32,), "int32"))
        args = (_abstract(params), _abstract(opt_state), None, batch)
        return trainer._step_fn, args, {"mesh": mesh}

    return build


def _b_bench_compression(scheme: str):
    """One compressed all-reduce over the dp axis, per wire scheme."""

    def build():
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from .. import compression as Comp
        from jax import shard_map

        if scheme == "fp8" and getattr(jnp, "float8_e4m3fn", None) is None:
            raise ProgramUnavailable("no fp8 dtype in this jax build")
        mesh = _mesh({"dp": 8})
        cfg = Comp.resolve(scheme)

        def body(y):
            return Comp.all_reduce(jnp.squeeze(y, 0), "dp", cfg, op="sum")[None]

        fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                       check_vma=False)
        x = _sds((8, 1, 4096))
        comp_kw = {"dp": cfg} if cfg.scheme != "none" else None
        return fn, (x,), {"mesh": mesh, "compression": comp_kw}

    return build


def _b_serving_verify_k():
    """The serving engine's speculative verify-k decode program
    (serving/engine.py _verify_accept): a [slots, k] decode-mode forward
    with per-slot cache cursors, in-program greedy acceptance, and the
    per-slot cursor rollback — the ONE extra compiled decode signature of
    speculative serving."""

    def build():
        import jax
        import jax.numpy as jnp
        import flax.linen as nn

        from ..models.transformer import TransformerConfig, TransformerLM

        cfg = TransformerConfig(
            vocab_size=32, d_model=16, n_layers=1, n_heads=2, d_ff=32,
            max_len=32, rope=True, attention="full", dtype=jnp.float32,
            decode=True,
        )
        model = TransformerLM(cfg)
        slots, k = 2, 4
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((slots, 1), jnp.int32))
        params = nn.meta.unbox(variables["params"])
        cache = variables["cache"]

        def verify(params, cache, toks, proposals):
            logits, st = model.apply(
                {"params": params, "cache": cache}, toks, mutable=["cache"]
            )
            g = jnp.argmax(
                logits.astype(jnp.float32), axis=-1
            ).astype(jnp.int32)
            ok = (proposals == g[:, : k - 1]).astype(jnp.int32)
            n_acc = jnp.cumprod(ok, axis=1).sum(axis=1)

            def roll(path, leaf):
                if getattr(path[-1], "key", None) == "idx":
                    return leaf - (k - 1 - n_acc).astype(leaf.dtype)
                return leaf

            cache2 = jax.tree_util.tree_map_with_path(roll, st["cache"])
            return g, n_acc, cache2

        toks = _sds((slots, k), "int32")
        proposals = _sds((slots, k - 1), "int32")
        return verify, (_abstract(params), _abstract(cache), toks,
                        proposals), {}

    return build


def _b_serving_kv_ship():
    """The disaggregation KV-ship program (ops/kv_ship.ship_kv_rows): every
    cache leaf rotates to the paired decode rank — one remote DMA per hop
    on the PR-12 plane, the bit-identical ppermute lowering (linted here)
    off it."""

    def build():
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from ..ops.kv_ship import ship_kv_rows

        mesh = _mesh({"dp": 8})

        def body(rows):
            shipped = ship_kv_rows(
                {"cached_k": jnp.squeeze(rows, 0)}, "dp", 1
            )
            return shipped["cached_k"][None]

        fn = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                       check_vma=False)
        x = _sds((8, 16, 2, 8))
        return fn, (x,), {"mesh": mesh}

    return build


def builtin_programs() -> List[Program]:
    return [
        # optimizers — every shipped family in its trainer harness
        Program("optimizer-ssgd", ("optimizer",), _b_ssgd("pmean"),
                "synchronous SGD, XLA-chosen allreduce"),
        Program("optimizer-ssgd-rs-ag", ("optimizer",), _b_ssgd("rs_ag"),
                "synchronous SGD, phased reduce_scatter+all_gather"),
        Program("optimizer-ssgd-ring", ("optimizer",), _b_ssgd("ring"),
                "synchronous SGD, explicit ppermute ring"),
        Program("optimizer-ssgd-hierarchical", ("optimizer",),
                _b_ssgd("hierarchical", axes=("dcn", "ici"),
                        mesh_shape={"dcn": 2, "ici": 4}),
                "synchronous SGD, ici reduce-scatter / dcn psum / ici gather"),
        Program("optimizer-ssgd-int8", ("optimizer", "compression"),
                _b_ssgd("pmean", compression="int8"),
                "compressed S-SGD: int8 wire + error feedback"),
        Program("optimizer-ssgd-dcn-int8", ("optimizer", "compression"),
                _b_ssgd("hierarchical", axes=("dcn", "ici"),
                        mesh_shape={"dcn": 2, "ici": 4},
                        compression={"dcn": "int8"}),
                "hierarchical S-SGD quantizing only the DCN leg"),
        Program("optimizer-sma", ("optimizer",), _b_sma(),
                "synchronous model averaging (per-replica params)"),
        Program("optimizer-gossip", ("optimizer",), _b_gossip("random"),
                "randomized directed ring gossip"),
        Program("optimizer-gossip-roundrobin", ("optimizer",),
                _b_gossip("roundrobin"), "round-robin gossip shifts"),
        Program("optimizer-adaptive", ("optimizer",), _b_adaptive(),
                "SMA -> S-SGD switch with rank-0 broadcast"),
        Program("optimizer-noise-adaptive", ("optimizer", "compression"),
                _b_noise_adaptive(),
                "GNS-driven in-program wire-format switch (wire-dtype "
                "suppressed: the full-precision psum branch IS the design — "
                "the raw wire is taken deliberately when GNS says precision "
                "matters; the switch predicate is pmin-folded so the branch "
                "choice stays uniform)",
                suppress=("wire-dtype",)),
        # session collectives — the registered strategy implementations
        Program("session-star", ("session",),
                _b_session("STAR", {"dp": 8}, 1), "one-shot psum"),
        Program("session-ring", ("session",),
                _b_session("RING", {"dp": 8}, 1), "chunked ppermute ring"),
        Program("session-clique", ("session",),
                _b_session("CLIQUE", {"dp": 8}, 1),
                "phased reduce_scatter + all_gather"),
        Program("session-binary-tree-star", ("session",),
                _b_session("BINARY_TREE_STAR", {"dcn": 2, "ici": 4}, 2),
                "hierarchical ici/dcn allreduce"),
        Program("session-allreduce-int8", ("session", "compression"),
                _b_session("BINARY_TREE_STAR", {"dcn": 2, "ici": 4}, 2,
                           compression="int8"),
                "session allreduce with the DCN leg quantized"),
        Program("session-group-fused", ("session", "bench"),
                _b_session_group(),
                "fused group allreduce (benchmark scaling arm)"),
        Program("session-pallas-ring", ("session",),
                _b_session("PALLAS_RING", {"dp": 8}, 1),
                "hand-scheduled Pallas DMA ring (lints the program the "
                "strategy selects here: the kernels on TPU, the lax-ring "
                "fallback off it)"),
        Program("session-pallas-ring-fused", ("session", "compression"),
                _b_session("PALLAS_RING_FUSED", {"dp": 8}, 1,
                           compression="int8"),
                "Pallas ring with the int8 codec fused into the kernel "
                "body (three-op XLA schedule off-TPU)"),
        Program("session-pallas-fused-matmul", ("session",),
                _b_session("PALLAS_FUSED_MATMUL", {"dp": 8}, 1),
                "fused computation-collective strategy (its allreduce is "
                "the pallas ring pair; the matmul fusion itself lives in "
                "ops/fused_matmul)"),
        # parallel schedules
        Program("pipeline-gpipe", ("parallel",), _b_pipeline(1),
                "GPipe schedule over the pp ring"),
        Program("pipeline-circular", ("parallel",), _b_pipeline(2),
                "circular (interleaved) pipeline, 2 rounds"),
        Program("fsdp-plain", ("parallel",), _b_fsdp(False),
                "MeshTrainer adam step, pure fsdp axis"),
        # examples + benchmark programs
        Program("example-mnist-slp", ("example",), _b_mnist_slp(),
                "examples/mnist_slp.py train step"),
        Program("example-fsdp-transformer", ("example", "bench"),
                _b_fsdp(True),
                "examples/fsdp_transformer.py hybrid dp x fsdp step "
                "(the largest corpus program)"),
        Program("bench-compression-int8", ("bench", "compression"),
                _b_bench_compression("int8"),
                "compression.all_reduce under shard_map, int8 wire"),
        Program("bench-compression-bf16", ("bench", "compression"),
                _b_bench_compression("bf16"),
                "compression.all_reduce under shard_map, bf16 wire"),
        # serving v2 compiled programs (docs/serving.md)
        Program("serving-verify-k", ("serving",), _b_serving_verify_k(),
                "speculative decoding's [slots, k] verify step: decode-mode "
                "forward + in-program acceptance + per-slot cursor rollback"),
        Program("serving-kv-ship", ("serving",), _b_serving_kv_ship(),
                "disaggregation's KV ship: per-leaf rotation to the paired "
                "decode rank (ring_shift DMA on TPU, the ppermute lowering "
                "linted here)"),
    ]


def get_program(name: str) -> Program:
    for p in builtin_programs():
        if p.name == name:
            return p
    raise KeyError(f"no built-in program {name!r}")


def check_program(program: Program, suppress: Sequence[str] = ()):
    """Build + check one Program; returns its findings."""
    from .check import check

    fn, args, kwargs = program.build()
    merged = tuple(suppress) + tuple(program.suppress)
    return check(fn, *args, suppress=merged, **kwargs)
