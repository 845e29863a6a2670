"""Decode-step attention (kungfu_tpu/ops/decode_attn.py).

The kernel's body in the Pallas interpreter against the dense einsum it
replaces on TPU, over ragged cursors, the verify shape, grouped queries, a
window and both cache dtypes, with NaN in every row beyond a cursor (a
kernel that read a dead row, or softmaxed an empty block, would show it);
its Mosaic lowering at the serving cells' shapes; and the choice between
kernel and einsum from what a call shows of itself.
"""
import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.export  # noqa: F401  (not an attribute until imported, on the pinned JAX)
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import Mesh

from kungfu_tpu.models.transformer import (TransformerConfig, TransformerLM,
                                           _compressed_keys)
from kungfu_tpu.ops import (decode_attention, decode_attention_reference,
                            mla_decode_attention_reference)
from kungfu_tpu.ops import decode_attn as da

MAX_LEN, BLOCK, D = 64, 16, 16

CURSORS = {  # eight slots each, so that one traced kernel serves them all
    "zero": [0] * 8,
    "one": [1] * 8,
    "block_minus_1": [BLOCK - 1] * 8,
    "block": [BLOCK] * 8,
    "block_plus_1": [BLOCK + 1] * 8,
    "last_row": [MAX_LEN - 1] * 8,
    # a slot at cursor 0 beside full ones, and every block count between
    "mix": [0, MAX_LEN - 1, 1, BLOCK, 3 * BLOCK - 1, 30, MAX_LEN - 1, 0],
}


def _case(cursors, L, H, Hkv, dtype, seed=0):
    """(q, cache_k, cache_v, positions, the cache with NaN beyond each
    slot's last position).  A cursor too near max_len for L rows is pulled
    back so the last query sits on the last row."""
    B = len(cursors)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, L, H, D), jnp.float32).astype(dtype)
    ck = jax.random.normal(kk, (B, MAX_LEN, Hkv, D), jnp.float32).astype(dtype)
    cv = jax.random.normal(kv, (B, MAX_LEN, Hkv, D), jnp.float32).astype(dtype)
    idx0 = jnp.minimum(jnp.asarray(cursors, jnp.int32), MAX_LEN - L)
    pos = idx0[:, None] + jnp.arange(L)[None, :]
    dead = (jnp.arange(MAX_LEN)[None, :] > pos[:, -1:])[:, :, None, None]
    return q, ck, cv, pos, jnp.where(dead, jnp.nan, ck), jnp.where(dead, jnp.nan, cv)


@functools.lru_cache(maxsize=None)
def _interpreted(window):
    """The kernel's body in the Pallas interpreter, traced once a shape."""
    return jax.jit(lambda q, k, v, pos: da._attn_pallas(
        q, k, v, pos, window, BLOCK, True,
        da.slot_walk(pos, None, BLOCK, MAX_LEN, window)))


def _check(cursors, L, H, Hkv, dtype, window):
    q, ck, cv, pos, ck_nan, cv_nan = _case(cursors, L, H, Hkv, dtype)
    want = decode_attention_reference(q, ck, cv, pos, window)
    got = _interpreted(window)(q, ck_nan, cv_nan, pos)
    assert got.shape == (len(cursors), L, H, D) and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    # float32: summation order only.  bf16: the probabilities are rounded
    # to bf16 before the running normaliser divides, not after
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("heads", [(16, 16), (8, 2)], ids=["mha16", "gqa8_2"])
@pytest.mark.parametrize("L", [1, 4])
@pytest.mark.parametrize("cursors", list(CURSORS), ids=list(CURSORS))
def test_kernel_matches_the_einsum_over_ragged_cursors(cursors, L, heads):
    _check(CURSORS[cursors], L, *heads, jnp.float32, 0)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("window", [0, 20])
@pytest.mark.parametrize("L", [1, 4])
def test_kernel_matches_the_einsum_with_a_window_and_a_bf16_cache(
        L, window, dtype):
    _check(CURSORS["mix"], L, 8, 2, dtype, window)


def test_a_row_of_the_verify_shape_sees_up_to_its_own_position():
    """Row l of a k-row call attends rows <= idx0 + l: changing cache row
    idx0 + 2 moves query rows 2 and 3 and leaves rows 0 and 1 alone."""
    q, ck, cv, pos, _, _ = _case([20] + [5] * 7, 4, 8, 2, jnp.float32)
    base = _interpreted(0)(q, ck, cv, pos)
    moved = _interpreted(0)(q, ck, cv.at[0, 22].add(1.0), pos)
    same = np.isclose(np.asarray(base), np.asarray(moved)).all(axis=(2, 3))
    assert same.tolist() == [[True, True, False, False]] + [[True] * 4] * 7


def test_live_blocks_are_the_same_on_the_host_and_in_the_program():
    lo = np.array([0, 15, 16, 40, 63, 70])
    hi = lo + np.array([0, 3, 0, 3, 0, 3])
    for window in (0, 20):
        host = da.live_blocks(np, lo, hi, BLOCK, MAX_LEN, window)
        prog = da.live_blocks(jnp, jnp.asarray(lo), jnp.asarray(hi), BLOCK,
                              MAX_LEN, window)
        for h, p in zip(host, prog):
            assert h.tolist() == np.asarray(p).tolist()
    first, last = da.live_blocks(np, lo, hi, BLOCK, MAX_LEN, 20)
    assert last.tolist() == [0, 1, 1, 2, 3, 3]     # rows beyond max_len clip
    assert first.tolist() == [0, 0, 0, 1, 2, 3]    # rows every window has left


def _listed(first, last, live):
    """The visit list written out: (slot, block) of every live slot's run."""
    return [(b, j) for b in range(len(first)) if live is None or live[b]
            for j in range(first[b], last[b] + 1)]


@pytest.mark.parametrize("live", [
    None, [True] * 6, [False] * 6, [False, True, False, False, True, False],
    [True, False, True, True, False, True]],
    ids=["none_given", "all", "no_slot", "two_of_six", "four_of_six"])
def test_the_visit_list_is_the_same_on_the_host_and_in_the_program(live):
    """`visits` under numpy (the engine's count of fetched rows) and under
    jax.numpy (what the kernels walk): the blocks first..last of every live
    slot, ordered by slot, and nothing of a slot that is not live; the
    places past the count hold block 0 of slot 0, which exists."""
    lo = np.array([0, 15, 16, 40, 63, 70])
    hi = lo + np.array([0, 3, 0, 3, 0, 3])
    mask = None if live is None else np.array(live)
    length = len(lo) * (MAX_LEN // BLOCK)
    for window in (0, 20):
        first, last = da.live_blocks(np, lo, hi, BLOCK, MAX_LEN, window)
        host = da.visits(np, first, last, mask, length)
        prog = da.visits(jnp, jnp.asarray(first), jnp.asarray(last),
                         None if live is None else jnp.asarray(mask), length)
        for h, p in zip(host, prog):
            assert np.asarray(h).tolist() == np.asarray(p).tolist()
        slot, block, n = host
        assert slot.dtype == block.dtype == np.int32 and slot.shape == (length,)
        assert list(zip(slot[:n].tolist(), block[:n].tolist())) == _listed(
            first, last, live)
        assert (slot[n:] == 0).all() and (block[n:] == 0).all()
        walk = da.slot_walk(jnp.stack([jnp.asarray(lo), jnp.asarray(hi)], 1),
                            None if live is None else jnp.asarray(mask),
                            BLOCK, MAX_LEN, window)
        assert int(walk.count) == n
        assert np.asarray(walk.slot).tolist() == slot.tolist()
        assert np.asarray(walk.block).tolist() == block.tolist()


# (cursors, query rows a slot, window, live): both kernels take every case
# but the window's, which the latent kernel has none of
LIVE_CASES = {
    "no_slot_live": (CURSORS["mix"], 1, 0, [False] * 8),
    "one_of_eight": ([0, 0, 0, 37, 0, 0, 0, 0], 1, 0,
                     [False, False, False, True, False, False, False, False]),
    "every_slot_at_max_len": ([MAX_LEN - 1] * 8, 1, 0, [True] * 8),
    # the window has left block 0 of the slots at 40 and beyond
    "window_first_block_not_0": ([40, 63, 5, 47, 63, 0, 33, 20], 1, 20,
                                 [True, True, False, True, False, False,
                                  True, True]),
    # four query rows at BLOCK - 2 .. BLOCK + 1: a slot's rows in two blocks
    "verify_rows_in_two_blocks": ([BLOCK - 2, 2 * BLOCK - 2, 0, 5, BLOCK - 2,
                                   3 * BLOCK - 3, 0, MAX_LEN - 1], 4, 0,
                                  [True, True, False, True, False, True,
                                   False, True]),
    "live_not_given": (CURSORS["mix"], 1, 0, None),
    "live_not_given_verify": (CURSORS["mix"], 4, 0, None),
}


@functools.lru_cache(maxsize=None)
def _interpreted_under_a_mask(kind, window):
    """Either kernel's body in the interpreter on the walk of a `live`
    mask, traced once a shape."""
    if kind == "latent":
        return jax.jit(lambda q, c, pos, live: da._mla_attn_pallas(
            q, c, pos, RANK, 0.25, BLOCK, True,
            da.slot_walk(pos, live, BLOCK, MAX_LEN)))
    return jax.jit(lambda q, k, v, pos, live: da._attn_pallas(
        q, k, v, pos, window, BLOCK, True,
        da.slot_walk(pos, live, BLOCK, MAX_LEN, window)))


@pytest.mark.parametrize("case,kind", [
    (c, k) for c in LIVE_CASES for k in ("per_head", "latent")
    if not (k == "latent" and LIVE_CASES[c][2])])
def test_kernels_match_their_einsums_under_a_live_mask(case, kind):
    """A live slot's rows are the einsum's; a slot that is not live comes
    back as zeros whatever its slot holds: NaN stands in EVERY row of such
    a slot (and beyond every live slot's cursor), and the kernel, which
    visits no block of it, neither reads it nor leaves its output
    unwritten."""
    cursors, L, window, live = LIVE_CASES[case]
    mask = np.ones(8, bool) if live is None else np.array(live)
    if kind == "latent":
        q, cache, pos, cache_nan = _latent_case(cursors, L, 4, jnp.float32)
        want = mla_decode_attention_reference(q, cache, pos, RANK, 0.25)
        leaves = (jnp.where(mask[:, None, None], cache_nan, jnp.nan),)
        plain = _latent_interpreted()
    else:
        q, ck, cv, pos, ck_nan, cv_nan = _case(cursors, L, 8, 2, jnp.float32)
        want = decode_attention_reference(q, ck, cv, pos, window)
        dead = ~mask[:, None, None, None]
        leaves = (jnp.where(dead, jnp.nan, ck_nan),
                  jnp.where(dead, jnp.nan, cv_nan))
        plain = _interpreted(window)
    if live is None:  # the walk the kernel builds for itself: every slot
        got = np.asarray(plain(q, *leaves, pos))
    else:
        got = np.asarray(_interpreted_under_a_mask(kind, window)(
            q, *leaves, pos, jnp.asarray(mask)))
    assert got.shape == want.shape and np.isfinite(got).all()
    assert (got[~mask] == 0.0).all()
    np.testing.assert_allclose(got[mask], np.asarray(want)[mask],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kind", ["per_head", "latent"])
def test_the_public_call_hands_live_to_the_kernel(kind, monkeypatch):
    """`decode_attention(..., live=)` and `mla_decode_attention(..., live=)`
    build the walk themselves where the caller brings none, and take the
    caller's where it does (the model's one list a step): the same rows
    either way, zeros for the slot that is not live; the einsum, off TPU,
    ignores the mask."""
    monkeypatch.setenv("KFT_PALLAS", "interpret")
    live = jnp.asarray([True, False] * 4)
    if kind == "latent":
        q, cache, pos, _ = _latent_case(CURSORS["mix"], 1, 4, jnp.float32)
        call = lambda **kw: da.mla_decode_attention(  # noqa: E731
            q, cache, pos, RANK, 0.25, **kw)
        block = da.kernel_block(1, cache.shape, cache.dtype)
        walk = da.slot_walk(pos, live, block, MAX_LEN)
    else:
        q, ck, cv, pos, _, _ = _case(CURSORS["mix"], 1, 8, 8, jnp.float32)
        q, ck, cv = (jnp.tile(t, (1, 1, 1, 8)) for t in (q, ck, cv))  # D = 128
        call = lambda **kw: decode_attention(q, ck, cv, pos, **kw)  # noqa: E731
        block = da.kernel_block(1, ck.shape, ck.dtype)
        walk = da.slot_walk(pos, live, block, MAX_LEN)
    assert block is not None
    every, masked, walked = call(), call(live=live), call(walk=walk)
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(walked))
    np.testing.assert_array_equal(np.asarray(masked)[::2], np.asarray(every)[::2])
    assert (np.asarray(masked)[1::2] == 0).all()
    assert np.abs(np.asarray(every)[1::2]).max() > 0
    monkeypatch.setenv("KFT_PALLAS", "off")
    np.testing.assert_allclose(np.asarray(call(live=live)), np.asarray(every),
                               rtol=2e-5, atol=2e-5)


# -- the lowering for the chip ---------------------------------------------------------


@pytest.mark.parametrize("L,dtype,window", [
    (1, jnp.bfloat16, 0),    # `_decode` of both serving cells
    (4, jnp.bfloat16, 0),    # `_verify_accept`
    (1, jnp.float32, 0),
    (1, jnp.bfloat16, 512),
], ids=["decode", "verify", "float32", "window"])
def test_lowers_for_tpu_at_the_cells_shapes(L, dtype, window):
    """`jax.export` runs the Pallas -> Mosaic lowering on this host:
    [8, L, 16, 128] against the [8, 2048, 16, 128] leaves of the cells."""
    q = jax.ShapeDtypeStruct((8, L, 16, 128), dtype)
    leaf = jax.ShapeDtypeStruct((8, 2048, 16, 128), dtype)
    pos = jax.ShapeDtypeStruct((8, L), jnp.int32)
    assert da.kernel_block(L, leaf.shape, dtype, interpret=False) == (
        256 if dtype == jnp.bfloat16 else 128)

    def f(q, k, v, p):
        return decode_attention(q, k, v, p, window, interpret=False)

    exp = jax.export.export(jax.jit(f), platforms=["tpu"])(q, leaf, leaf, pos)
    assert da.KERNEL_NAME in exp.mlir_module()


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described (not attached) v5e: the TPU compiler runs on
    this host.  Described inside the fixture, so only the worker that runs
    this file loads the TPU's library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _scatter_loops(text):
    """The `while` loops the TPU compiler expands a windowed scatter into,
    one turn a slot: what a vmapped `dynamic_update_slice` of a step's new
    cache rows becomes, two a layer."""
    return [name for name in re.findall(
        r' while\([^\n]*op_name="([^"]*)"', text) if name.endswith("/scatter")]


def _whole_leaves_moved(text, leaf):
    """Copies, transposes and converts of a whole cache leaf (`leaf`: a
    regular expression of its dimensions) outside fusions: inside a fusion
    nothing is written to HBM."""
    moved = []
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text):
        if "fused_computation" in comp.split("\n", 1)[0]:
            continue
        moved += re.findall(
            rf"= \S*\[{leaf}\]\S* (?:copy|transpose|convert)\(", comp)
    return moved


# (fields over the dense block, slots, the leaf's dimensions, kernel calls a layer)
STEP_MODELS = {
    "dense": ({}, 8, "8,2048,16,128", 1),
    "experts": (dict(n_experts=8, experts_per_token=2, moe_every=1), 8,
                "8,2048,16,128", 1),
    # jamba2-3b-serve's attention layers: one KV head at 64 slots of 4,096
    # rows, read by the dense einsum (no kernel for one KV head)
    "one_kv_head": (dict(d_model=2560, n_heads=20, n_kv_heads=1, max_len=4096,
                         rope=False, pos_table=False, norm="rms"), 64,
                    "64,4096,1,128", 0),
}


@pytest.mark.parametrize("call", ["four_arguments", "the_engines"])
@pytest.mark.parametrize("model", list(STEP_MODELS))
def test_the_engines_decode_program_reads_the_donated_cache_where_it_lies(
        v5e_chip, monkeypatch, model, call):
    """`ServingEngine._decode` at the cells' widths (two layers), compiled
    for the chip: one kernel call a layer, no copy, transpose or convert of
    a whole cache leaf ahead of it (a layout change of the operand would
    move 64 MiB a leaf a step), the step's new rows written with no loop
    over the slots (one scatter fusion a leaf), and the donated cache still
    aliases the program's output.  The mask that tells it which slots are
    free rides in the token upload: the one array a call brings from the
    host.  The engine's own call brings one more from the device, the
    [slots] tokens of the step before (a slot that holds CARRY takes its
    token from them), and is the same program otherwise."""
    from kungfu_tpu import compat
    from kungfu_tpu.ops.gmm import KERNEL_NAME as GMM
    from kungfu_tpu.serving import ServingEngine

    # the program asks jax.default_backend(), the CPU here: the test (not
    # the program) steers it onto the path the chip takes
    monkeypatch.setattr(compat, "pallas_mode", lambda interpret=None: "compiled")
    fields, slots, leaf, kernel_calls = STEP_MODELS[model]
    cfg = TransformerConfig(**{**dict(
        vocab_size=512, d_model=2048, n_layers=2, n_heads=16, d_ff=256,
        max_len=2048, rope=True, attention="full", dtype=jnp.bfloat16,
        ffn="swiglu"), **fields})
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip), tree)
    params = described(nn.meta.unbox(jax.eval_shape(
        TransformerLM(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 1), jnp.int32))["params"]))
    eng = ServingEngine(cfg, params, slots=slots)
    brought = [jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=v5e_chip)]
    if call == "the_engines":
        brought.append(described(eng._no_prev))
    lowered = eng._decode.lower(
        described(eng.params), described(eng.cache),
        described(eng._dev_counters), *brought)
    resident = (eng.params, eng.cache, eng._dev_counters)
    assert len(jax.tree.leaves(lowered.args_info)) == len(
        jax.tree.leaves(resident)) + len(brought)
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = lambda kernel: re.findall(  # noqa: E731
        rf"= \S+ custom-call\([^\n]*{kernel}", text)
    assert len(calls("kft_decode_attn")) == kernel_calls * cfg.n_layers
    # gate, up and down of every expert layer: one grouped matmul each
    assert len(calls(GMM)) == (3 * cfg.n_layers if cfg.n_experts else 0)
    assert _scatter_loops(text) == []
    assert _whole_leaves_moved(text, leaf) == []
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(eng.cache))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // (2 * cfg.n_layers)


# -- which of the two a call gets ------------------------------------------------------

LEAF = (8, 2048, 16, 128)


def test_selection_from_shape_and_dtype():
    pick = lambda rows=1, shape=LEAF, dtype=jnp.bfloat16, mode=True: (  # noqa: E731
        da.kernel_block(rows, shape, dtype, interpret=mode))
    assert pick() == 256
    assert pick(rows=da.MAX_QUERY_ROWS) == 256              # a verify's k rows
    assert pick(rows=16) is None                            # a prefill bucket
    assert pick(dtype=jnp.int8) is None                     # read through its scales
    assert pick(mode=None) is None                          # no kernels on this backend
    assert pick(shape=(8, 2048, 2, 128)) is None            # KV heads fill no tile
    assert pick(shape=(8, 2048, 16, 64)) is None            # head_dim fills no lane tile
    assert pick(shape=(8, 2048, 8, 128), dtype=jnp.float32) == 256
    assert pick(shape=(8, 96, 16, 128)) == 32               # the block divides max_len
    assert pick(shape=(8, 100, 16, 128)) is None


def _decode_program_text(cfg, L, monkeypatch, mode="interpret"):
    """The jaxpr of one decode-mode call of an attention layer."""
    monkeypatch.setenv("KFT_PALLAS", mode)
    model = TransformerLM(cfg)
    toks = jnp.zeros((2, L), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), toks[:, :1])
    return str(jax.make_jaxpr(
        lambda v, t: model.apply(v, t, mutable=["cache"]))(variables, toks))


def test_the_model_step_takes_the_kernel_where_selection_says(monkeypatch):
    """`Attention.__call__` hands the stored leaves and the cursors to
    `decode_attention`: a decode or verify step traces the kernel, a
    prefill bucket, an int8 cache, attention="full" (what a cache on a
    mesh is served with), a config on a mesh and a backend without the
    kernels trace the einsum."""
    cfg = TransformerConfig(vocab_size=32, d_model=1024, n_layers=1, n_heads=8,
                            d_ff=32, max_len=64, rope=True, decode=True,
                            dtype=jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    text = lambda c=cfg, L=1, **kw: _decode_program_text(c, L, monkeypatch, **kw)  # noqa: E731
    assert da.KERNEL_NAME in text()
    assert da.KERNEL_NAME in text(L=4)
    assert da.KERNEL_NAME not in text(L=16)
    assert da.KERNEL_NAME not in text(
        dataclasses.replace(cfg, kv_cache_dtype="int8"))
    assert da.KERNEL_NAME not in text(dataclasses.replace(cfg, attention="full"))
    assert da.KERNEL_NAME not in text(dataclasses.replace(cfg, mesh=mesh))
    assert da.KERNEL_NAME not in text(mode="off")


def test_serving_over_a_mesh_keeps_the_einsum_and_places_no_constraint(
        monkeypatch):
    """`ServingEngine(mesh=...)` and `generate(mesh=...)` ask for the plain
    einsum (attention="full") and, as before the kernel, give the decode
    model no mesh: traced inside a caller's `nn.logical_axis_rules` the
    program carries no sharding constraint, and is the program traced
    outside one.  Without a mesh the same engine traces the kernel."""
    from kungfu_tpu.models import transformer as T
    from kungfu_tpu.parallel.sharding import DEFAULT_RULES
    from kungfu_tpu.serving import ServingEngine

    monkeypatch.setenv("KFT_PALLAS", "interpret")
    cfg = TransformerConfig(vocab_size=32, d_model=1024, n_layers=1, n_heads=8,
                            d_ff=32, max_len=512, rope=True, dtype=jnp.float32)
    params = nn.meta.unbox(TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    toks = jnp.zeros((2, 1), jnp.int32)
    traced = lambda eng: str(jax.make_jaxpr(eng._decode)(  # noqa: E731
        eng.params, eng.cache, {}, toks))

    with nn.logical_axis_rules(DEFAULT_RULES):
        sharded = ServingEngine(cfg, params, slots=2, mesh=mesh)
        under_rules = traced(sharded)
    assert (sharded.dcfg.attention, sharded.dcfg.mesh) == ("full", None)
    assert da.KERNEL_NAME not in under_rules
    assert "sharding_constraint" not in under_rules
    assert traced(ServingEngine(cfg, params, slots=2, mesh=mesh)) == under_rules
    assert sharded._attn_block == {1: None}  # counts the whole cache

    single = ServingEngine(cfg, params, slots=2)
    assert (single.dcfg.attention, single.dcfg.mesh) == ("auto", None)
    assert da.KERNEL_NAME in traced(single)
    assert single._attn_block == {1: 256}

    seen = []
    monkeypatch.setattr(T, "_generate_compiled", lambda dcfg, *a: seen.append(
        dcfg) or (lambda params, cache, prompt, rng: prompt))
    prompt = jnp.zeros((2, 4), jnp.int32)
    T.generate(cfg, params, prompt, 4)
    T.generate(cfg, params, prompt, 4, mesh=mesh)
    assert [(c.attention, c.mesh) for c in seen] == [("auto", None), ("full", None)]


@pytest.mark.parametrize("kind", ["per_head", "latent", "latent_shortcut"])
def test_the_decode_program_builds_the_visit_list_once(kind, monkeypatch):
    """`TransformerLM` builds the step's walk from the first attention
    layer's cursors and hands it to every layer: the engine's `_decode` of
    a two-layer model holds one cumsum (the list's) and one kernel call a
    layer (a sublayer under the shortcut block), where a list a layer would
    hold as many cumsums as calls: each layer's cursors are a buffer of its
    own, and no compiler merges what is computed from different buffers."""
    from kungfu_tpu.serving import ServingEngine

    monkeypatch.setenv("KFT_PALLAS", "interpret")
    fields = dict(vocab_size=32, n_layers=2, d_ff=32, max_len=64, rope=True,
                  dtype=jnp.float32)
    if kind == "per_head":
        cfg = TransformerConfig(d_model=1024, n_heads=8, **fields)
        name, calls = da.KERNEL_NAME, 2
    else:
        shortcut = kind == "latent_shortcut"
        cfg = TransformerConfig(
            d_model=64, n_heads=4, norm="rms", kv_lora_rank=32, q_lora_rank=24,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            **(dict(block="shortcut_moe", n_experts=4, experts_per_token=2,
                    moe_every=1, d_ff_expert=16) if shortcut else {}),
            **fields)
        name, calls = da.MLA_KERNEL_NAME, 4 if shortcut else 2
    params = nn.meta.unbox(jax.eval_shape(
        TransformerLM(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 4), jnp.int32))["params"])
    eng = ServingEngine(cfg, params, slots=2)
    text = str(jax.make_jaxpr(eng._decode)(
        eng.params, eng.cache, eng._dev_counters, jnp.zeros((2, 1), jnp.int32),
        eng._no_prev))
    assert text.count(f"name={name}") == calls
    # over the two slots (the expert layer's own run over its experts)
    assert text.count("i32[2] = cumsum[") == 1
    # a layer called by itself, without the model's list, builds its own
    own = str(jax.make_jaxpr(lambda q, c, p: da.mla_decode_attention(
        q, c, p, 32, 0.2, live=p[:, 0] > 0))(
        jnp.zeros((2, 1, 4, 40)), jnp.zeros((2, 64, 40)),
        jnp.zeros((2, 1), jnp.int32)))
    assert own.count("i32[2] = cumsum[") == 1


def test_decode_step_logits_equal_the_einsums(monkeypatch):
    """One model, the same cache: a prefill through the einsum, then decode
    steps through the kernel, against the same steps through the einsum."""
    cfg = TransformerConfig(vocab_size=32, d_model=1024, n_layers=2, n_heads=8,
                            d_ff=32, max_len=512, rope=True, decode=True,
                            dtype=jnp.float32)
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 300), 1, 32)
    variables = nn.meta.unbox(model.init(jax.random.PRNGKey(0), toks[:, :1]))

    def run(mode):
        monkeypatch.setenv("KFT_PALLAS", mode)
        params, cache = variables["params"], variables["cache"]
        _, st = model.apply({"params": params, "cache": cache},
                            toks[:, :290], mutable=["cache"])
        out = []
        for i in range(290, 294):  # the cursor passes the first block's end
            logits, st = model.apply({"params": params, "cache": st["cache"]},
                                     toks[:, i:i + 1], mutable=["cache"])
            out.append(np.asarray(logits))
        return np.stack(out)

    np.testing.assert_allclose(run("interpret"), run("off"), rtol=2e-4, atol=2e-4)


# -- latent attention: one shared row a token ------------------------------------------

RANK, ROPE = 32, 8


def _latent_case(cursors, L, H, dtype, seed=0):
    """(q, cache, positions, the cache with NaN beyond each slot's last
    position) for q [B, L, H, RANK + ROPE] against [B, MAX_LEN, RANK + ROPE]."""
    B, W = len(cursors), RANK + ROPE
    kq, kc = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (B, L, H, W), jnp.float32).astype(dtype)
    cache = jax.random.normal(kc, (B, MAX_LEN, W), jnp.float32).astype(dtype)
    idx0 = jnp.minimum(jnp.asarray(cursors, jnp.int32), MAX_LEN - L)
    pos = idx0[:, None] + jnp.arange(L)[None, :]
    dead = (jnp.arange(MAX_LEN)[None, :] > pos[:, -1:])[:, :, None]
    return q, cache, pos, jnp.where(dead, jnp.nan, cache)


@functools.lru_cache(maxsize=None)
def _latent_interpreted():
    return jax.jit(lambda q, c, pos: da._mla_attn_pallas(
        q, c, pos, RANK, 0.25, BLOCK, True,
        da.slot_walk(pos, None, BLOCK, MAX_LEN)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("L", [1, 4])
@pytest.mark.parametrize("cursors", list(CURSORS), ids=list(CURSORS))
def test_latent_kernel_matches_the_einsum_over_ragged_cursors(cursors, L, dtype):
    """Every head against the same rows, each row key and value at once;
    NaN in every row beyond a cursor must not reach the result."""
    q, cache, pos, cache_nan = _latent_case(CURSORS[cursors], L, 4, dtype)
    want = mla_decode_attention_reference(q, cache, pos, RANK, 0.25)
    got = _latent_interpreted()(q, cache_nan, pos)
    assert got.shape == (8, L, 4, RANK) and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    tol = 2e-5 if dtype == jnp.float32 else 2e-2  # as the per-head kernel's
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_selection_for_a_latent_leaf():
    pick = lambda rows=1, shape=(32, 4096, 576), dtype=jnp.bfloat16, mode=True: (  # noqa: E731
        da.kernel_block(rows, shape, dtype, interpret=mode))
    assert pick() == 512                          # 512 x 576 bf16: under 1 MiB
    assert pick(rows=da.MAX_QUERY_ROWS) == 512
    assert pick(rows=16) is None                  # a prefill bucket materialises
    assert pick(dtype=jnp.float32) == 256
    assert pick(mode=None) is None
    assert pick(shape=(4, 64, 40)) == 64
    assert pick(shape=(4, 100, 40)) is None


@pytest.mark.parametrize("L", [1, 4], ids=["decode", "verify"])
def test_latent_kernel_lowers_for_tpu_at_the_cells_shape(L):
    """[32, L, 16, 576] against the [32, 4096, 576] leaf of
    `serve-longcat-reason-r80`, through the Pallas -> Mosaic lowering."""
    q = jax.ShapeDtypeStruct((32, L, 16, 576), jnp.bfloat16)
    leaf = jax.ShapeDtypeStruct((32, 4096, 576), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((32, L), jnp.int32)

    def f(q, c, p):
        return da.mla_decode_attention(q, c, p, 512, 192 ** -0.5,
                                       interpret=False)

    exp = jax.export.export(jax.jit(f), platforms=["tpu"])(q, leaf, pos)
    assert da.MLA_KERNEL_NAME in exp.mlir_module()


def test_the_latent_decode_program_reads_the_donated_cache_where_it_lies(
        v5e_chip, monkeypatch):
    """`ServingEngine._decode` of the latent-attention block at the cell's
    attention widths (one layer, 32 slots of 4,096 rows), compiled for the
    chip: the cache is one [32, 4096, 576] bf16 leaf a sublayer, one kernel
    call a sublayer, NO per-head K or V of cached rows anywhere in the
    program, no copy, transpose or convert of a whole leaf ahead of the
    kernel (the chip lays the leaf out feature-major and the kernel takes it
    so: `ops/decode_attn.py`), and the donated cache aliases the output."""
    from kungfu_tpu import compat
    from kungfu_tpu.ops.gmm import KERNEL_NAME as GMM
    from kungfu_tpu.serving import ServingEngine

    monkeypatch.setattr(compat, "pallas_mode", lambda interpret=None: "compiled")
    cfg = TransformerConfig(
        vocab_size=512, d_model=6144, n_layers=1, n_heads=16, d_ff=256,
        max_len=4096, rope=True, rope_theta=1e7, attention="full",
        dtype=jnp.bfloat16, ffn="swiglu", norm="rms", block="shortcut_moe",
        kv_lora_rank=512, q_lora_rank=1536, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, mla_scale_q_lora=True,
        mla_scale_kv_lora=True, d_ff_expert=256, n_experts=512,
        n_zero_experts=256, experts_per_token=12, moe_every=1,
        routed_scaling_factor=6.0, router_bias=True, experts_held=8)
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip), tree)
    params = described(nn.meta.unbox(jax.eval_shape(
        TransformerLM(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 1), jnp.int32))["params"]))
    eng = ServingEngine(cfg, params, slots=32)
    leaves = {path[-1].key: leaf for path, leaf in
              jax.tree_util.tree_leaves_with_path(eng.cache)}
    assert set(leaves) == {"cached_latent", "idx", "overflowed"}
    assert leaves["cached_latent"].shape == (32, 4096, 576)
    assert leaves["cached_latent"].dtype == jnp.bfloat16
    assert eng._attn_block == {1: 512}
    compiled = eng._decode.lower(
        described(eng.params), described(eng.cache),
        described(eng._dev_counters),
        jax.ShapeDtypeStruct((32, 1), jnp.int32, sharding=v5e_chip)).compile()
    text = compiled.as_text()
    calls = lambda kernel: re.findall(  # noqa: E731
        rf"= \S+ custom-call\([^\n]*{kernel}", text)
    assert len(calls(da.MLA_KERNEL_NAME)) == 2 * cfg.n_layers
    assert len(calls("kft_decode_attn")) == 0
    assert len(calls(GMM)) == 3 * cfg.n_layers
    assert "[32,4096,16," not in text          # no K or V of a cached row
    # the step's 32 new rows a sublayer: no loop over the slots, and not
    # the scatter that would copy the leaf to a row-major layout and back
    assert _scatter_loops(text) == []
    assert _whole_leaves_moved(text, "32,(?:4096,576|576,4096)") == []
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(eng.cache))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 4


# -- attention over selected blocks (`kft_sparse_decode_attn`) -------------------------

S_BLOCK, S_STRIDE, S_TOPK, S_LEN = 8, 2, 6, 128
S_CHOICE = dict(block=S_BLOCK, stride=S_STRIDE, topk=S_TOPK, init_blocks=1,
                window=2 * S_BLOCK)


def _sparse_case(B, L, H, Hkv, D, dtype, cursors, seed=0, past_the_leaf=False):
    """(q, K, V, compressed keys, positions): rows [B, S_LEN, Hkv x D] with
    NaN in K and V beyond each slot's last position (a cursor is pulled
    back so that the call ends inside the leaf unless `past_the_leaf`)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = (3 * jax.random.normal(ks[0], (B, L, H, D), jnp.float32)).astype(dtype)
    ck = jax.random.normal(ks[1], (B, S_LEN, Hkv * D), jnp.float32).astype(dtype)
    cv = jax.random.normal(ks[2], (B, S_LEN, Hkv * D), jnp.float32).astype(dtype)
    kc = _compressed_keys(ck, S_STRIDE).astype(dtype)
    idx0 = jnp.asarray(cursors, jnp.int32)
    if not past_the_leaf:
        idx0 = jnp.minimum(idx0, S_LEN - L)
    pos = idx0[:, None] + jnp.arange(L)[None, :]
    dead = (jnp.arange(S_LEN)[None, :] > pos[:, -1:])[:, :, None]
    return (q, jnp.where(dead, jnp.nan, ck), jnp.where(dead, jnp.nan, cv), kc,
            pos, ck, cv)


def _plain(q, ck, cv, pos, Hkv, D):
    B = q.shape[0]
    return decode_attention_reference(
        q, ck.reshape(B, S_LEN, Hkv, D), cv.reshape(B, S_LEN, Hkv, D), pos)


def test_the_selector_forces_the_first_block_and_the_window_then_ranks():
    q, _, _, kc, pos, _, _ = _sparse_case(3, 1, 8, 2, 16, jnp.float32,
                                          [100, 37, 0])
    ids, n = da.select_blocks(q, kc, pos, **S_CHOICE)
    assert ids.shape == (3, 1, 2, S_TOPK) and n.shape == (3, 1, 2)
    ids, n = np.asarray(ids), np.asarray(n)
    # position 100 is in block 12: blocks 0, 11, 12 always, three by score
    assert n[0].tolist() == [[S_TOPK, S_TOPK]]
    for k in range(2):
        assert set(ids[0, 0, k, :3]) == {0, 11, 12}
        assert all(0 < b < 11 for b in ids[0, 0, k, 3:])
        assert len(set(ids[0, 0, k])) == S_TOPK
    # position 37 is in block 4: five blocks reachable, every one chosen
    assert n[1].tolist() == [[5, 5]]
    assert all(set(ids[1, 0, k, :5]) == set(range(5)) for k in range(2))
    # a slot at position 0 reads block 0 alone
    assert n[2].tolist() == [[1, 1]] and (ids[2, 0, :, 0] == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("cursors", [[100, 37, 0, 127], [127] * 4, [0] * 4,
                                     [47, 48, 49, 7]],
                         ids=["mix", "last_row", "zero", "block_edges"])
def test_sparse_kernel_matches_the_gather_over_ragged_cursors(cursors, dtype):
    q, ck, cv, kc, pos, ck0, cv0 = _sparse_case(4, 1, 8, 2, 128, dtype, cursors)
    ids, n = da.select_blocks(q, kc, pos, **S_CHOICE)
    want = da.sparse_decode_attention_reference(q, ck0, cv0, ids, n, pos, S_BLOCK)
    got = da.sparse_decode_attention(q, ck, cv, ids, n, pos, S_BLOCK,
                                     interpret=True)
    assert got.shape == (4, 1, 8, 128) and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_sparse_reference_takes_a_verify_steps_rows_each_with_its_own_list():
    q, _, _, kc, pos, ck, cv = _sparse_case(2, 4, 8, 2, 16, jnp.float32, [90, 30])
    ids, n = da.select_blocks(q, kc, pos, **S_CHOICE)
    whole = da.sparse_decode_attention(q, ck, cv, ids, n, pos, S_BLOCK,
                                       interpret=True)   # 4 rows: the gather
    for l in range(4):
        one = da.sparse_decode_attention_reference(
            q[:, l:l + 1], ck, cv, ids[:, l:l + 1], n[:, l:l + 1],
            pos[:, l:l + 1], S_BLOCK)
        np.testing.assert_allclose(np.asarray(whole[:, l]), np.asarray(one[:, 0]),
                                   rtol=1e-6, atol=1e-6)


def test_at_most_topk_blocks_reachable_is_plain_attention():
    """Rows 0 .. topk x block - 1 attend every block at or before them: the
    prefill form there equals itself with nothing to drop bit for bit, and
    the plain causal einsum to rounding; later rows differ from it."""
    B, L, H, Hkv, D = 2, 112, 8, 2, 16
    q, _, _, kc, _, ck, cv = _sparse_case(B, L, H, Hkv, D, jnp.float32, [0, 0])
    pos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    got = da.sparse_prefill_attention(q, ck, cv, kc, pos, **S_CHOICE)
    every = da.sparse_prefill_attention(
        q, ck, cv, kc, pos, **dict(S_CHOICE, topk=S_LEN // S_BLOCK))
    plain = _plain(q, ck, cv, pos, Hkv, D)
    reach = S_TOPK * S_BLOCK
    np.testing.assert_array_equal(np.asarray(got[:, :reach]),
                                  np.asarray(every[:, :reach]))
    np.testing.assert_allclose(np.asarray(got[:, :reach]),
                               np.asarray(plain[:, :reach]), rtol=2e-6, atol=2e-6)
    assert float(jnp.abs(got[:, reach:] - plain[:, reach:]).max()) > 0.1


def test_the_prefill_form_is_the_decode_form_row_by_row():
    B, L, H, Hkv, D = 2, 120, 8, 2, 16
    q, _, _, kc, _, ck, cv = _sparse_case(B, L, H, Hkv, D, jnp.float32, [0, 0])
    pos = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    got = da.sparse_prefill_attention(q, ck, cv, kc, pos, **S_CHOICE)
    ids, n = da.select_blocks(q, kc, pos, **S_CHOICE)
    want = da.sparse_decode_attention_reference(q, ck, cv, ids, n, pos, S_BLOCK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sparse_selection_from_what_a_call_shows():
    takes = da.sparse_kernel_takes
    assert takes(1, 128, jnp.bfloat16, interpret=True)
    assert takes(1, 128, jnp.float32, interpret=False)
    assert not takes(4, 128, jnp.bfloat16, interpret=True)    # a verify step
    assert not takes(1, 64, jnp.bfloat16, interpret=False)    # half a lane tile
    assert takes(1, 64, jnp.bfloat16, interpret=True)
    assert not takes(1, 128, jnp.int8, interpret=True)
    assert not takes(1, 128, jnp.bfloat16)                    # no kernels here


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_sparse_kernel_lowers_for_tpu_at_the_cells_shape(dtype):
    """`jax.export` runs the Pallas -> Mosaic lowering on this host: 16
    slots, 32 query heads on 2 KV heads of 128, 64 blocks of 64 of 12,288
    rows."""
    S = jax.ShapeDtypeStruct
    rows = S((16, 12288, 256), dtype)

    def f(q, k, v, ids, n, pos):
        return da.sparse_decode_attention(q, k, v, ids, n, pos, 64,
                                          interpret=False)

    exp = jax.export.export(jax.jit(f), platforms=["tpu"])(
        S((16, 1, 32, 128), dtype), rows, rows, S((16, 1, 2, 64), jnp.int32),
        S((16, 1, 2), jnp.int32), S((16, 1), jnp.int32))
    assert da.SPARSE_KERNEL_NAME in exp.mlir_module()


def test_the_sala_decode_program_reads_the_donated_cache_where_it_lies(
        v5e_chip, monkeypatch):
    """`ServingEngine._decode` of a block-selected layer and a lightning
    layer at the published widths, compiled for the chip: one
    `kft_sparse_decode_attn` and one `kft_lightning_attn`, no
    `kft_decode_attn`, no copy, transpose or convert of a whole K, V or
    state leaf ahead of them, and the donated cache aliases the output."""
    from kungfu_tpu import compat
    from kungfu_tpu.ops.lightning_attn import KERNEL_NAME as LIGHTNING
    from kungfu_tpu.serving import ServingEngine

    monkeypatch.setattr(compat, "pallas_mode", lambda interpret=None: "compiled")
    slots = 16
    cfg = TransformerConfig(
        vocab_size=512, d_model=4096, n_layers=2, n_heads=32, n_kv_heads=2,
        d_ff=256, max_len=12288, rope=True, attn_use_rope=False, norm="rms",
        ffn="swiglu", dtype=jnp.bfloat16,
        mixer_types=["minicpm4", "lightning-attn"], scale_emb=12.0,
        scale_depth=1.4, scale_depth_layers=32, dim_model_base=256)
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip), tree)
    params = described(nn.meta.unbox(jax.eval_shape(
        TransformerLM(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 1), jnp.int32))["params"]))
    eng = ServingEngine(cfg, params, slots=slots)
    compiled = eng._decode.lower(
        described(eng.params), described(eng.cache),
        described(eng._dev_counters),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=v5e_chip),
        described(eng._no_prev)).compile()
    text = compiled.as_text()
    calls = lambda kernel: re.findall(  # noqa: E731
        rf" custom-call\([^\n]*{kernel}", text)   # (o, state): a tuple's type
    assert len(calls(da.SPARSE_KERNEL_NAME)) == 1
    assert len(calls(LIGHTNING)) == 1
    assert calls("kft_decode_attn") == []
    assert _whole_leaves_moved(text, "16,12288,256") == []
    assert _whole_leaves_moved(text, "16,32,128,128") == []
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(eng.cache))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    # the compiler's own staging of weights (two 4096 x 4096 kernels in
    # flight) is all: less than ONE K leaf
    assert mem.temp_size_in_bytes < slots * 12288 * 256 * 2


# -- a prefill's attention over selected blocks (`kft_sparse_prefill_attn`) ------------


PREFILL_CASES = {
    # B, L, H, Hkv, D, cursors
    "cold": (2, 112, 8, 2, 16, [0, 0]),
    "warm_cursors": (2, 40, 8, 2, 16, [60, 17]),
    "ragged_last_tile": (1, 100, 8, 2, 16, [20]),   # 100 rows: tiles of 128
    "several_tiles": (1, 128, 4, 2, 16, [0]),       # four query tiles of 32
    "past_the_leaf": (2, 40, 8, 2, 16, [100, 90]),  # positions 128.. are padding
    "one_query_head_a_kv_head": (2, 64, 2, 2, 16, [0, 30]),
    "sixteen_query_heads_a_kv_head": (1, 64, 32, 2, 128, [50]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(PREFILL_CASES))
def test_sparse_prefill_kernel_matches_the_xla_chunks(case, dtype, monkeypatch):
    """The kernel's body in the interpreter against the XLA chunks on the
    rows as stored, with NaN in K and V beyond each slot's last position: a
    cold call and warm cursors, a last tile of padding, rows past the leaf,
    one and sixteen query heads a KV head, both dtypes."""
    B, L, H, Hkv, D, cursors = PREFILL_CASES[case]
    if case == "several_tiles":
        monkeypatch.setattr(da, "_SPARSE_PREFILL_TILES", (32, 64))
    q, ck, cv, kc, pos, ck0, cv0 = _sparse_case(
        B, L, H, Hkv, D, dtype, cursors, past_the_leaf=True)
    want = da.sparse_prefill_attention_reference(q, ck0, cv0, kc, pos, **S_CHOICE)
    got = da.sparse_prefill_attention(q, ck, cv, kc, pos, interpret=True,
                                      **S_CHOICE)
    assert got.shape == (B, L, H, D) and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_sparse_prefill_kernel_takes_padding_rows_at_position_0():
    """Rows a caller pads a call with (position 0, as the chunks pad their
    own last chunk) attend row 0 alone and move no other row."""
    B, L, H, Hkv, D = 2, 48, 8, 2, 16
    q, ck, cv, kc, pos, ck0, cv0 = _sparse_case(B, L, H, Hkv, D, jnp.float32,
                                                [40, 0])
    pos = jnp.where(jnp.arange(L) < 37, pos, 0)
    want = da.sparse_prefill_attention_reference(q, ck0, cv0, kc, pos, **S_CHOICE)
    got = da.sparse_prefill_attention(q, ck, cv, kc, pos, interpret=True,
                                      **S_CHOICE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    v0 = cv0.reshape(B, S_LEN, Hkv, D)[:, 0]            # row 0, a KV head
    np.testing.assert_allclose(
        np.asarray(got[:, 37:]).reshape(B, L - 37, Hkv, H // Hkv, D),
        np.broadcast_to(np.asarray(v0)[:, None, :, None],
                        (B, L - 37, Hkv, H // Hkv, D)), rtol=1e-6, atol=1e-6)


def test_sparse_prefill_kernel_under_topk_blocks_is_plain_attention():
    """Rows 0 .. topk x block - 1 have at most topk blocks at or before
    them: the kernel there is the plain causal einsum."""
    B, L, H, Hkv, D = 2, S_TOPK * S_BLOCK, 8, 2, 16
    q, ck, cv, kc, pos, ck0, cv0 = _sparse_case(B, L, H, Hkv, D, jnp.float32,
                                                [0, 0])
    got = da.sparse_prefill_attention(q, ck, cv, kc, pos, interpret=True,
                                      **S_CHOICE)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_plain(q, ck0, cv0, pos, Hkv, D)),
                               rtol=1e-5, atol=1e-5)


def test_sparse_prefill_kernel_reads_the_bitmap():
    """A block cleared in one (row, KV head)'s bitmap changes that row's
    output for that KV head's query heads and no other number: the kernel
    attends what the bitmap says, not every row at or before the query."""
    B, L, H, Hkv, D = 1, 96, 8, 2, 16
    q, _, _, kc, pos, ck, cv = _sparse_case(B, L, H, Hkv, D, jnp.float32, [0])
    hit = da._chosen_bitmap(q, kc, pos, **S_CHOICE)     # [B, L, Hkv, nb]
    run = functools.partial(da._sparse_prefill_pallas, q, ck, cv,
                            block=S_BLOCK, tile_q=32, tile_k=64, interpret=True,
                            vmem_bytes=64 << 20)
    whole = run(hit, pos)
    np.testing.assert_allclose(
        np.asarray(whole), np.asarray(da.sparse_prefill_attention_reference(
            q, ck, cv, kc, pos, **S_CHOICE)), rtol=1e-5, atol=1e-5)
    row, head = 90, 1
    assert bool(hit[0, row, head, 0])                   # block 0 is forced
    less = run(hit.at[0, row, head, 0].set(False), pos)
    moved = np.abs(np.asarray(less - whole)).max(-1)[0]         # [L, H]
    G = H // Hkv
    assert (moved[row, head * G:(head + 1) * G] > 1e-3).all()
    moved[row, head * G:(head + 1) * G] = 0
    assert not moved.any()


def test_two_sparse_layers_of_one_shape_trace_the_prefill_kernel_once():
    """Two layers' calls of one shape in one program (after
    test_flash_cached.py): the kernel's body is traced once and the module
    lowered for a TPU holds one Mosaic kernel, called twice."""
    choice = dict(block=64, stride=16, topk=2, init_blocks=1, window=64)
    S = jax.ShapeDtypeStruct
    rows = S((1, 256, 256), jnp.bfloat16)

    def two(q, k, v, kc, pos):
        for _ in range(2):
            q = da.sparse_prefill_attention(
                q, k, v, kc, pos, interpret=False, **choice).astype(q.dtype)
        return q

    txt = jax.export.export(jax.jit(two), platforms=["tpu"])(
        S((1, 256, 4, 128), jnp.bfloat16), rows, rows,
        S((1, 16, 256), jnp.bfloat16), S((1, 256), jnp.int32)).mlir_module()
    assert txt.count("stablehlo.custom_call @tpu_custom_call") == 1
    assert re.findall(r'kernel_name = "([^"]+)"', txt) == [
        da.SPARSE_PREFILL_KERNEL_NAME]


def test_sparse_prefill_selection_from_what_a_call_shows():
    tiles = da.sparse_prefill_tiles
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert tiles(12288, 12288, 128, 64, bf16, interpret=False) == (128, 1024)
    assert tiles(12288, 12288, 128, 64, f32, interpret=False) == (128, 1024)
    assert tiles(16, 12288, 128, 64, bf16, interpret=False) == (32, 1024)
    assert tiles(100, 12288, 128, 64, bf16, interpret=False) == (128, 1024)
    assert tiles(1, 12288, 128, 64, bf16, interpret=False) is None   # a decode step
    assert tiles(da.MAX_QUERY_ROWS, 12288, 128, 64, bf16, interpret=False) is None
    assert tiles(4096, 12288, 64, 64, bf16, interpret=False) is None  # half a lane tile
    assert tiles(4096, 12288, 64, 64, bf16, interpret=True) == (128, 1024)
    assert tiles(4096, 12288, 128, 64, jnp.int8, interpret=True) is None
    assert tiles(64, 64, 128, 8, f32, interpret=True) == (64, 64)
    assert tiles(64, 64, 128, 8, f32, interpret=False) is None  # no whole lane tile of keys
    assert tiles(4096, 12288, 128, 96, bf16, interpret=True) is None  # no tile of whole blocks
    assert tiles(4096, 12288, 128, 64, bf16) is None                  # no kernels here


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("rows", [16, 12288], ids=["bucket16", "bucket12288"])
def test_sparse_prefill_kernel_compiles_for_the_chip_at_the_cells_shape(
        v5e_chip, rows, dtype):
    """One request's prefill bucket at the published widths (32 query heads
    on 2 KV heads of 128, 192 blocks of 64 of 12,288 rows), compiled for the
    chip: the kernel is there, and no [.., 128, 12288] float32 score chunk
    (201 MB) is among the program's temporaries: what is, is the output's
    and q's change of layout."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)  # noqa: E731
    leaf = S((1, 12288, 256), dtype)
    choice = dict(block=64, stride=16, topk=64, init_blocks=1, window=2048)

    def f(q, k, v, kc, pos):
        return da.sparse_prefill_attention(q, k, v, kc, pos, interpret=False,
                                           **choice)

    compiled = jax.jit(f).lower(
        S((1, rows, 32, 128), dtype), leaf, leaf, S((1, 768, 256), dtype),
        S((1, rows), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall(
        rf" custom-call\([^\n]*{da.SPARSE_PREFILL_KERNEL_NAME}", text)) == 1
    assert not re.search(r"f32\[[0-9,]*128,12288\]", text)
    out_bytes = rows * 32 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * out_bytes + (64 << 20)


def test_the_benchmark_buckets_the_prefill_kernel_by_its_name():
    """`sparse_prefill_attn_share` finds the kernel by the name the program
    gives it, and the decode kernel's metric (a substring match over the
    whole capture) cannot take its events."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "sparse_prefill_attn_share.json")) as f:
        reader = json.load(f)
    assert reader["bucket"]["match"] == da.SPARSE_PREFILL_KERNEL_NAME
    assert reader["kind"] == "trace" and reader["reduce"] == "share_of_busy"
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "sparse_attn_share.json")) as f:
        assert json.load(f)["bucket"]["match"] not in da.SPARSE_PREFILL_KERNEL_NAME
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "sparse_prefill_attn_share"]
    assert entry == [{
        "name": "sparse_prefill_attn_share", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "tpot_p50_ms",
        "workloads": ["serve-sala-docs-r80"]}]
