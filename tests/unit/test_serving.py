"""Elastic inference serving subsystem (kungfu_tpu/serving/).

Fast tier: admission-queue semantics (FIFO, deadlines, backpressure,
re-queue-to-front, the requeue-vs-expiry race), slot ledger,
continuous-batching engine parity against the full-sequence forward
(greedy tokens identical under interleaved admissions and slot reuse),
warm-resume determinism, int8 KV serving, the serving-v2 multipliers —
radix prefix cache (parity, radix semantics, LRU eviction, weight-reload
invalidation), speculative decoding (bit-exact parity, ONE extra compiled
signature, acceptance collapse), disaggregation (KV ship round trip,
prefill_only/submit_prefilled parity, tiered documents, the tiered
autoscaler) — the crash_serve chaos grammar incl. tier targeting, the
config server's /health endpoint, and the queue-depth autoscaler against a
real config server.  Slow tier (`faults` + `slow`): the multi-process CPU
drills — a serving rank killed mid-stream (monolithic and per-tier), zero
dropped requests, buddy-weight rejoin, scale-down/up commits.
"""
import dataclasses
import functools
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

from kungfu_tpu.models.transformer import TransformerConfig, TransformerLM, generate
from kungfu_tpu.serving import (
    AdmissionQueue,
    BackpressureError,
    PrefixCache,
    Request,
    ServingEngine,
    SlotManager,
    SpecDecoder,
    default_buckets,
)
from kungfu_tpu.serving.engine import CARRY, FREE
from kungfu_tpu.serving.slots import set_cursors, write_slot

pytestmark = pytest.mark.serving


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
                max_len=48, rope=True, n_kv_heads=2, attention="full",
                dtype=jnp.float32)
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = _cfg()
    model = TransformerLM(cfg)
    probe = jnp.zeros((1, 4), jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), probe)["params"])
    return cfg, model, params


# -- request/queue ---------------------------------------------------------------------


class TestAdmissionQueue:
    def test_fifo_and_depth(self):
        q = AdmissionQueue(capacity=4)
        reqs = [Request(prompt=(1, 2), max_new_tokens=1) for _ in range(3)]
        assert all(q.put(r) for r in reqs)
        assert q.depth() == 3
        assert [q.pop() for _ in range(3)] == reqs
        assert q.pop(timeout_s=0.01) is None

    def test_backpressure_at_capacity(self):
        q = AdmissionQueue(capacity=2)
        assert q.put(Request(prompt=(1,), max_new_tokens=1))
        assert q.put(Request(prompt=(1,), max_new_tokens=1))
        assert not q.put(Request(prompt=(1,), max_new_tokens=1))

    def test_requeue_jumps_the_line_and_never_drops(self):
        q = AdmissionQueue(capacity=1)
        first = Request(prompt=(1,), max_new_tokens=1)
        assert q.put(first)
        victim = Request(prompt=(2,), max_new_tokens=1)
        q.requeue(victim)  # over capacity on purpose: re-queues cannot drop
        assert q.depth() == 2
        assert q.pop() is victim
        assert victim.requeues == 1
        assert q.pop() is first

    def test_expired_swept_to_rejection_not_wedged(self):
        q = AdmissionQueue()
        dead = Request(prompt=(1,), max_new_tokens=1, deadline_s=0.01)
        live = Request(prompt=(2,), max_new_tokens=1)
        q.put(dead)
        q.put(live)
        time.sleep(0.03)
        assert q.pop() is live  # the expired one is skipped, not returned
        swept = q.drain_expired()
        assert swept == [dead]
        assert q.drain_expired() == []

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_requeue_racing_expiry_never_reorders_or_double_serves(self, seed):
        """Property test: requeue-to-front threads racing concurrent
        poppers (whose pops sweep the deadline-expired aside) must never
        (a) hand the same request to two dispatchers, (b) lose a live
        request — everything either serves or comes back as an explicit
        expiry — or (c) wedge a re-queued victim (every victim re-serves
        and its requeue count bumps exactly once)."""
        rng = np.random.default_rng(seed)
        q = AdmissionQueue(capacity=512)
        n = 60
        reqs = [Request(prompt=(i + 1,), max_new_tokens=1,
                        deadline_s=(0.02 if rng.random() < 0.3 else 0.0))
                for i in range(n)]
        victims = [r for r in reqs if rng.random() < 0.25
                   and not r.deadline_s]
        for r in reqs:
            assert q.put(r)
        served = []
        expired_seen = []
        served_lock = threading.Lock()
        stop = threading.Event()

        def popper():
            while not stop.is_set() or q.depth():
                r = q.pop(timeout_s=0.01)
                swept = q.drain_expired()
                with served_lock:
                    expired_seen.extend(swept)
                if r is not None:
                    with served_lock:
                        served.append(r)
                    time.sleep(rng.random() * 0.003)

        def requeuer():
            for v in victims:
                # a victim re-queues only once it was popped (a dispatch
                # failed) — mirror that: wait until it shows up served,
                # then push it back to the front exactly once
                while not stop.is_set():
                    with served_lock:
                        if v in served:
                            served.remove(v)
                            break
                    time.sleep(0.001)
                q.requeue(v)

        threads = [threading.Thread(target=popper) for _ in range(3)]
        rt = threading.Thread(target=requeuer)
        for t in threads:
            t.start()
        rt.start()
        rt.join(timeout=20)
        time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not rt.is_alive(), "requeuer wedged: a victim never re-served"
        # (a) no double-serves (a swept expiry is a rejection, not a serve)
        ids = [r.req_id for r in served]
        assert len(ids) == len(set(ids)), "a request was served twice"
        assert not (set(ids) & {r.req_id for r in expired_seen})
        # (b) nothing lost: every request either served or swept expired
        swept = {r.req_id for r in expired_seen} | {
            r.req_id for r in q.drain_expired()}
        all_out = set(ids) | swept
        for v in victims:  # requeued victims were removed from `served`
            all_out.add(v.req_id)
        assert all_out == {r.req_id for r in reqs}, "a request vanished"
        # requeue bookkeeping: every victim's requeue count bumped once
        assert all(v.requeues == 1 for v in victims)


class TestSlotManager:
    def test_allocate_release_reuse(self):
        sm = SlotManager(2)
        a = Request(prompt=(1,), max_new_tokens=1)
        b = Request(prompt=(2,), max_new_tokens=1)
        sa, sb = sm.allocate(a), sm.allocate(b)
        assert {sa, sb} == {0, 1}
        assert sm.allocate(Request(prompt=(3,), max_new_tokens=1)) is None
        assert sm.release(sa) is a
        assert sm.free_count == 1
        # deterministic reuse: lowest freed slot first
        assert sm.allocate(Request(prompt=(4,), max_new_tokens=1)) == sa


# -- engine ----------------------------------------------------------------------------


class TestEngine:
    def test_greedy_parity_with_full_forward(self, model_and_params):
        """Continuous-batched greedy == generate() == naive full-sequence
        argmax, across interleaved admissions and slot reuse (5 requests
        over 2 slots)."""
        cfg, model, params = model_and_params
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8, 16))
        rs = np.random.RandomState(0)
        prompts = [rs.randint(1, 64, (n,)).astype(np.int32)
                   for n in (5, 7, 3, 9, 4)]
        pend = [eng.submit(Request(prompt=tuple(p), max_new_tokens=6))
                for p in prompts]
        eng.run_until_idle()
        for p, pd in zip(prompts, pend):
            assert pd.result.status == "ok"
            ref = np.asarray(generate(cfg, params, jnp.asarray(p)[None], 6))[0]
            np.testing.assert_array_equal(np.asarray(pd.result.tokens), ref)
            # naive reference: recompute the whole sequence every step
            seq = list(p)
            for _ in range(6):
                logits = model.apply({"params": params},
                                     jnp.asarray(seq)[None])
                seq.append(int(np.asarray(logits)[0, -1].argmax()))
            np.testing.assert_array_equal(np.asarray(pd.result.tokens), seq)

    def test_slot_reuse_after_eviction_is_clean(self, model_and_params):
        """A slot that served a long request then a short one must not leak
        stale KV rows into the reuse (per-slot cursor reset + masking)."""
        cfg, _, params = model_and_params
        eng = ServingEngine(cfg, params, slots=1, prefill_buckets=(8, 16))
        rs = np.random.RandomState(1)
        long_p = tuple(rs.randint(1, 64, (14,)))
        short_p = tuple(rs.randint(1, 64, (3,)))
        r1 = eng.submit(Request(prompt=long_p, max_new_tokens=8))
        r2 = eng.submit(Request(prompt=short_p, max_new_tokens=8))
        eng.run_until_idle()
        for p, pd in ((long_p, r1), (short_p, r2)):
            ref = np.asarray(
                generate(cfg, params, jnp.asarray(p)[None], 8))[0]
            np.testing.assert_array_equal(np.asarray(pd.result.tokens), ref)

    @pytest.mark.parametrize("draft", [False, True],
                             ids=["plain", "speculative"])
    def test_a_slot_free_for_longer_than_the_cache_stays_at_zero(
            self, model_and_params, draft):
        """Slot 1 serves one short request and is then free for 86 steps
        of slot 0, more than `max_len` 48: its cursor does not ride along
        (it would pass `max_len` and raise its overflow flag), on the
        device or on the host, and slot 0's tokens are `generate()`'s."""
        cfg, _, params = model_and_params
        spec = SpecDecoder(cfg, params, slots=2, k=4,
                           prefill_buckets=(8,)) if draft else None
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8,),
                            spec=spec)
        ending = _ending_rows(eng)

        def serve(*requests):
            pend = [eng.submit(Request(prompt=p, max_new_tokens=new))
                    for p, new in requests]
            while eng.queue.depth() or eng.slot_mgr.active_count:
                eng.step()
                _assert_free_slots_stand_still(eng)
            for (p, new), pd in zip(requests, pend):
                ref = np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                          new))[0]
                np.testing.assert_array_equal(np.asarray(pd.result.tokens),
                                              ref)

        serve(((1, 2, 3), 3), ((4, 5), 2))     # both slots have been used
        before = eng.decode_rows()["free"]
        serve(((7, 8, 9, 10), 44))             # slot 0 to its last row ...
        serve(((11, 12, 13, 14), 44))          # ... twice; slot 1 looks on
        if not draft:
            assert eng.decode_rows()["free"] - before == 2 * 43 > cfg.max_len
            # (4, 5) met its budget while (1, 2, 3) decoded on: its row of
            # the step dispatched meanwhile did no work, cursor held at 3
            assert ending == [3]
        rows = eng.decode_attn_rows()
        assert (rows["written_free"], rows["fetched_free"]) == \
            _rows_of_idle_slots(eng, ending)

    def test_warm_resume_matches_uninterrupted(self, model_and_params):
        """prior_tokens (the re-queue warm path) must continue the stream
        exactly: prompt+prior re-prefilled, only the remainder generated."""
        cfg, _, params = model_and_params
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8, 16))
        prompt = (5, 9, 2, 7)
        full = eng.submit(Request(prompt=prompt, max_new_tokens=8))
        eng.run_until_idle()
        tokens = list(full.result.tokens)
        prior = tuple(tokens[len(prompt):len(prompt) + 3])  # "died" after 3
        resumed = eng.submit(Request(prompt=prompt, max_new_tokens=8,
                                     prior_tokens=prior))
        eng.run_until_idle()
        assert list(resumed.result.tokens) == tokens

    def test_deadline_expired_rejected_not_wedged(self, model_and_params):
        cfg, _, params = model_and_params
        eng = ServingEngine(cfg, params, slots=1, prefill_buckets=(8,))
        dead = eng.submit(Request(prompt=(1, 2, 3), max_new_tokens=4,
                                  deadline_s=0.01))
        time.sleep(0.05)
        live = eng.submit(Request(prompt=(1, 2, 3), max_new_tokens=4))
        eng.run_until_idle()
        assert dead.result.status == "expired"
        assert live.result.status == "ok"

    def test_backpressure_and_impossible_requests(self, model_and_params):
        cfg, _, params = model_and_params
        eng = ServingEngine(cfg, params, slots=1, queue_capacity=1,
                            prefill_buckets=(8,))
        with pytest.raises(ValueError):  # can never fit in max_len
            eng.submit(Request(prompt=(1,) * 8, max_new_tokens=cfg.max_len))
        eng.submit(Request(prompt=(1, 2), max_new_tokens=2))
        with pytest.raises(BackpressureError):
            eng.submit(Request(prompt=(1, 2), max_new_tokens=2))

    def test_int8_kv_cache_serving(self, model_and_params):
        """kv_cache_dtype="int8" flows from the model config into the
        serving cache: int8 + f32 scale leaves, outputs near the fp cache."""
        cfg, _, params = model_and_params
        icfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
        eng = ServingEngine(icfg, params, slots=2, prefill_buckets=(8,))
        dtypes = {leaf.dtype.name for leaf in jax.tree.leaves(eng.cache)}
        assert "int8" in dtypes and "float32" in dtypes
        prompt = (3, 1, 4, 1, 5)
        pd = eng.submit(Request(prompt=prompt, max_new_tokens=6))
        eng.run_until_idle()
        assert pd.result.status == "ok"
        assert len(pd.result.tokens) == len(prompt) + 6

    def test_counters_telemetry(self, model_and_params):
        from kungfu_tpu.monitor.counters import Counters

        cfg, _, params = model_and_params
        c = Counters()
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8,),
                            counters=c)
        eng.submit(Request(prompt=(1, 2, 3), max_new_tokens=4))
        eng.run_until_idle()
        hists = c.hist_summaries()
        assert hists["ttft_ms"][""]["count"] == 1
        assert hists["tok_latency_ms"][""]["count"] >= 3
        assert c.events().get("requests_completed") == 1
        assert "queue_depth" in c.gauges()

    def test_default_buckets_cover_max_len(self):
        assert default_buckets(96) == (16, 32, 64, 96)
        assert default_buckets(16) == (16,)


# -- step programs: greedy tokens on the device, the slot cache donated -----------------


def _host_pick(rng, logits, temperature):
    """What the host did with fetched logits before the programs returned
    token ids: argmax, or one draw from the engine's generator."""
    if temperature <= 0.0:
        return int(np.argmax(logits))
    z = logits.astype(np.float64) / temperature
    z -= z.max()
    p = np.exp(z)
    p /= p.sum()
    return int(rng.choice(len(p), p=p))


def _host_path_tokens(cfg, params, reqs, buckets):
    """The host path as a plain loop over the same programs: every request
    admitted before the first decode step, every step's logits fetched and
    picked on the host from a generator seeded as the engine's."""
    ref = ServingEngine(cfg, params, slots=len(reqs), prefill_buckets=buckets)
    rng = np.random.default_rng(0)
    cache, nxt, out = ref.cache, np.zeros(len(reqs), np.int32), []
    for slot, (prompt, new, temp) in enumerate(reqs):
        padded = np.zeros((1, buckets[-1]), np.int32)
        padded[0, :len(prompt)] = prompt
        _, last, small = ref._prefill(ref.params, ref._small_cache0,
                                      jnp.asarray(padded), len(prompt),
                                      len(prompt))
        cache = write_slot(cache, small, slot)
        nxt[slot] = _host_pick(rng, np.asarray(last), temp)
        out.append([int(nxt[slot])])
    fetches = 0
    while any(len(o) < new for o, (_, new, _) in zip(out, reqs)):
        live = [s for s, (_, new, _) in enumerate(reqs) if len(out[s]) < new]
        fetches += any(reqs[s][2] > 0.0 for s in live)
        _, logits, cache, _ = ref._decode(ref.params, cache, {},
                                          jnp.asarray(nxt[:, None]))
        logits = np.asarray(logits)
        for s in live:  # a finished slot rides along on its last token
            nxt[s] = _host_pick(rng, logits[s], reqs[s][2])
            out[s].append(int(nxt[s]))
    return out, fetches


class TestStepPrograms:
    """ServingEngine's step programs hand the host token ids and keep their
    state on the device: `_decode` and `_verify` update the donated slot
    cache, `_prefill`/`_decode` return argmax beside logits that are fetched
    only for a sampling request."""

    @pytest.mark.parametrize("case", ["plain", "tie", "nan_overflow_row"])
    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_greedy_tokens_are_argmax_of_returned_logits(
            self, model_and_params, program, case):
        cfg, _, params = model_and_params
        if case == "tie":
            # the head's upper half repeats its lower half: every logit v
            # has an equal twin at v + 32, so every maximum is a tie
            k = params["lm_head"]["kernel"]
            params = dict(params, lm_head={
                "kernel": jnp.concatenate([k[:, :32], k[:, :32]], axis=1)})
        eng = ServingEngine(cfg, params, slots=3, prefill_buckets=(8,))
        toks = jnp.asarray([[5], [9], [40]], jnp.int32)
        if program == "decode":
            cache = eng.cache
            if case == "nan_overflow_row":
                # slot 1's write runs past max_len: the model poisons that
                # row with NaN and leaves the others clean
                cache = set_cursors(cache, jnp.asarray([0, cfg.max_len, 0]))
            got, logits, _, _ = eng._decode(eng.params, cache, {}, toks)
            got, logits = np.asarray(got), np.asarray(logits)
            assert got.shape == (3,) and got.dtype == np.int32
            assert logits.shape == (3, cfg.vocab_size)
        else:
            small = eng._small_cache0
            if case == "nan_overflow_row":
                small = set_cursors(small, jnp.asarray([cfg.max_len]))
            padded = jnp.asarray([[5, 9, 40, 2, 0, 0, 0, 0]], jnp.int32)
            got, logits, _ = eng._prefill(eng.params, small, padded, 4, 4)
            got, logits = np.asarray(got)[None], np.asarray(logits)[None]
        np.testing.assert_array_equal(got, np.argmax(logits, axis=-1))
        if case == "tie":
            np.testing.assert_array_equal(logits[:, :32], logits[:, 32:])
            assert (got < 32).all()  # the first of the two, as np.argmax
        if case == "nan_overflow_row":
            row = 1 if program == "decode" else 0
            assert np.isnan(logits[row]).all() and got[row] == 0
            assert np.isfinite(np.delete(logits, row, axis=0)).all()

    @pytest.mark.parametrize("mix", [
        pytest.param(((6, 0.0), (6, 0.0)), id="all_greedy"),
        pytest.param(((3, 0.8), (6, 0.0)), id="sampler_leaves_first"),
        pytest.param(((6, 0.0), (5, 1.3)), id="sampler_second_slot"),
        pytest.param(((4, 0.7), (6, 1.1)), id="all_sampling"),
    ])
    def test_mixed_batch_matches_host_path_and_counts_logit_fetches(
            self, model_and_params, mix):
        from kungfu_tpu.monitor.counters import Counters

        cfg, _, params = model_and_params
        prompts = ((3, 1, 4, 1, 5), (9, 2, 6))
        reqs = [(p, new, t) for p, (new, t) in zip(prompts, mix)]
        want, want_fetches = _host_path_tokens(cfg, params, reqs, (8,))
        c = Counters()
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8,),
                            counters=c)
        pend = [eng.submit(Request(prompt=p, max_new_tokens=new,
                                   temperature=t)) for p, new, t in reqs]
        eng.run_until_idle()
        for (p, _, _), pd, w in zip(reqs, pend, want):
            assert list(pd.result.tokens) == list(p) + w
        # a step fetches logits exactly when a sampling slot is active in it
        assert want_fetches == max(
            [new - 1 for _, new, t in reqs if t > 0.0], default=0)
        assert eng.decode_logit_fetches == want_fetches
        assert eng.stats()["decode_logit_fetches"] == want_fetches
        assert c.events().get("decode_logit_fetches", 0) == want_fetches

    @pytest.mark.parametrize("program", ["decode", "verify"])
    def test_cache_is_donated(self, model_and_params, program):
        cfg, _, params = model_and_params
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8,))
        n_leaves = len(jax.tree.leaves(eng.cache))
        if program == "decode":
            fn, args = eng._decode, (jnp.zeros((2, 1), jnp.int32),)
        else:
            fn, args = eng._verify, (jnp.zeros((2, 4), jnp.int32),
                                     jnp.zeros((2, 3), jnp.int32))
        # the lowering marks every cache leaf a donor (aliased to an output
        # where it can say which), whatever the backend makes of it
        # (the third argument holds a model's device counters: none here)
        text = fn.lower(eng.params, eng.cache, {}, *args).as_text()
        head = next(ln for ln in text.splitlines() if "@main(" in ln)
        sig = head.split("->")[0]
        assert (sig.count("tf.aliasing_output")
                + sig.count("jax.buffer_donor")) == n_leaves
        old = eng.cache
        out = fn(eng.params, eng.cache, {}, *args)
        assert all(not x.is_deleted() for x in jax.tree.leaves(out[-2]))
        deleted = [x.is_deleted() for x in jax.tree.leaves(old)]
        if any(deleted):  # this backend implements donation
            assert all(deleted)
        else:
            pytest.skip("this backend ignores donation")

    @pytest.mark.parametrize("reader", ["preempt_readmit", "prefix_insert"])
    def test_cache_readers_after_a_decode_step_see_live_buffers(
            self, model_and_params, reader):
        """The two places that read the slot cache outside a step program,
        after decode steps have donated earlier caches away."""
        cfg, _, params = model_and_params
        rs = np.random.RandomState(7)
        a = tuple(int(t) for t in rs.randint(1, 64, (7,)))
        if reader == "preempt_readmit":
            from kungfu_tpu.serving.tenancy import TenantRegistry, TenantSpec

            reg = TenantRegistry(specs={
                "bulk": TenantSpec(name="bulk", priority=0),
                "gold": TenantSpec(name="gold", priority=2)})
            eng = ServingEngine(cfg, params, slots=1, prefill_buckets=(8, 16),
                                prefix_cache=PrefixCache(1 << 20),
                                tenants=reg)
            b = tuple(int(t) for t in rs.randint(1, 64, (4,)))
            first = eng.submit(Request(prompt=a, max_new_tokens=8,
                                       tenant="bulk"))
            for _ in range(4):
                eng.step()
            second = eng.submit(Request(prompt=b, max_new_tokens=4,
                                        tenant="gold"))
            eng.run_until_idle()
            # the victim's rows came out of the live cache into the radix
            # tree, and its readmission was a warm hit on them
            assert eng.preemptions == 1
            assert eng.prefix.stats()["hit_tokens"] > 0
        else:
            eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8, 16),
                                prefix_cache=PrefixCache(1 << 20))
            b = a[:5] + (11, 12)
            first = eng.submit(Request(prompt=a, max_new_tokens=8))
            for _ in range(4):
                eng.step()
            second = eng.submit(Request(prompt=b, max_new_tokens=4))
            eng.run_until_idle()
            assert eng.prefix.stats()["hit_tokens"] >= 5
        for prompt, pd, new in ((a, first, 8), (b, second, 4)):
            assert pd.result.status == "ok"
            ref = np.asarray(generate(cfg, params,
                                      jnp.asarray(prompt)[None], new))[0]
            np.testing.assert_array_equal(np.asarray(pd.result.tokens), ref)


# -- the decode-step attention the engine's programs are built with ---------------------


def _slot_leaves(eng, name):
    """The `name` leaf ("idx", "overflowed") of every layer of the slot cache."""
    return [np.asarray(leaf) for path, leaf
            in jax.tree_util.tree_leaves_with_path(eng.cache)
            if getattr(path[-1], "key", None) == name]


def _assert_free_slots_stand_still(eng):
    """Between two steps: the device's cursors (once the step in flight, if
    any, has run) are the host's mirror; a slot that holds a request has a
    token or CARRY, and CARRY only for a row of the step in flight; a slot
    that holds none has token FREE, cursor 0 and a clear flag."""
    busy = np.zeros(eng.n_slots, bool)
    busy[list(eng.slot_mgr.active())] = True
    np.testing.assert_array_equal(eng._next_tok != FREE, busy)
    carried = np.zeros(eng.n_slots, bool)
    if eng._flight is not None:
        carried[[s for s, r in eng._flight.rows
                 if eng.slot_mgr.request_at(s) is r]] = True
    np.testing.assert_array_equal(eng._next_tok == CARRY, carried)
    assert (eng._next_tok[busy & ~carried] >= 0).all()
    for idx in _slot_leaves(eng, "idx"):
        np.testing.assert_array_equal(idx, eng._cursor)
    assert not eng._cursor[~busy].any()
    for flag in _slot_leaves(eng, "overflowed"):
        assert not flag[~busy].any()


def _ending_rows(eng):
    """Watch what the engine uploads to its decode program: -> a list that
    grows by the cursor of every row a call holds FREE under a slot that
    holds a request: one whose last token was in flight from the step
    before, which does no work and keeps its cursor until that is read."""
    seen, program = [], eng._decode

    def call(params, cache, counters, toks, prev):
        idle = np.asarray(toks)[:, 0] == FREE
        seen.extend(int(eng._cursor[s]) for s in eng.slot_mgr.active()
                    if idle[s])
        return program(params, cache, counters, toks, prev)

    eng._decode = call
    return seen


def _rows_of_idle_slots(eng, ending, kernel=False):
    """(`written_free`, `fetched_free`) as `decode_rows` and the uploads
    say they must be: an empty slot has nothing written, a slot whose
    request was ending the rows up to its cursor; the einsum reads the
    whole slot of either, the kernels' walk (`kernel`) visits neither."""
    idle = eng.decode_rows()["free"]
    return sum(ending), 0 if kernel else idle * eng.dcfg.max_len


def _staggered_run_with_a_preemption(monkeypatch, pallas, draft=False):
    """Five requests over two slots of a model whose cache the decode
    kernel takes (8 KV heads x 128, float32; two blocks of 256 rows):
    admissions between decode steps, one priority preemption, prompts on
    both sides of the first block's end, a slot left free while the other
    decodes on; with `draft`, speculative rounds (the model its own draft).
    Free slots are looked at after every step.
    -> (requests, tokens of each, the engine, its counters); the engine
    carries `ending`, the `_ending_rows` of the run"""
    from kungfu_tpu.monitor.counters import Counters
    from kungfu_tpu.serving.tenancy import TenantRegistry, TenantSpec

    monkeypatch.setenv("KFT_PALLAS", pallas)
    cfg, params = _kernel_sized_model()
    reg = TenantRegistry(specs={
        "bulk": TenantSpec(name="bulk", priority=0),
        "gold": TenantSpec(name="gold", priority=2)})
    counters = Counters()
    spec = SpecDecoder(cfg, params, slots=2, k=4,
                       prefill_buckets=(8, 16, 512)) if draft else None
    eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8, 16, 512),
                        prefix_cache=PrefixCache(1 << 24), tenants=reg,
                        spec=spec, counters=counters)
    eng.ending = _ending_rows(eng)
    rs = np.random.RandomState(3)
    prompt = lambda n: tuple(int(t) for t in rs.randint(1, 64, (n,)))  # noqa: E731
    reqs = []

    def submit(n, new, tenant="bulk"):
        reqs.append(Request(prompt=prompt(n), max_new_tokens=new, tenant=tenant))
        return eng.submit(reqs[-1])

    def step(times=1):
        for _ in range(times):
            eng.step()
            _assert_free_slots_stand_still(eng)

    pend = [submit(5, 12)]
    step()
    pend.append(submit(250, 10))
    step(1 if draft else 3)  # a round commits up to 4 tokens: both still busy
    pend.append(submit(7, 5, "gold"))  # evicts a bulk request
    pend.append(submit(12, 6))
    step(4)
    pend.append(submit(3, 4))
    while eng.queue.depth() or eng.slot_mgr.active_count:
        step()
    assert eng.preemptions == 1
    assert all(p.result.status == "ok" for p in pend)
    return reqs, [tuple(p.result.tokens) for p in pend], eng, counters


@functools.lru_cache(maxsize=1)
def _kernel_sized_model():
    cfg = _cfg(d_model=1024, n_heads=8, n_kv_heads=8, d_ff=32, max_len=512)
    return cfg, nn.meta.unbox(TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])


@functools.lru_cache(maxsize=None)
def _generated_alone(prompt, new):
    """What `generate()` returns for one request of the staggered run, by
    itself through the dense einsum (asked once a request, not once a case)."""
    cfg, params = _kernel_sized_model()
    return tuple(int(t) for t in np.asarray(
        generate(cfg, params, jnp.asarray(prompt)[None], new))[0])


def test_engine_tokens_and_row_counts_with_the_kernel_and_with_the_einsum(
        monkeypatch):
    """The engine returns token for token the same with the length-aware
    kernel (its body in the Pallas interpreter) as with the dense einsum
    forced, and `decode_attn_rows` says which of the two its decode
    program was built with."""
    _, got, kernel, _ = _staggered_run_with_a_preemption(monkeypatch, "interpret")
    _, want, einsum, _ = _staggered_run_with_a_preemption(monkeypatch, "off")
    assert got == want
    k, e = kernel.stats()["decode_attn_rows"], einsum.decode_attn_rows()
    for rows, eng in ((k, kernel), (e, einsum)):
        assert 0 < rows["written"] <= rows["fetched"] <= rows["cache"]
        # a free slot's cursor stays at 0: no row stands written under one
        # but a slot's whose request was ending
        assert (rows["written_free"], rows["fetched_free"]) == \
            _rows_of_idle_slots(eng, eng.ending, kernel=eng is kernel)
    assert e["fetched"] == e["cache"]          # the einsum reads every row
    assert 0 < e["fetched_free"] < e["fetched"]
    assert k["fetched"] < k["cache"]           # the kernel the live blocks
    assert k["fetched_free"] == 0              # ... of the live slots alone
    # the rows needed, rounded up to blocks a live slot-step: nothing else
    assert k["fetched"] <= k["written"] - k["written_free"] + 256 * \
        kernel.decode_rows()["live"]
    # the same steps on both sides: the cursors do not depend on the path
    assert [k[kind] for kind in ("cache", "written", "written_free")] == [
        e[kind] for kind in ("cache", "written", "written_free")]
    assert kernel.decode_rows() == einsum.decode_rows()
    assert k["cache"] % (2 * 512) == 0
    # every step fetched one or two blocks of 256 rows a live slot
    assert k["fetched"] % 256 == 0
    assert 256 * kernel.decode_rows()["live"] <= k["fetched"]


@pytest.mark.parametrize("before,live,kernel,einsum", [
    # (cursors a step starts from, who did work) -> (fetched, fetched_free)
    ([0, 0, 0, 0], [False] * 4, (0, 0), (2048, 2048)),          # nobody live
    ([255, 0, 0, 0], [True, False, False, False], (256, 0), (2048, 1536)),
    ([256, 0, 300, 0], [True, False, True, False], (1024, 0), (2048, 1024)),
    # a request whose last token is in flight: its slot is not visited either
    ([256, 40, 300, 511], [True, False, False, True], (1024, 0), (2048, 1024)),
    ([511] * 4, [True] * 4, (2048, 0), (2048, 0)),              # the old walk
], ids=["no_slot_live", "one_of_four", "two_of_four", "one_ending", "all_full"])
def test_a_step_counts_the_blocks_of_the_list_the_kernels_walk(
        monkeypatch, before, live, kernel, einsum):
    """`_count_step` under the kernels counts `visits` of the step's
    cursors and `live` mask, as the program builds it: the live slots'
    blocks of 256 rows and no block of a slot that did no work, so
    `fetched_free` is 0; under the einsum every slot's 512 rows."""
    cfg, params = _kernel_sized_model()
    for pallas, (fetched, fetched_free) in (("interpret", kernel),
                                            ("off", einsum)):
        monkeypatch.setenv("KFT_PALLAS", pallas)
        eng = ServingEngine(cfg, params, slots=4, prefill_buckets=(8,))
        assert eng._attn_block == {1: 256 if pallas == "interpret" else None}
        start, did = np.array(before), np.array(live)
        eng._cursor = start + did
        for _ in range(2):
            eng._count_step(start, 1, did)
        rows = eng.decode_attn_rows()
        assert (rows["fetched"], rows["fetched_free"]) == (
            2 * fetched, 2 * fetched_free)
        assert rows["cache"] == 2 * 4 * 512
        assert rows["written"] == 2 * int((start + did).sum())
        assert rows["written_free"] == 2 * int(start[~did].sum())
        assert eng.decode_rows() == {"live": 2 * int(did.sum()),
                                     "free": 2 * int((~did).sum())}


@pytest.mark.parametrize("draft", [False, True], ids=["plain", "speculative"])
@pytest.mark.parametrize("pallas", ["off", "interpret"])
def test_a_free_slot_does_no_work_and_busy_slots_serve_generates_tokens(
        monkeypatch, pallas, draft):
    """Staggered admissions, releases and a preemption, with and without a
    speculative draft: after every step the device's cursors equal the
    host's, a free slot's is 0 and its overflow flag clear (looked at
    inside the run); no row stands written under a free slot, a free slot's
    step reads no block under the kernels and its whole slot under the
    einsum, every slot-step is counted live or free; and each request's
    tokens are its own `generate()`'s, token for token."""
    reqs, got, eng, counters = _staggered_run_with_a_preemption(
        monkeypatch, pallas, draft)
    monkeypatch.setenv("KFT_PALLAS", "off")
    assert got == [_generated_alone(r.prompt, r.max_new_tokens) for r in reqs]
    steps = counters.hist_summaries()["tok_latency_ms"][""]["count"]
    rows, kinds = eng.decode_attn_rows(), eng.stats()["decode_rows"]
    assert kinds["live"] + kinds["free"] == 2 * steps
    assert 0 < kinds["free"] < kinds["live"]
    assert rows["cache"] == steps * 2 * 512
    assert (rows["written_free"], rows["fetched_free"]) == \
        _rows_of_idle_slots(eng, eng.ending, kernel=pallas == "interpret")
    ran = eng.decode_steps()
    assert ran["wasted_rows"] == 0 and eng._flight is None
    if draft:
        assert eng.spec.rounds > 0
        assert ran["ahead"] + ran["synced"] < steps  # the rest were rounds
    else:
        # the six admissions (some in one iteration) and the preemption
        # read the step in flight first: the next was dispatched with
        # nothing in flight; between them the loop ran one step ahead
        assert ran["ahead"] + ran["synced"] == steps
        assert ran["ahead"] > 6 >= ran["synced"] > 0
        # one token a live slot-step; each of the six admissions (five
        # requests, the evicted one twice) brought one from its prefill
        assert kinds["live"] == sum(
            len(t) - len(r.prompt) for t, r in zip(got, reqs)) - 5 - 1


# -- the decode loop one step ahead ------------------------------------------------------


def _alone(cfg, params, prompt, new):
    """A request's tokens from an engine that serves nothing else."""
    eng = ServingEngine(cfg, params, slots=1, prefill_buckets=(8, 16))
    pd = eng.submit(Request(prompt=prompt, max_new_tokens=new))
    eng.run_until_idle()
    return tuple(pd.result.tokens)


class TestOneStepAhead:
    """`ServingEngine._decode_step` dispatches step N+1 before it reads
    step N's tokens wherever the host need not have seen them first, and
    reads the step in flight before anything that does need them."""

    @pytest.mark.parametrize("drive", ["step", "run_until_idle"])
    def test_the_benchmarks_warm_up(self, model_and_params, drive):
        """What `benchmark/lib/serve_driver.py` sends before a window: a
        request of two new tokens alone in the engine for each prefill
        bucket (its second token is its last: the one decode step it joins
        leaves no row live, so nothing may be dispatched behind it), then
        one fixed request twice, which must answer with the same tokens.
        Nothing is left in flight."""
        cfg, _, params = model_and_params
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8, 16))

        def serve(prompt, new):
            pd = eng.submit(Request(prompt=prompt, max_new_tokens=new))
            if drive == "run_until_idle":
                eng.run_until_idle(timeout_s=60)
            else:
                for _ in range(new + 2):
                    eng.step()
                    _assert_free_slots_stand_still(eng)
            assert pd.result is not None and pd.result.status == "ok"
            assert eng._flight is None and not eng.slot_mgr.active_count
            return tuple(pd.result.tokens)

        rs = np.random.RandomState(9)
        for n in (5, 13):
            prompt = tuple(int(t) for t in rs.randint(1, 64, (n,)))
            assert serve(prompt, 2) == _alone(cfg, params, prompt, 2)
        assert eng.decode_steps() == {"ahead": 0, "synced": 2,
                                      "wasted_rows": 0}
        fixed = tuple(int(t) for t in rs.randint(1, 64, (5,)))
        a, b = serve(fixed, 8), serve(fixed, 8)
        assert a == b and len(a) == 5 + 8
        np.testing.assert_array_equal(a, np.asarray(generate(
            cfg, params, jnp.asarray(fixed)[None], 8))[0])
        # 7 decode steps a request: the first with nothing in flight, the
        # others behind it; none behind the one that brings the last token
        assert eng.decode_steps() == {"ahead": 12, "synced": 4,
                                      "wasted_rows": 0}
        assert eng._decode._cache_size() == 1

    @pytest.mark.parametrize("script", ["one_request", "a_second_joins"])
    def test_counts_of_steps_ahead_and_synced(self, model_and_params, script):
        """A scripted run, step for step: which steps are dispatched with
        the one before unread, and what each `step()` hands back."""
        cfg, _, params = model_and_params
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8,))
        new = 6 if script == "one_request" else 8
        first = eng.submit(Request(prompt=(1, 2, 3), max_new_tokens=new))
        seen = []

        def step(times=1):
            for _ in range(times):
                eng.step()
                _assert_free_slots_stand_still(eng)
                ran = eng.decode_steps()
                seen.append((ran["synced"], ran["ahead"],
                             len(first.request.generated)))

        if script == "one_request":
            step(5)
            # the admission's first token, then one token a step(): step 1
            # and step 2 are dispatched in the first, the sixth token's
            # step is the last and nothing follows it
            assert seen == [(1, 1, 2), (1, 2, 3), (1, 3, 4), (1, 4, 5),
                            (1, 4, 6)]
        else:
            step(2)
            second = eng.submit(Request(prompt=(4, 5), max_new_tokens=3))
            step(4)
            # the admission reads the step in flight (the first request's
            # fourth token comes out with it), then both decode: synced,
            # and ahead again.  With the second request's last token in
            # flight its row rides FREE in the step behind, once, while the
            # first goes on alone to its own last token
            assert seen == [(1, 1, 2), (1, 2, 3), (2, 3, 5), (2, 4, 6),
                            (2, 5, 7), (2, 5, 8)]
            assert len(second.request.generated) == 3
            # seven steps of two slots: the empty slot of four of them and
            # the ending row of one did no work
            assert eng.decode_rows() == {"live": 7 + 2, "free": 4 + 1}
        assert first.result.status == "ok" and eng._flight is None
        assert eng.decode_steps()["wasted_rows"] == 0
        assert eng.stats()["decode_steps"] == eng.decode_steps()
        want = np.asarray(generate(cfg, params, jnp.asarray((1, 2, 3))[None],
                                   new))[0]
        np.testing.assert_array_equal(np.asarray(first.result.tokens), want)

    @pytest.mark.parametrize("beside", ["alone", "beside_another"])
    def test_an_eos_the_step_in_flight_could_not_know_of(
            self, model_and_params, beside):
        """A request ends on its `eos` in step N with step N+1 dispatched:
        N+1 computed its row once more, for nothing.  The token is never
        read into any request, the row is counted, the slot is reset behind
        N+1 and the next admission into it decodes as if alone."""
        cfg, _, params = model_and_params
        other, later = (9, 2, 6, 5), (7, 7, 1)
        # a stream whose third or later new token is one it has not made
        # before: as `eos` it stops the request there, mid-stream
        prompt, full, cut = next(
            (p, full, cut) for p in ((a, b, c) for a in range(1, 9)
                                     for b in (11, 29) for c in (4, 50))
            for full in [_alone(cfg, params, p, 12)]
            for cut in range(len(p) + 2, len(p) + 9)
            if full[cut] not in full[len(p):cut])
        eos = full[cut]
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8,))
        ended = eng.submit(Request(prompt=prompt, max_new_tokens=12,
                                   eos_id=int(eos)))
        pend = [eng.submit(Request(prompt=other, max_new_tokens=10))] \
            if beside == "beside_another" else []
        while ended.result is None:
            eng.step()
            _assert_free_slots_stand_still(eng)
        assert tuple(ended.result.tokens) == full[:cut + 1]
        assert eng._next_tok[0] == FREE and eng._cursor[0] == 0
        if beside == "alone":
            # the step() that read the eos found nothing else to wait for
            assert eng._flight is None
            assert eng.decode_steps()["wasted_rows"] == 1
        else:
            assert [s for s, _ in eng._flight.rows] == [0, 1]
        pend.append(eng.submit(Request(prompt=later, max_new_tokens=9)))
        while eng.queue.depth() or eng.slot_mgr.active_count:
            eng.step()
            _assert_free_slots_stand_still(eng)
        assert eng.decode_steps()["wasted_rows"] == 1 and eng._flight is None
        for pd in pend:
            assert tuple(pd.result.tokens) == _alone(
                cfg, params, pd.request.prompt, pd.request.max_new_tokens)
        assert eng.total_tokens == sum(
            len(pd.request.generated) for pd in [ended] + pend)

    @pytest.mark.parametrize("sampler", ["leaves_first", "joins_later"])
    def test_no_step_runs_ahead_while_a_request_samples(
            self, model_and_params, sampler):
        """A sampling request's token is drawn on the host from fetched
        logits, so its steps stay synchronous: the same draws from the
        same generator as the host path, and `ahead` stands still while it
        is active."""
        cfg, _, params = model_and_params
        reqs = [((3, 1, 4, 1, 5), 4, 0.8), ((9, 2, 6), 12, 0.0)]
        want, _ = _host_path_tokens(cfg, params, reqs, (8,))
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8,))
        submit = lambda r: eng.submit(Request(  # noqa: E731
            prompt=r[0], max_new_tokens=r[1], temperature=r[2]))
        if sampler == "leaves_first":
            hot, cold = submit(reqs[0]), submit(reqs[1])
        else:
            cold = submit(reqs[1])
            for _ in range(3):
                eng.step()
            assert eng.decode_steps()["ahead"] == 3 and eng._flight is not None
            hot = submit(reqs[0])
        ahead = eng.decode_steps()["ahead"]
        while hot.result is None:
            eng.step()
            _assert_free_slots_stand_still(eng)
            assert eng._flight is None
        assert eng.decode_steps()["ahead"] == ahead
        assert eng.decode_logit_fetches == 3
        eng.run_until_idle()
        assert eng.decode_steps()["ahead"] > ahead
        assert list(cold.result.tokens) == list(reqs[1][0]) + want[1]
        if sampler == "leaves_first":
            assert list(hot.result.tokens) == list(reqs[0][0]) + want[0]

    @pytest.mark.parametrize("what", ["set_params", "in_flight",
                                      "prefill_only", "preemption"])
    def test_what_needs_the_last_token_reads_the_step_in_flight_first(
            self, model_and_params, what):
        cfg, _, params = model_and_params
        tenants = None
        if what == "preemption":
            from kungfu_tpu.serving.tenancy import TenantRegistry, TenantSpec

            tenants = TenantRegistry(specs={
                "bulk": TenantSpec(name="bulk", priority=0),
                "gold": TenantSpec(name="gold", priority=2)})
        eng = ServingEngine(cfg, params, slots=1, prefill_buckets=(8, 16),
                            prefix_cache=PrefixCache(1 << 20), tenants=tenants)
        prompt = (5, 9, 2, 7)
        pd = eng.submit(Request(prompt=prompt, max_new_tokens=5,
                                tenant="bulk"))
        for _ in range(3):
            eng.step()
        # four tokens read, the fifth and last in flight
        assert len(pd.request.generated) == 4 and eng._flight is not None
        others = []
        if what == "set_params":
            eng.set_params(params)
        elif what == "in_flight":
            assert eng.in_flight() == []  # it had finished, had one looked
        elif what == "prefill_only":
            first, rows, total, _ = eng.prefill_only(
                Request(prompt=(8, 8, 3), max_new_tokens=4))
            assert total == 3 and first == _alone(
                cfg, params, (8, 8, 3), 1)[-1]
        else:
            others.append(eng.submit(Request(prompt=(6, 1), max_new_tokens=3,
                                             tenant="gold")))
        if what != "preemption":
            # read where it was asked for, handed out by the next step()
            assert eng._flight is None and pd.result.status == "ok"
        done = eng.step()
        assert [r.req_id for r in done][:1] == [pd.request.req_id]
        # the last token was in flight, so the request ended: no eviction
        assert eng.preemptions == 0
        eng.run_until_idle()
        for p in [pd] + others:
            assert tuple(p.result.tokens) == _alone(
                cfg, params, p.request.prompt, p.request.max_new_tokens)

    def test_the_engine_has_one_decode_program_and_callers_keep_theirs(
            self, model_and_params):
        """The engine's calls all take the [slots] tokens of the step
        before (zeros when none is in flight): one signature, one
        executable.  A caller that gives four arguments lowers the program
        without the select (tests/unit/test_parent_programs.py holds its
        text), and a row that holds CARRY takes the token `prev` has."""
        cfg, _, params = model_and_params
        eng = ServingEngine(cfg, params, slots=3, prefill_buckets=(8,))
        for n in (3, 5, 2, 4):
            eng.submit(Request(prompt=tuple(range(1, n + 1)),
                               max_new_tokens=n + 2))
        eng.run_until_idle()
        assert eng.decode_steps()["ahead"] > eng.decode_steps()["synced"] > 0
        assert eng._decode._cache_size() == 1
        toks = jax.ShapeDtypeStruct((3, 1), jnp.int32)
        four = eng._decode.lower(eng.params, eng.cache, {}, toks).as_text()
        five = eng._decode.lower(eng.params, eng.cache, {}, toks,
                                 eng._no_prev).as_text()
        arguments = lambda text: next(  # noqa: E731
            ln for ln in text.splitlines() if "@main(" in ln).count("%arg")
        assert arguments(five) == arguments(four) + 1
        carry = "dense<-2> : tensor<i32>"  # compared with in one of them
        assert carry in five and carry not in four
        fresh = lambda: ServingEngine(  # noqa: E731
            cfg, params, slots=3, prefill_buckets=(8,))
        a = fresh()
        known, _, _, _ = a._decode(a.params, a.cache, {},
                                   jnp.asarray([[5], [FREE], [40]], jnp.int32))
        b = fresh()
        carried, _, cache, _ = b._decode(
            b.params, b.cache, {},
            jnp.asarray([[CARRY], [FREE], [40]], jnp.int32),
            jnp.asarray([5, 17, 23], jnp.int32))
        np.testing.assert_array_equal(np.asarray(known), np.asarray(carried))
        idx = [np.asarray(leaf) for path, leaf
               in jax.tree_util.tree_leaves_with_path(cache)
               if getattr(path[-1], "key", None) == "idx"]
        assert all((i == [1, 0, 1]).all() for i in idx)


# -- radix prefix cache ----------------------------------------------------------------


class TestPrefixCache:
    def _rows(self, tokens):
        """Synthetic rows keyed like extract_rows: one leaf whose row i is
        filled with token i (row identity is checkable by value)."""
        return {("k",): np.asarray(tokens, np.float32)[:, None]
                * np.ones((1, 4), np.float32)}

    def test_radix_match_insert_split_semantics(self):
        pc = PrefixCache(budget_bytes=1 << 20)
        a = (1, 2, 3, 4, 5)
        pc.insert(a, self._rows(a))
        # exact-prefix hit capped at len - 1
        hit, lease = pc.match((1, 2, 3, 4, 5))
        assert hit == 4
        np.testing.assert_array_equal(
            lease.rows()[("k",)][:, 0], [1, 2, 3, 4])
        lease.release()
        # divergence mid-edge: shared prefix only
        b = (1, 2, 9, 9)
        hit, lease = pc.match(b)
        assert hit == 2
        lease.release()
        pc.insert(b, self._rows(b))  # splits at 2
        hit, lease = pc.match((1, 2, 9, 9, 7))
        assert hit == 4
        np.testing.assert_array_equal(
            lease.rows()[("k",)][:, 0], [1, 2, 9, 9])
        lease.release()
        # the original path still matches after the split
        hit, lease = pc.match((1, 2, 3, 4, 5, 6))
        assert hit == 5
        lease.release()
        # miss: nothing shared
        hit, lease = pc.match((8, 8))
        assert hit == 0 and lease is None
        # dedup: re-inserting a covered prefix allocates nothing
        before = pc.total_bytes
        called = []
        pc.insert(a, lambda: called.append(1) or self._rows(a))
        assert pc.total_bytes == before and not called

    def test_lru_eviction_under_budget_journaled(self, tmp_path,
                                                 monkeypatch):
        from kungfu_tpu.monitor import journal as J

        path = str(tmp_path / "j.jsonl")
        monkeypatch.setenv(J.JOURNAL_FILE_ENV, path)
        J._reset_for_tests()
        try:
            row_bytes = 4 * 4  # one row = [1, 4] f32
            pc = PrefixCache(budget_bytes=8 * row_bytes)
            pc.insert((1, 2, 3, 4), self._rows((1, 2, 3, 4)))
            hit, lease = pc.match((1, 2, 3))  # touch the old entry
            if lease:
                lease.release()
            pc.insert((9, 8, 7, 6, 5, 4), self._rows((9, 8, 7, 6, 5, 4)))
            assert pc.total_bytes <= pc.budget
            assert pc.evictions >= 1
            kinds = {e["event"] for e in J.read_journal(path)}
            assert "prefix_evicted" in kinds
        finally:
            J._reset_for_tests()

    def test_refcounted_lease_blocks_eviction(self):
        row_bytes = 16
        pc = PrefixCache(budget_bytes=4 * row_bytes)
        pc.insert((1, 2, 3, 4), self._rows((1, 2, 3, 4)))
        hit, lease = pc.match((1, 2, 3, 4, 9))
        assert hit == 4
        # over-budget insert while the path is pinned: the pinned node
        # must survive
        pc.insert((5, 6, 7, 8), self._rows((5, 6, 7, 8)))
        hit2, lease2 = pc.match((1, 2, 3, 4, 9))
        assert hit2 == 4  # still there
        if lease2:
            lease2.release()
        lease.release()

    def test_engine_parity_with_shared_prefixes(self, model_and_params):
        """Prefix-grafted output == generate() bit-exact over interleaved
        admissions + slot reuse, with real hits."""
        cfg, _, params = model_and_params
        pc = PrefixCache(budget_bytes=64 << 20)
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8, 16),
                            prefix_cache=pc)
        rs = np.random.RandomState(3)
        shared = tuple(rs.randint(1, 64, (6,)))
        prompts = [shared + tuple(rs.randint(1, 64, (n,)))
                   for n in (3, 5, 2, 4)]
        prompts.append(shared + prompts[1][6:])  # exact duplicate tail
        pend = [eng.submit(Request(prompt=p, max_new_tokens=6))
                for p in prompts]
        eng.run_until_idle()
        for p, pd in zip(prompts, pend):
            ref = np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                      6))[0]
            np.testing.assert_array_equal(np.asarray(pd.result.tokens), ref)
        assert pc.hit_tokens > 0
        assert 0.0 < pc.hit_rate() < 1.0
        assert eng.stats()["prefix"]["nodes"] >= 2

    def test_int8_cache_rows_graft(self, model_and_params):
        """The radix cache stores and grafts quantized rows + scales when
        the engine serves an int8 KV cache."""
        cfg, _, params = model_and_params
        icfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
        pc = PrefixCache(budget_bytes=64 << 20)
        eng = ServingEngine(icfg, params, slots=1, prefill_buckets=(8,),
                            prefix_cache=pc)
        p1 = (7, 3, 5, 2)
        r1 = eng.submit(Request(prompt=p1, max_new_tokens=4))
        eng.run_until_idle()
        r2 = eng.submit(Request(prompt=p1, max_new_tokens=4))
        eng.run_until_idle()
        assert list(r1.result.tokens) == list(r2.result.tokens)
        assert pc.hit_tokens >= 3

    def test_invalidated_on_weight_reload(self, model_and_params):
        cfg, _, params = model_and_params
        pc = PrefixCache(budget_bytes=64 << 20)
        eng = ServingEngine(cfg, params, slots=1, prefill_buckets=(8,),
                            prefix_cache=pc)
        eng.submit(Request(prompt=(1, 2, 3, 4), max_new_tokens=2))
        eng.run_until_idle()
        assert pc.total_bytes > 0
        params2 = jax.tree.map(lambda x: x * 1.01, params)
        eng.set_params(params2)
        assert pc.total_bytes == 0 and eng.params_version == 1
        # post-reload output matches fresh generate with the new weights
        pd = eng.submit(Request(prompt=(1, 2, 3, 4), max_new_tokens=4))
        eng.run_until_idle()
        ref = np.asarray(generate(cfg, params2,
                                  jnp.asarray((1, 2, 3, 4))[None], 4))[0]
        np.testing.assert_array_equal(np.asarray(pd.result.tokens), ref)

    def test_counters_telemetry(self, model_and_params):
        from kungfu_tpu.monitor.counters import Counters

        cfg, _, params = model_and_params
        c = Counters()
        pc = PrefixCache(budget_bytes=64 << 20, counters=c)
        eng = ServingEngine(cfg, params, slots=1, prefill_buckets=(8,),
                            prefix_cache=pc, counters=c)
        eng.submit(Request(prompt=(5, 6, 7, 8), max_new_tokens=2))
        eng.run_until_idle()
        eng.submit(Request(prompt=(5, 6, 7, 8), max_new_tokens=2))
        eng.run_until_idle()
        assert c.events().get("prefix_hit_tokens", 0) >= 3
        g = c.gauges()
        assert g.get("prefix_hit_rate", 0) > 0
        assert g.get("prefix_cache_bytes", 0) > 0


# -- speculative decoding --------------------------------------------------------------


class TestSpeculative:
    def test_parity_self_draft(self, model_and_params):
        """Spec output == generate() bit-exact over interleaved admissions
        and slot reuse; acceptance engaged (self-draft ~= 1.0)."""
        cfg, _, params = model_and_params
        spec = SpecDecoder(cfg, params, slots=2, k=4,
                           prefill_buckets=(8, 16))
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8, 16),
                            spec=spec)
        rs = np.random.RandomState(1)
        prompts = [tuple(rs.randint(1, 64, (n,))) for n in (4, 7, 3, 6, 5)]
        pend = [eng.submit(Request(prompt=p, max_new_tokens=7))
                for p in prompts]
        eng.run_until_idle()
        for p, pd in zip(prompts, pend):
            ref = np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                      7))[0]
            np.testing.assert_array_equal(np.asarray(pd.result.tokens), ref)
        assert spec.rounds > 0
        assert spec.accept_rate() > 0.5  # self-draft: near-total acceptance

    def test_parity_truncated_draft(self, model_and_params):
        """A genuinely different (1-layer truncated) draft: lower
        acceptance, IDENTICAL tokens — acceptance is self-validating."""
        cfg, _, params = model_and_params
        dcfg = dataclasses.replace(cfg, n_layers=1)
        dparams = {k: v for k, v in params.items()
                   if not k.startswith("block_") or k == "block_0"}
        spec = SpecDecoder(dcfg, dparams, slots=2, k=4,
                           prefill_buckets=(8, 16))
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8, 16),
                            spec=spec)
        rs = np.random.RandomState(2)
        prompts = [tuple(rs.randint(1, 64, (n,))) for n in (5, 3, 6)]
        pend = [eng.submit(Request(prompt=p, max_new_tokens=8))
                for p in prompts]
        eng.run_until_idle()
        for p, pd in zip(prompts, pend):
            ref = np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                      8))[0]
            np.testing.assert_array_equal(np.asarray(pd.result.tokens), ref)
        assert spec.rounds > 0

    def test_one_extra_compiled_signature_across_mixes(self,
                                                       model_and_params):
        """Across wildly different request mixes the verify program stays
        ONE compiled signature and the plain decode program never joins in
        while speculation is healthy."""
        cfg, _, params = model_and_params
        spec = SpecDecoder(cfg, params, slots=3, k=4,
                           prefill_buckets=(8, 16))
        eng = ServingEngine(cfg, params, slots=3, prefill_buckets=(8, 16),
                            spec=spec)
        rs = np.random.RandomState(4)
        for batch in ((3, 9), (1,), (6, 2, 8, 4)):
            pend = [eng.submit(Request(
                prompt=tuple(rs.randint(1, 64, (n,))),
                max_new_tokens=int(rs.randint(2, 9))))
                for n in batch]
            eng.run_until_idle()
            assert all(p.result.status == "ok" for p in pend)
        assert eng._verify._cache_size() == 1
        assert eng._decode._cache_size() == 0  # spec stayed engaged

    def test_acceptance_collapse_disables_and_falls_back(
            self, model_and_params, tmp_path, monkeypatch):
        """A useless draft (params from a different seed) collapses
        acceptance: slots journal spec_disabled, the engine drops to the
        plain program, output stays bit-exact."""
        from kungfu_tpu.monitor import journal as J

        path = str(tmp_path / "j.jsonl")
        monkeypatch.setenv(J.JOURNAL_FILE_ENV, path)
        J._reset_for_tests()
        try:
            cfg, model, params = model_and_params
            bad = nn.meta.unbox(model.init(jax.random.PRNGKey(9),
                                           jnp.zeros((1, 4), jnp.int32))
                                )["params"]
            spec = SpecDecoder(cfg, bad, slots=1, k=4, prefill_buckets=(8,),
                               disable_after=2, disable_below=0.3)
            eng = ServingEngine(cfg, params, slots=1, prefill_buckets=(8,),
                                spec=spec)
            pd = eng.submit(Request(prompt=(2, 4, 6), max_new_tokens=16))
            eng.run_until_idle()
            ref = np.asarray(generate(cfg, params,
                                      jnp.asarray((2, 4, 6))[None], 16))[0]
            np.testing.assert_array_equal(np.asarray(pd.result.tokens), ref)
            assert spec._disabled.any()
            assert eng._decode._cache_size() == 1  # plain fallback engaged
            events = J.read_journal(path)
            assert any(e["event"] == "spec_disabled" for e in events)
        finally:
            J._reset_for_tests()

    def test_temperature_request_forces_plain_path(self, model_and_params):
        """Sampling requests can't speculate (acceptance is an argmax
        identity): a mixed batch runs plain and still completes."""
        cfg, _, params = model_and_params
        spec = SpecDecoder(cfg, params, slots=2, k=4, prefill_buckets=(8,))
        eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8,),
                            spec=spec)
        hot = eng.submit(Request(prompt=(1, 2, 3), max_new_tokens=5,
                                 temperature=0.8))
        cold = eng.submit(Request(prompt=(4, 5, 6), max_new_tokens=5))
        eng.run_until_idle()
        assert hot.result.status == "ok" and cold.result.status == "ok"
        ref = np.asarray(generate(cfg, params,
                                  jnp.asarray((4, 5, 6))[None], 5))[0]
        np.testing.assert_array_equal(np.asarray(cold.result.tokens), ref)
        assert spec.rounds == 0  # never speculated under sampling

    def test_eos_mid_accepted_run(self, model_and_params):
        """An eos landing inside an accepted run stops the stream exactly
        there — same tokens as the plain engine with the same eos."""
        cfg, _, params = model_and_params
        prompt = (3, 1, 4)
        ref_eng = ServingEngine(cfg, params, slots=1, prefill_buckets=(8,))
        full = ref_eng.submit(Request(prompt=prompt, max_new_tokens=12))
        ref_eng.run_until_idle()
        toks = list(full.result.tokens)
        eos = toks[len(prompt) + 4]  # force a stop mid-stream
        ref2 = ServingEngine(cfg, params, slots=1, prefill_buckets=(8,))
        want = ref2.submit(Request(prompt=prompt, max_new_tokens=12,
                                   eos_id=int(eos)))
        ref2.run_until_idle()
        spec = SpecDecoder(cfg, params, slots=1, k=4, prefill_buckets=(8,))
        eng = ServingEngine(cfg, params, slots=1, prefill_buckets=(8,),
                            spec=spec)
        got = eng.submit(Request(prompt=prompt, max_new_tokens=12,
                                 eos_id=int(eos)))
        eng.run_until_idle()
        assert list(got.result.tokens) == list(want.result.tokens)

    def test_spec_telemetry(self, model_and_params):
        from kungfu_tpu.monitor.counters import Counters

        cfg, _, params = model_and_params
        c = Counters()
        spec = SpecDecoder(cfg, params, slots=1, k=4, prefill_buckets=(8,),
                           counters=c)
        eng = ServingEngine(cfg, params, slots=1, prefill_buckets=(8,),
                            spec=spec, counters=c)
        eng.submit(Request(prompt=(1, 2, 3), max_new_tokens=8))
        eng.run_until_idle()
        assert c.events().get("spec_rounds", 0) >= 1
        assert c.hist_summaries()["spec_accept_rate"][""]["count"] >= 1
        assert "spec" in eng.stats()


# -- disaggregation --------------------------------------------------------------------


class TestDisagg:
    def test_pack_unpack_round_trip_and_torn_blob(self):
        from kungfu_tpu.ops.kv_ship import pack_kv, unpack_kv

        rows = {("block_0", "attn", "cached_k"):
                np.arange(24, dtype=np.float32).reshape(3, 2, 4)}
        meta = {"cursor": 3, "first_token": 7, "request": {"id": "r1"}}
        blob = pack_kv(meta, rows)
        got = unpack_kv(blob)
        assert got is not None
        m2, r2 = got
        assert m2["cursor"] == 3 and m2["first_token"] == 7
        np.testing.assert_array_equal(
            r2[("block_0", "attn", "cached_k")],
            rows[("block_0", "attn", "cached_k")])
        assert unpack_kv(blob[:10]) is None
        assert unpack_kv(b"garbage") is None

    def test_prefill_only_ship_parity(self, model_and_params):
        """prefill_only on one engine + submit_prefilled on another ==
        generate(), incl. the prior-token warm path and int8 rows."""
        cfg, _, params = model_and_params
        from kungfu_tpu.ops.kv_ship import pack_kv, unpack_kv

        for kv_dtype in ("model", "int8"):
            c = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
            pre = ServingEngine(c, params, slots=1, prefill_buckets=(8, 16))
            dec = ServingEngine(c, params, slots=2, prefill_buckets=(8, 16))
            rs = np.random.RandomState(5)
            for n in (4, 7, 3):
                p = tuple(rs.randint(1, 64, (n,)))
                req = Request(prompt=p, max_new_tokens=6)
                first, rows, total, hit = pre.prefill_only(req)
                blob = pack_kv({"cursor": total, "first_token": first,
                                "request": req.to_json()}, rows)
                meta, rows2 = unpack_kv(blob)
                pd = dec.submit_prefilled(Request.from_json(meta["request"]),
                                          meta, rows2)
                dec.run_until_idle()
                if kv_dtype == "model":
                    ref = np.asarray(generate(cfg, params,
                                              jnp.asarray(p)[None], 6))[0]
                    np.testing.assert_array_equal(
                        np.asarray(pd.result.tokens), ref)
                else:
                    assert pd.result.status == "ok"

    def test_double_ship_dedupes(self, model_and_params):
        cfg, _, params = model_and_params
        pre = ServingEngine(cfg, params, slots=1, prefill_buckets=(8,))
        dec = ServingEngine(cfg, params, slots=1, prefill_buckets=(8,))
        req = Request(prompt=(1, 2, 3), max_new_tokens=4)
        first, rows, total, _ = pre.prefill_only(req)
        meta = {"cursor": total, "first_token": first}
        p1 = dec.submit_prefilled(req, meta, rows)
        p2 = dec.submit_prefilled(req, meta, rows)  # the re-ship
        assert p1 is p2
        dec.run_until_idle()
        assert p1.result.status == "ok"
        assert dec.total_completed == 1  # served exactly once

    def test_cluster_tiers_document(self):
        from kungfu_tpu.plan import Cluster, HostList

        c = Cluster.from_hostlist(HostList.parse("127.0.0.1:4"), 3)
        assert c.tiers is None and c.tier_of(c.workers[0]) == ""
        # untier'd documents keep their exact serialized bytes
        assert "tiers" not in c.to_json()
        t = c.assign_tiers(1)
        assert t.tier_of(t.workers[0]) == "prefill"
        assert t.tier_of(t.workers[1]) == "decode"
        assert t.tier_counts() == {"prefill": 1, "decode": 2}
        rt = Cluster.from_json(t.to_json())
        assert rt.tiers == t.tiers
        # resize preserves retained tiers, defaults grown workers to decode
        grown = t.resize(4)
        assert grown.tier_of(grown.workers[3]) == "decode"
        shrunk = t.resize(2)
        assert set(shrunk.tiers) == {str(w) for w in shrunk.workers}
        # validation: tier entries must name workers
        bad = Cluster(runners=c.runners, workers=c.workers,
                      tiers={"1.2.3.4:1": "prefill"})
        with pytest.raises(ValueError):
            bad.validate()
        with pytest.raises(ValueError):
            c.assign_tiers(3)  # would leave the decode pool empty

    def test_ship_kv_rows_rotation(self):
        """The in-mesh ship path: every leaf lands on the rank offset
        ahead (the ppermute lowering off-TPU, bit-identical contract)."""
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from kungfu_tpu.ops.kv_ship import ship_kv_rows

        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
        x = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4)

        def body(rows):
            return ship_kv_rows({"k": jnp.squeeze(rows, 0)}, "dp", 1)["k"][None]

        out = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                        check_vma=False)(x)
        np.testing.assert_array_equal(np.asarray(out)[0], np.asarray(x)[1])
        np.testing.assert_array_equal(np.asarray(out)[1], np.asarray(x)[0])

    def test_tiered_autoscaler_grows_the_right_pool(self):
        from kungfu_tpu.elastic.config_client import ConfigClient
        from kungfu_tpu.elastic.config_server import ConfigServer
        from kungfu_tpu.plan import Cluster, HostList
        from kungfu_tpu.serving.disagg import TieredAutoscaler

        cluster = Cluster.from_hostlist(
            HostList.parse("127.0.0.1:6"), 3).assign_tiers(1)
        srv = ConfigServer(host="127.0.0.1", port=0, init=cluster).start()
        try:
            class _R:
                completed = 0

                def __init__(self, comp):
                    self._comp = comp

                def queue_composition(self):
                    return self._comp

                def active_requests(self):
                    return 0

                def healthy_count(self):
                    return 99  # all-healthy: idle veto stays out of the way

            # prefill-bound backlog: queued prompt tokens dominate
            client = ConfigClient(srv.url)
            r = _R({"depth": 8, "prefill_tokens": 4000, "decode_tokens": 10})
            s = TieredAutoscaler(client, r, max_size=6, up_after=1)
            s._tick()
            got, _ = client.poll_cluster()
            assert got.tier_counts() == {"prefill": 2, "decode": 2}
            # decode-bound backlog grows the decode pool
            r2 = _R({"depth": 8, "prefill_tokens": 10,
                     "decode_tokens": 4000})
            s2 = TieredAutoscaler(client, r2, max_size=6, up_after=1)
            s2._tick()
            got, _ = client.poll_cluster()
            assert got.tier_counts() == {"prefill": 2, "decode": 3}
            # sustained idle shrinks (never below 1 per pool)
            r3 = _R({"depth": 0, "prefill_tokens": 0, "decode_tokens": 0})
            r3.completed = 5
            s3 = TieredAutoscaler(client, r3, max_size=6, down_after=1)
            for _ in range(4):
                s3._tick()
            got, _ = client.poll_cluster()
            counts = got.tier_counts()
            assert counts["prefill"] >= 1 and counts["decode"] >= 1
            assert sum(counts.values()) < 5
            kinds = [e["kind"] for e in s.events + s2.events + s3.events]
            assert "scale_up" in kinds and "scale_down" in kinds
            assert all("tier" in e for e in s.events + s2.events + s3.events)
        finally:
            srv.stop()

    def test_crash_serve_tier_grammar(self):
        from kungfu_tpu.chaos.inject import ChaosInjector
        from kungfu_tpu.chaos.plan import parse_fault_plan

        plan = parse_fault_plan("crash_serve@tokens=8:tier=prefill:rank=-1")
        (f,) = plan.serve_faults()
        assert (f.tokens, f.tier, f.rank) == (8, "prefill", -1)
        with pytest.raises(ValueError):  # tier must be a real pool
            parse_fault_plan("crash_serve@tokens=8:tier=bogus:rank=0")
        with pytest.raises(ValueError):  # rank=-1 needs a tier filter
            parse_fault_plan("crash_serve@tokens=8:rank=-1")
        exits = []
        inj = ChaosInjector(plan, exit_fn=exits.append)
        inj.on_serve_tokens(9, rank=0, tier="decode")  # wrong tier
        assert exits == []
        inj.on_serve_tokens(9, rank=3, tier="prefill")  # any rank, right tier
        assert exits == [45]
        inj.on_serve_tokens(20, rank=3, tier="prefill")  # one-shot
        assert exits == [45]


# -- chaos grammar ---------------------------------------------------------------------


class TestCrashServeFault:
    def test_parse(self):
        from kungfu_tpu.chaos.plan import parse_fault_plan

        plan = parse_fault_plan("crash_serve@tokens=24:rank=1")
        (f,) = plan.serve_faults()
        assert (f.tokens, f.rank, f.code) == (24, 1, 45)
        assert not plan.worker_faults()

    def test_parse_rejects_malformed(self):
        from kungfu_tpu.chaos.plan import parse_fault_plan

        with pytest.raises(ValueError):
            parse_fault_plan("crash_serve@rank=1")  # missing tokens=
        with pytest.raises(ValueError):
            parse_fault_plan("crash_serve@tokens=5:rank=1:code=0")

    def test_injector_fires_once_at_threshold(self):
        from kungfu_tpu.chaos.inject import ChaosInjector
        from kungfu_tpu.chaos.plan import parse_fault_plan

        exits = []
        inj = ChaosInjector(parse_fault_plan("crash_serve@tokens=10:rank=1"),
                            exit_fn=exits.append)
        inj.on_serve_tokens(9, rank=1)
        assert exits == []
        inj.on_serve_tokens(10, rank=0)  # wrong rank
        assert exits == []
        inj.on_serve_tokens(10, rank=1)
        inj.on_serve_tokens(11, rank=1)
        assert exits == [45]  # one-shot


# -- config server /health -------------------------------------------------------------


class TestConfigHealth:
    def test_health_endpoint_and_client(self):
        from kungfu_tpu.elastic.config_client import ConfigClient
        from kungfu_tpu.elastic.config_server import ConfigServer
        from kungfu_tpu.plan import Cluster, HostList

        cluster = Cluster.from_hostlist(HostList.parse("127.0.0.1:4"), 2)
        srv = ConfigServer(host="127.0.0.1", port=0, init=cluster).start()
        try:
            client = ConfigClient(srv.url)
            h = client.get_health()
            # single replica: leader of epoch 1 from the first request on
            # (docs/fault_tolerance.md "Replicated control plane")
            assert h == {"ok": True, "version": 0, "size": 2,
                         "cleared": False, "role": "leader",
                         "replica": 0, "leader_epoch": 1}
            assert client.put_cluster(cluster.resize(3), version=0)
            h = client.get_health()
            assert (h["version"], h["size"]) == (1, 3)
        finally:
            srv.stop()

    def test_health_served_inside_flap_window(self):
        from kungfu_tpu.chaos.inject import ServerChaos
        from kungfu_tpu.chaos.plan import parse_fault_plan
        from kungfu_tpu.elastic.config_client import ConfigClient
        from kungfu_tpu.elastic.config_server import ConfigServer
        from kungfu_tpu.plan import Cluster, HostList

        chaos = ServerChaos(parse_fault_plan("flap@config_server=30s:after=0"))
        cluster = Cluster.from_hostlist(HostList.parse("127.0.0.1:2"), 2)
        srv = ConfigServer(host="127.0.0.1", port=0, init=cluster,
                           chaos=chaos).start()
        try:
            client = ConfigClient(srv.url, retries=0, retry_deadline_s=0.5)
            assert client.poll_cluster() is None  # document plane flapped
            h = client.get_health()  # liveness still answers
            assert h is not None and h["ok"]
        finally:
            srv.stop()


# -- autoscaler ------------------------------------------------------------------------


class _StubRouter:
    """Just enough router surface for the Autoscaler: a queue with depth(),
    an active-request count, and the served-traffic counter."""

    def __init__(self):
        self._depth = 0
        self.busy = 0
        self.completed = 0
        self.healthy = 99  # all-healthy fleet unless a test says otherwise
        self.queue = self

    def depth(self):
        return self._depth

    def active_requests(self):
        return self.busy

    def healthy_count(self):
        return self.healthy


class TestAutoscaler:
    def _scaler(self, srv, router, **kw):
        from kungfu_tpu.elastic.config_client import ConfigClient
        from kungfu_tpu.serving.router import Autoscaler

        kw.setdefault("min_size", 1)
        kw.setdefault("max_size", 3)
        kw.setdefault("hi_depth", 4)
        kw.setdefault("up_after", 2)
        kw.setdefault("down_after", 2)
        return Autoscaler(ConfigClient(srv.url), router, **kw)

    def _server(self, np=2):
        from kungfu_tpu.elastic.config_server import ConfigServer
        from kungfu_tpu.plan import Cluster, HostList

        cluster = Cluster.from_hostlist(HostList.parse("127.0.0.1:4"), np)
        return ConfigServer(host="127.0.0.1", port=0, init=cluster).start()

    def test_scale_up_after_sustained_depth(self):
        srv = self._server()
        try:
            router = _StubRouter()
            router._depth = 5
            scaler = self._scaler(srv, router)
            scaler._tick()  # streak 1: no commit yet
            assert not scaler.events
            scaler._tick()  # streak 2: commit
            assert [e["kind"] for e in scaler.events] == ["scale_up"]
            assert scaler.client.get_health()["size"] == 3
        finally:
            srv.stop()

    def test_scale_down_requires_served_traffic(self):
        srv = self._server()
        try:
            router = _StubRouter()
            scaler = self._scaler(srv, router)
            for _ in range(5):  # idle but never served: warming, not idle
                scaler._tick()
            assert not scaler.events
            router.completed = 7
            scaler._tick()
            scaler._tick()
            assert [e["kind"] for e in scaler.events] == ["scale_down"]
            assert scaler.client.get_health()["size"] == 1
        finally:
            srv.stop()

    def test_scale_down_vetoed_mid_heal(self):
        # a crashed worker's respawn is not yet healthy: the fleet is
        # healing, not idle — shrinking would scale away the exact peer the
        # supervisor is rebooting (and race its rank_rejoined record)
        srv = self._server()
        try:
            router = _StubRouter()
            router.completed = 7
            router.healthy = 1  # 2-worker document, 1 healthy: mid-heal
            scaler = self._scaler(srv, router)
            for _ in range(5):
                scaler._tick()
            assert not scaler.events
            router.healthy = 2  # victim rejoined: idle may now count
            scaler._tick()
            scaler._tick()
            assert [e["kind"] for e in scaler.events] == ["scale_down"]
        finally:
            srv.stop()

    def test_min_size_floor(self):
        srv = self._server(np=1)
        try:
            router = _StubRouter()
            router.completed = 1
            scaler = self._scaler(srv, router)
            for _ in range(6):
                scaler._tick()
            assert not scaler.events  # already at the floor
        finally:
            srv.stop()

    def test_lost_cas_race_retries(self):
        srv = self._server()
        try:
            router = _StubRouter()
            router._depth = 9
            scaler = self._scaler(srv, router, up_after=1)
            # another writer moves the document between health read and PUT
            real_poll = scaler.client.poll_cluster

            def racing_poll():
                got = real_poll()
                cluster, version = got
                # report a stale version so the conditional PUT loses
                return cluster, version - 1

            scaler.client.poll_cluster = racing_poll
            scaler._tick()
            assert not scaler.events  # lost the race, no event
            scaler.client.poll_cluster = real_poll
            scaler._tick()
            assert [e["kind"] for e in scaler.events] == ["scale_up"]
        finally:
            srv.stop()


class TestWeightedFairQueueProperty:
    """Seeded-thread property test for the WFQ that replaces FIFO when
    tenancy is configured (kungfu_tpu/serving/tenancy/scheduler.py): under
    concurrent producers, consumers, and requeues, no request is lost or
    double-served, and a fully backlogged queue serves token shares in
    weight order."""

    def _fixture(self, weights):
        import random

        from kungfu_tpu.serving.tenancy import (
            TenantRegistry, TenantSpec, WeightedFairQueue)

        specs = {t: TenantSpec(name=t, weight=w) for t, w in weights.items()}
        reg = TenantRegistry(specs=specs)
        q = WeightedFairQueue(capacity=4096, registry=reg)
        rng = random.Random(1234)
        # every tenant offers the SAME sequence of shapes, so offered token
        # volume is identical per tenant and shares are comparable
        shapes = [(rng.randint(1, 12), rng.randint(1, 16))
                  for _ in range(60)]
        reqs = []
        for i, (plen, new) in enumerate(shapes):
            for tenant in weights:
                reqs.append(Request(
                    req_id=f"{tenant}-{i}", prompt=tuple(range(1, plen + 1)),
                    max_new_tokens=new, tenant=tenant))
        rng.shuffle(reqs)
        return q, reqs

    @staticmethod
    def _cost(req):
        return max(1, len(req.prefill_tokens) + req.remaining_new_tokens)

    def test_backlogged_shares_follow_weights(self):
        q, reqs = self._fixture({"a": 1.0, "b": 2.0, "c": 4.0})
        for r in reqs:
            assert q.put(r)
        # with every tenant backlogged, an early service window splits
        # token shares ~1:2:4; count the first third of the total volume
        budget = sum(self._cost(r) for r in reqs) // 3
        shares = {"a": 0, "b": 0, "c": 0}
        while budget > 0:
            r = q.pop(timeout_s=0)
            shares[r.tenant] += self._cost(r)
            budget -= self._cost(r)
        assert shares["c"] > shares["b"] > shares["a"]
        assert shares["c"] >= 2.5 * shares["a"]
        # no starvation: the weight-1 tenant was served inside the window
        assert shares["a"] > 0

    def test_seeded_threads_no_loss_no_double_serve(self):
        import random

        q, reqs = self._fixture({"a": 1.0, "b": 2.0, "c": 4.0})
        served = []
        lock = threading.Lock()
        requeued_once = set()
        stop = threading.Event()

        def producer(seed, chunk):
            rng = random.Random(seed)
            for req in chunk:
                assert q.put(req)
                if rng.random() < 0.2:
                    time.sleep(0.0005)

        def consumer(seed):
            rng = random.Random(seed)
            while not stop.is_set():
                req = q.pop(timeout_s=0.02)
                if req is None:
                    continue
                with lock:
                    first_bounce = req.req_id not in requeued_once
                    if first_bounce:
                        requeued_once.add(req.req_id)
                if first_bounce and rng.random() < 0.15:
                    q.requeue(req)  # failover path: keeps the fair tag
                    continue
                with lock:
                    served.append(req)

        producers = [threading.Thread(target=producer,
                                      args=(100 + i, reqs[i::4]))
                     for i in range(4)]
        consumers = [threading.Thread(target=consumer, args=(200 + i,))
                     for i in range(3)]
        for t in producers + consumers:
            t.start()
        for t in producers:
            t.join(timeout=30)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with lock:
                if len(served) == len(reqs):
                    break
            time.sleep(0.01)
        stop.set()
        for t in consumers:
            t.join(timeout=10)
        ids = [r.req_id for r in served]
        assert len(ids) == len(reqs), f"lost {len(reqs) - len(ids)} requests"
        assert len(set(ids)) == len(ids), "a request was double-served"
        assert q.depth() == 0
        requeued = [r for r in served if r.requeues > 0]
        assert requeued, "the seeded mix never exercised the requeue path"


# -- resident parameters ---------------------------------------------------------------
#
# models/transformer.py `resident_params`: the engine keeps each weight in the
# dtype its programs read it in.  The guard is bit equality on the CPU, where
# a float32 matmul is a float32 matmul: a leaf narrowed that something reads
# in float32 changes the logits here.  Dense model, tied head, q/k/v biases;
# tests/unit/test_moe.py holds the same for sparse experts, QK-norm and an
# untied head.


def _floats(tree):
    return {jax.tree_util.keystr(path): str(leaf.dtype)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def bf16_tied():
    from kungfu_tpu.models.transformer import resident_params
    from kungfu_tpu.serving.worker import seed_params

    cfg = _cfg(dtype=jnp.bfloat16, tie_embeddings=True, ffn="swiglu",
               attention_bias=True)
    params = seed_params(cfg, 11)
    return cfg, params, resident_params(cfg, params)


def decode_mode_logits(cfg, params, toks, n_prompt):
    """(prefill logits, one-token step logits) of the decode-mode model over
    a fresh cache: what the engine's `_prefill` and `_decode` trace."""
    model = TransformerLM(dataclasses.replace(cfg, decode=True))
    state = jax.eval_shape(model.init, jax.random.PRNGKey(0), toks[:, :1])
    state = {col: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tree)
             for col, tree in state.items() if col != "params"}
    prefill, st = model.apply({"params": params, **state}, toks[:, :n_prompt],
                              mutable=list(state))
    step, _ = model.apply({"params": params, **st},
                          toks[:, n_prompt:n_prompt + 1], mutable=list(state))
    return prefill, step


@pytest.mark.parametrize("program", ["forward", "prefill", "decode_step"])
def test_resident_tree_gives_the_same_logits_bit_for_bit(bf16_tied, program):
    cfg, params, resident = bf16_tied
    toks = jnp.asarray(np.random.RandomState(2).randint(0, 64, (3, 9)), jnp.int32)
    if program == "forward":
        run = lambda p: TransformerLM(cfg).apply({"params": p}, toks)  # noqa: E731
    else:
        i = ("prefill", "decode_step").index(program)
        run = lambda p: decode_mode_logits(cfg, p, toks, 8)[i]  # noqa: E731
    want, got = run(params), run(resident)
    assert want.dtype == got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_resident_params_narrow_what_is_read_through_a_conversion(bf16_tied):
    from kungfu_tpu.models.transformer import resident_params

    cfg, params, resident = bf16_tied
    assert set(_floats(params).values()) == {"float32"}  # the checkpoint form
    for name, dtype in _floats(resident).items():
        dense = any(f"['{m}']['{leaf}']" in name
                    for m in ("q", "k", "v", "out", "in", "gate")
                    for leaf in ("kernel", "bias"))
        # every _dense kernel and bias; not the scales, not the tied table
        assert dtype == ("bfloat16" if dense else "float32"), name
    assert _floats(resident)["['block_0']['attn']['q']['bias']"] == "bfloat16"
    assert _floats(resident)["['embed']['embedding']"] == "float32"
    # idempotent: the resident tree comes back leaf for leaf
    again = resident_params(cfg, resident)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(resident)))
    # numpy leaves (a buddy's blob) and shapes (a compile rehearsal) alike
    host = resident_params(cfg, jax.tree.map(np.asarray, params))
    shapes = resident_params(cfg, jax.eval_shape(lambda: params))
    assert _floats(host) == _floats(shapes) == _floats(resident)
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(resident)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resident_params_leave_a_float32_configuration_alone(model_and_params):
    from kungfu_tpu.models.transformer import resident_params

    cfg, _, params = model_and_params
    assert resident_params(cfg, params) is params
    eng = ServingEngine(cfg, params, slots=1, prefill_buckets=(8,))
    assert eng.params is params
    assert eng.stats()["param_bytes"] == {"float32": sum(
        a.size * 4 for a in jax.tree.leaves(params))}


def test_resident_params_donate_spends_the_tree_it_is_given():
    """The worker's use: the rung's float32 tree is nobody else's, so each
    leaf goes as its narrower copy arrives.  Without `donate` the caller's
    tree is untouched."""
    from kungfu_tpu.models.transformer import resident_params
    from kungfu_tpu.serving.worker import seed_params

    cfg = _cfg(dtype=jnp.bfloat16, tie_embeddings=True)
    params = seed_params(cfg, 1)
    kept = resident_params(cfg, params)
    assert not any(a.is_deleted() for a in jax.tree.leaves(params))
    spent = resident_params(cfg, params, donate=True)
    assert _floats(spent) == _floats(kept)
    for (path, old), new in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                                jax.tree.leaves(spent)):
        assert old.is_deleted() == (new.dtype == jnp.bfloat16), path
        assert new is old or old.is_deleted()


@pytest.mark.parametrize("mode", ["decode", "training"])
@pytest.mark.parametrize("stored", ["float32", "bfloat16"])
def test_embedding_lookup_equals_nn_embed(stored, mode):
    """What the lookup hands the blocks is what `nn.Embed(dtype=bfloat16)`
    gave on every call, bit for bit: in decode mode rows gathered and then
    converted (no table-sized conversion in the program), in training the
    table converted and then gathered, as before."""
    cfg = _cfg(dtype=jnp.bfloat16, n_layers=1, vocab_size=80,
               decode=mode == "decode")
    table = jax.random.normal(jax.random.PRNGKey(4), (80, 32)).astype(stored)
    toks = jnp.asarray(np.random.RandomState(6).randint(0, 80, (2, 7)), jnp.int32)
    old = nn.Embed(80, 32, dtype=jnp.bfloat16).apply(
        {"params": {"embedding": table}}, toks)
    model = TransformerLM(cfg)
    state = nn.meta.unbox(model.init(jax.random.PRNGKey(0), toks))
    state["params"]["embed"]["embedding"] = table
    seen = []

    def watch(next_fun, args, kwargs, context):
        if context.module.name == "block_0":  # what the lookup hands the blocks
            seen.append(args[0])
        return next_fun(*args, **kwargs)

    def run(variables):
        with nn.intercept_methods(watch):
            return model.apply(variables, toks, mutable=["cache"])

    run(state)
    assert old.dtype == seen[0].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(seen[0], np.float32),
                                  np.asarray(old, np.float32))
    converts = [e for e in jax.make_jaxpr(run)(state).jaxpr.eqns
                if e.primitive.name == "convert_element_type"
                and e.invars[0].aval.shape == (80, 32)]
    assert len(converts) == (1 if (mode, stored) == ("training", "float32") else 0)


def _worker(monkeypatch, model_json, **kw):
    import argparse

    from kungfu_tpu.monitor import counters as C
    from kungfu_tpu.serving.worker import ServingWorker

    counters = C.Counters()
    monkeypatch.setattr(C, "counters_if_enabled", lambda: counters)
    args = argparse.Namespace(
        host="127.0.0.1", port=0, launch_rank=0, incarnation=0,
        config_server="", preset="tiny", model_json=model_json, tier="",
        prefix_cache="off", spec_draft="", spec_k=4, slots=2,
        queue_capacity=8, seed=3, weights_file="", warm_ship_s=0.15,
        buddy_timeout_s=3.0, request_timeout_s=30.0)
    for k, v in kw.items():
        setattr(args, k, v)
    return ServingWorker(args)


def test_every_installation_path_ends_in_the_same_resident_form(monkeypatch):
    """The seed rung, the constructor, a reload and a buddy's /weights blob
    hold the same dtypes and serve the same tokens; the blob carries bf16
    leaves there and back; `seed_params` stays the float32 checkpoint form."""
    from kungfu_tpu.resilience.buddy import unpack_snapshot
    from kungfu_tpu.serving.worker import build_config, seed_params

    model_json = '{"dtype": "bfloat16", "tie_embeddings": true}'
    worker = _worker(monkeypatch, model_json)
    cfg = build_config("tiny", model_json)
    f32 = seed_params(cfg, 3)
    assert set(_floats(f32).values()) == {"float32"}

    built = ServingEngine(cfg, f32, slots=2)
    reloaded = ServingEngine(cfg, seed_params(cfg, 4), slots=2)
    reloaded.set_params(f32)
    snap = unpack_snapshot(np.frombuffer(worker._weights(), np.uint8))
    blob = snap["state"]["params"]
    assert all(isinstance(a, np.ndarray) for a in jax.tree.leaves(blob))
    assert _floats(blob) == _floats(worker.engine.params)  # bf16 round trip
    for a, b in zip(jax.tree.leaves(blob), jax.tree.leaves(worker.engine.params)):
        np.testing.assert_array_equal(a, np.asarray(b))
    buddy = ServingEngine(cfg, blob, slots=2)

    engines = [worker.engine, built, reloaded, buddy]
    assert "bfloat16" in set(_floats(built.params).values())
    tokens_of = []
    for eng in engines:
        assert _floats(eng.params) == _floats(built.params)
        assert eng.param_bytes == built.param_bytes
        pend = eng.submit(Request(prompt=(5, 17, 42, 7), max_new_tokens=8))
        eng.run_until_idle()
        tokens_of.append(tuple(pend.result.tokens))
    assert len(set(tokens_of)) == 1 and reloaded.params_version == 1


def test_self_draft_shares_the_engines_resident_tree(monkeypatch):
    worker = _worker(monkeypatch, '{"dtype": "bfloat16"}', spec_draft="same")
    assert all(a is b for a, b in zip(jax.tree.leaves(worker.engine.spec.params),
                                      jax.tree.leaves(worker.engine.params)))
    assert "bfloat16" in set(_floats(worker.engine.params).values())


def test_worker_reports_resident_bytes_by_dtype(monkeypatch):
    """`kft_serve_param_bytes{dtype=...}` on /metrics (a gauge), the same in
    /healthz's `param_bytes`: set at installation from the leaves' shapes."""
    worker = _worker(monkeypatch, '{"dtype": "bfloat16"}')
    held = worker.engine.stats()["param_bytes"]
    want = {}
    for leaf in jax.tree.leaves(worker.engine.params):
        want[str(leaf.dtype)] = want.get(str(leaf.dtype), 0) + leaf.nbytes
    assert held == want and set(held) == {"bfloat16", "float32"}
    text = worker.counters.prometheus_text()
    assert "# TYPE kft_serve_param_bytes gauge" in text
    for name, n in held.items():
        assert f'kft_serve_param_bytes{{dtype="{name}"}} {n}' in text


def test_worker_reports_decode_attn_rows(monkeypatch):
    """`kft_serve_decode_attn_rows_total{kind=...}` on /metrics (a counter)
    is the engine's `decode_attn_rows`, and `kft_serve_decode_rows_total`
    its `decode_rows`, which a profile capture reads at both ends through
    the same source; a speculative round counts its k query rows a slot
    like a decode step its one."""
    worker = _worker(monkeypatch, "", spec_draft="same", slots=1)
    eng = worker.engine
    assert set(eng.decode_attn_rows().values()) == {0}
    eng.submit(Request(prompt=(1, 2, 3), max_new_tokens=9))
    eng.run_until_idle()
    assert worker.counters.events().get("spec_rounds", 0) >= 1
    rows = eng.decode_attn_rows()
    steps = worker.counters.hist_summaries()["tok_latency_ms"][""]["count"]
    assert rows["cache"] == steps * eng.dcfg.max_len
    # no kernels on this backend: every step read the whole cache
    assert rows["fetched"] == rows["cache"] and rows["fetched_free"] == 0
    assert rows["written_free"] == 0  # its one slot is busy in every step
    # a lone request: 3 + 9 - 1 rows stand written after its last step
    assert 0 < rows["written"] <= steps * 11
    text = worker.counters.prometheus_text()
    assert "# TYPE kft_serve_decode_attn_rows_total counter" in text
    for kind, n in rows.items():
        assert f'kft_serve_decode_attn_rows_total{{kind="{kind}"}} {n}' in text
    families = worker.counters.source_families()
    assert families["kft_serve_decode_attn_rows_total"]['kind="cache"'] \
        == rows["cache"]
    # slot-steps by what the slot held: the same source, /metrics and stats()
    assert eng.stats()["decode_rows"] == {"live": steps, "free": 0}
    assert "# TYPE kft_serve_decode_rows_total counter" in text
    assert f'kft_serve_decode_rows_total{{kind="live"}} {steps}' in text
    assert families["kft_serve_decode_rows_total"] == {
        'kind="live"': steps, 'kind="free"': 0}


def test_worker_reports_decode_steps(monkeypatch):
    """`kft_serve_decode_steps_total{kind=...}` on /metrics (a counter) is
    the engine's `decode_steps`, through the one source a profile capture
    reads at both ends; `stats()` (so /healthz) holds the same."""
    worker = _worker(monkeypatch, "", slots=2)
    eng = worker.engine
    assert eng.decode_steps() == {"ahead": 0, "synced": 0, "wasted_rows": 0}
    eng.submit(Request(prompt=(1, 2, 3), max_new_tokens=9))
    eng.run_until_idle()
    ran = eng.decode_steps()
    assert ran == {"ahead": 7, "synced": 1, "wasted_rows": 0}
    # the worker's weights and the step's own tokens are one kind of array
    # to `jit`: the zeros of the first call added no second executable
    assert eng._decode._cache_size() == 1
    text = worker.counters.prometheus_text()
    assert "# TYPE kft_serve_decode_steps_total counter" in text
    assert "# HELP kft_serve_decode_steps_total " in text
    for kind, n in ran.items():
        assert f'kft_serve_decode_steps_total{{kind="{kind}"}} {n}' in text
    assert worker.counters.source_families()[
        "kft_serve_decode_steps_total"] == {
            f'kind="{kind}"': n for kind, n in ran.items()}
    assert eng.stats()["decode_steps"] == ran


# -- program observatory regression ----------------------------------------------------


class TestSignatureStability:
    def test_radix_admissions_compile_count_constant_after_warmup(
            self, model_and_params, monkeypatch):
        """PR-14's recompile bug as a registry invariant: prompts of 8
        DISTINCT lengths admitted through the radix prefix cache + bucket
        padding must reuse the same compiled programs — after the warm
        wave, repeating the exact traffic adds ZERO new signatures, decode
        stays at its single promised program, and the engine's declared
        budgets hold (kungfu_tpu.monitor.programs)."""
        from kungfu_tpu.monitor import programs as P

        cfg, _, params = model_and_params
        monkeypatch.delenv("KFT_PROGRAMS", raising=False)  # observatory on
        monkeypatch.delenv("KFT_SIG_BUDGET", raising=False)
        P._reset_for_tests()
        try:
            eng = ServingEngine(cfg, params, slots=2, prefill_buckets=(8, 16))
            base = tuple(range(1, 17))

            def wave():
                # shared prefixes of 8 distinct lengths straddling both
                # buckets: radix hits vary the UNCACHED remainder per admit
                pend = [eng.submit(Request(prompt=base[:n], max_new_tokens=3))
                        for n in (2, 4, 6, 8, 10, 12, 14, 16)]
                eng.run_until_idle()
                assert all(p.result.status == "ok" for p in pend)

            wave()
            reg = P.global_registry()
            warm = reg.compiles_total()
            assert reg.signatures("serve.decode") == 1
            assert 1 <= reg.signatures("serve.prefill") <= 2
            wave()
            assert reg.compiles_total() == warm
            assert reg.check_budgets() == []
            rep = reg.report()["programs"]
            assert all(p["storms"] == 0 for p in rep.values())
        finally:
            P._reset_for_tests()


# -- multi-process drill ---------------------------------------------------------------


@pytest.mark.faults
@pytest.mark.slow
class TestServeDrill:
    def test_rank_kill_zero_drops_rejoin_and_autoscale(self):
        """The end-to-end serving contract on a real 2-rank CPU fleet: a
        crash_serve kill mid-stream, every request completes (0 dropped),
        the victim rejoins from buddy weights (journal rank_rejoined with
        recovery_rung=buddy), and scale-down + scale-up both commit."""
        from kungfu_tpu.serving.drill import run_serve_drill

        summary = run_serve_drill(np=2, timeout_s=300.0)
        assert summary["ok"], summary["failures"]
        assert summary["completed"] == summary["requests"]
        assert summary["requeued_requests"] >= 1
        assert summary["rejoin_rung"] == "buddy"
        assert summary["rejoin_restore_s"] < 1.0  # sub-second weight rejoin
        counts = summary["journal_event_counts"]
        assert counts.get("request_requeued", 0) >= 1
        assert counts.get("scale_down", 0) >= 1
        assert counts.get("scale_up", 0) >= 1

    @pytest.mark.parametrize("tier", ["prefill", "decode"])
    def test_tier_rank_kill_zero_drops(self, tier):
        """The disaggregated failover contract per pool: a prefill-rank or
        decode-rank crash mid-burst heals with zero dropped requests,
        bounded p99, and a tier-stamped rank_rejoined."""
        from kungfu_tpu.serving.drill import run_serve_drill

        summary = run_serve_drill(np=3, timeout_s=300.0, tier=tier)
        assert summary["ok"], summary["failures"]
        assert summary["completed"] == summary["requests"]
        counts = summary["journal_event_counts"]
        assert counts.get("request_requeued", 0) >= 1
        assert counts.get("rank_rejoined", 0) >= 1
