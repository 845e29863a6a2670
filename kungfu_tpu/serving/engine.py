"""Continuous-batching inference engine over the flagship transformer.

One engine = one model replica serving many concurrent requests through a
fixed-shape slot batch:

  * admission: requests queue in an `AdmissionQueue`; a free KV slot admits
    the oldest live request (deadline-expired ones are swept to rejection,
    never wedged)
  * prefill: the request's tokens run through a batch-1 decode-mode forward,
    padded RIGHT to the nearest bucket length — causal attention makes the
    padding invisible to real positions, so bucketing costs zero accuracy
    and bounds the compile count to len(buckets).  The resulting cache row
    is grafted into the big cache at the slot (slots.write_slot), cursor set
    to the TRUE length
  * decode: one fixed-shape [slots, 1] step advances every active slot one
    token.  No recompile ever happens after warmup: the decode program is
    a single (shape, dtype) signature regardless of the request mix.  The
    step updates the slot cache it is given (donated) and the host reads
    [slots] greedy token ids; the [slots, vocab] logits leave the device
    only in a step where a request samples (`decode_logit_fetches`)
    On TPU the step's attention reads each slot's cache up to its cursor
    (ops/decode_attn.py); `decode_attn_rows` says how many rows that was
  * free slots: a slot with no request holds the token id FREE (-1) in the
    [slots, 1] upload, and that is how the step program knows: it hands
    the model `live = token >= 0` (models/transformer.py), so a free row's
    cursor stays at 0 on the device (the host's mirror does the same), its
    attention reads the first block of its slot and nothing more, and no
    expert is read for it.  The row still fills its place in the fixed
    shape and its outputs are ignored.  `decode_rows` says how many
    slot-steps were of each kind
  * one step ahead: a greedy decode-only iteration dispatches step N+1
    before it reads step N's tokens.  A slot whose next input is step N's
    own output holds CARRY (-2) in the upload, and the step program takes
    that row's token from step N's [slots] tokens on the device, so the
    chip works on N+1 while the host reads N, pushes its tokens and
    finishes requests.  Whatever needs the host to have seen the last
    token first reads the step in flight (`_drain`): an admission, a
    preemption, a speculative round, a sampling request, `set_params`,
    `in_flight`, `prefill_only`.  `decode_steps` says how often it ran
    ahead
  * completion: a slot frees on max_new_tokens or eos; its row is reused by
    the next admission (slots.reset_slot puts the free row's cursor at 0,
    where it stays)

Serving v2 composes three multipliers onto that loop, each at bit-identical
greedy output (docs/serving.md):

  * prefix reuse (`prefix_cache=` — serving/prefix.py): admission matches
    the request's tokens against the radix KV cache and prefills only the
    un-cached SUFFIX from a warm batch-1 cache (cursor = hit length); the
    same bucketed prefill programs serve warm and cold starts, so the
    compile count is unchanged
  * speculative decoding (`spec=` — serving/spec.py): a draft model
    proposes, the target verifies k tokens in ONE [slots, k] forward — the
    single new compiled decode signature — and per-slot accept cursors roll
    back through `slots.set_cursors`.  A drafter that is part of the target
    (`spec.reads_hidden`: the model's own prediction module) is handed the
    target's final hidden states: the prefill and verify programs built
    for it return them beside what they always return, on the device
  * disaggregation (serving/disagg.py): `prefill_only` runs the prefill
    half with no slot at all (the prefill-tier surface), and
    `submit_prefilled` admits shipped KV rows straight into a slot with no
    local prefill (the decode-tier surface)

What a decode-mode model counts on the device (the "moe_stats" collection
of a model with sparse experts, parallel/moe.py) rides beside the slot
cache: `_decode` and `_verify` take it donated and hand it back updated,
nothing else touches it, and `device_counters()` is its one read, made when
a scrape asks.  The engine knows no more of the model than that.

The parameters the engine holds are the resident form of the tree it is
given (models/transformer.py `resident_params`): each leaf in the dtype
the programs read it in, converted once at `__init__` / `set_params`, so no
step converts a whole weight again.  `param_bytes` says what that is.

A model with recurrent layers (state-space mixers, `cfg.mamba_d_state`)
keeps, for a slot, a state beside the attention layers' rows: leaves of the
one slot cache with no position axis (serving/slots.py `STATE_LEAVES`).  The
loop above is the same: an admission's `write_slot` replaces the slot's
state with the prefill's, a free slot's row is not live and keeps its state,
and the prefill program tells the model how many of the bucket's tokens are
real (`n_new`), because a recurrence would run through the padding that
attention cannot see.  What slices a cache by position or rolls a cursor
back is refused when the engine is made (`prefix_cache`, `spec`) or called
(`submit_prefilled`, `prefill_only`), with the reason; a preempted request
resumes through a cold prefill of its folded tokens.  `cache_bytes` says
what the cache holds by kind, `scan_tokens` how many tokens the scan walked.
Linear-attention layers ("lightning-attn" in `cfg.mixer_types`) are the
same to the loop: a matrix state a slot, counted by `scan_tokens` too.
Block-selected layers ("minicpm4") keep compressed keys at a stride of
their own beside their rows (slots.py `STRIDED_LEAVES`), refused the same
things; `sparse_rows` says what their decode steps held, fetched and scored.

The per-slot cache cursors this relies on live in models/transformer.py
(decode mode).  The int8 KV-cache storage dtype comes straight from the
model config (`kv_cache_dtype="int8"`): the serving cache stores quantized
bytes + scales exactly as the training-side decode bench does.

Sharded serving: pass `mesh` (and optionally `rules`) to place the params
under the parallel/sharding.py rules table (Megatron tp for q/k/v/mlp) —
the KV cache inherits the head sharding through GSPMD, pinned explicitly by
parallel.sharding.decode_cache_shardings.  Long-context sequence-parallel
serving (ring/ulysses) shards the cache's max_len axis instead; see
docs/serving.md for the trade-off.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import (
    TransformerConfig,
    TransformerLM,
    resident_params,
)
from ..monitor.journal import journal_event
from ..ops.decode_attn import (kernel_block, live_blocks, visible_kernels,
                               visits)
from ..utils import get_logger
from ..utils.trace import (
    BOOT_CAT,
    TraceContext,
    child_span,
    trace_context,
    trace_scope,
)
from .queue import AdmissionQueue
from .request import Request, Result
from .slots import (
    SlotManager,
    cache_bytes,
    extract_rows,
    extract_slot_rows,
    has_state,
    reset_slot,
    warm_small_cache,
    write_slot,
)
from .tenancy import TenantRegistry, WeightedFairQueue

log = get_logger("kungfu.serving")

#: the token id a free slot holds in the upload of a slot-cache step: no
#: token in this row.  The step programs read liveness from it
FREE = -1
#: the token id of a slot whose next input is the output of the decode step
#: in flight: the decode program takes that row from the step's own tokens
CARRY = -2


def default_buckets(max_len: int, lo: int = 16) -> Tuple[int, ...]:
    """Powers of two from `lo` up to (and always including) max_len."""
    out: List[int] = []
    b = lo
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


@dataclasses.dataclass
class _Step:
    """A decode step on the device whose tokens the host has not read."""

    greedy: Any                       # [slots] int32, on the device
    logits: Any                       # [slots, vocab] float32, on the device
    rows: List[Tuple[int, Request]]   # the (slot, request) it was sent for
    sampling: bool                    # a row of it draws from the logits
    t0: float                         # monotonic, just before its dispatch


class _Pending:
    """Handle returned by submit(); worker HTTP threads block on wait()."""

    def __init__(self, req: Request):
        self.request = req
        self._done = threading.Event()
        self.result: Optional[Result] = None

    def _finish(self, result: Result) -> None:
        self.result = result
        self._done.set()

    def wait(self, timeout_s: Optional[float] = None) -> Optional[Result]:
        self._done.wait(timeout_s)
        return self.result


class ServingEngine:
    @trace_scope("boot:engine", cat=BOOT_CAT)  # the slot cache and the programs
    def __init__(
        self,
        cfg: TransformerConfig,
        params: Any,
        slots: int = 4,
        queue_capacity: int = 64,
        prefill_buckets: Optional[Sequence[int]] = None,
        mesh=None,
        rules=None,
        counters=None,
        prefix_cache=None,
        spec=None,
        tenants: Optional[TenantRegistry] = None,
    ):
        assert cfg.rope or not cfg.pos_table, (
            "serving decode: rope positions from the cache cursors, or none")
        # decode overrides mirror generate(): full attention on the cache, a
        # dense head, GSPMD (not shard_map) sharding under `mesh`: there the
        # cache read stays the plain einsum GSPMD can split ("full"); on
        # one device it may be the length-aware kernel ("auto")
        self.dcfg = dataclasses.replace(
            cfg, decode=True, attention="full" if mesh is not None else "auto",
            mesh=None, head="dense"
        )
        self.model = TransformerLM(self.dcfg)
        self.n_slots = slots
        self.tenants = tenants
        if tenants is not None:
            # tenanted: weighted-fair slot admission + priority preemption
            self.queue = WeightedFairQueue(queue_capacity, registry=tenants)
        else:
            self.queue = AdmissionQueue(queue_capacity)
        self.slot_mgr = SlotManager(slots)
        self.preemptions = 0
        self.decode_logit_fetches = 0  # decode steps that fetched logits
        # cache rows the decode-step attention spans, holds and reads,
        # summed over steps (`_count_step`, `decode_attn_rows`)
        self._attn_rows = dict.fromkeys(
            ("cache", "written", "written_free", "fetched", "fetched_free"), 0)
        # slot-steps of the same steps by what the slot held (`decode_rows`)
        self._decode_rows = {"live": 0, "free": 0}
        # decode steps by what was in flight at their dispatch (`decode_steps`)
        self._decode_steps = {"ahead": 0, "synced": 0, "wasted_rows": 0}
        self.counters = counters
        self.buckets = tuple(sorted(prefill_buckets or default_buckets(cfg.max_len)))
        assert self.buckets[-1] <= cfg.max_len

        # the state a decode-mode model declares beside its parameters, all
        # of it zeros at init: the KV cache, and whatever else it counts on
        # the device (the experts' "moe_stats").  Taken from the shapes of
        # an abstract init, so no second set of parameters is ever made
        def zeros(batch: int) -> Tuple[Any, Dict[str, Any]]:
            shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0),
                                    jnp.zeros((batch, 1), jnp.int32))
            return shapes["params"], {
                col: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tree)
                for col, tree in shapes.items() if col != "params"}

        abstract_params, state = zeros(slots)
        self.cache = state.pop("cache")
        # device counters: carried (donated, updated) by the slot-cache
        # programs _decode and _verify only, read by device_counters()
        self._dev_counters = state
        self._dev_counters_host = jax.device_get(state)
        self._dev_lock = threading.Lock()
        self._small_cache0 = zeros(1)[1]["cache"]
        # bytes of the slot cache by kind (rows | state), from its shapes
        self.cache_bytes = cache_bytes(self.cache)
        # recurrent layers keep a state a slot: no position to cut it at
        self._stateful = has_state(self.cache)
        if self._stateful and (prefix_cache is not None or spec is not None):
            raise ValueError(
                "a model with recurrent state, or keys compressed at a "
                "stride of their own, serves with no prefix cache and no "
                "speculation: a state is the summary of every token so far, "
                "so a prefix hit would need a snapshot of it at the hit "
                "length and a rejected draft a way to roll it back "
                "(serving/slots.py STATE_LEAVES, STRIDED_LEAVES)")
        # tokens the recurrent layers' scan walked, a layer (`scan_tokens`)
        self._scan_tokens = {"prefill": 0, "decode": 0}
        # rows of the block-selected layers' decode steps (`sparse_rows`)
        self._sparse = self.dcfg.has_mixer("minicpm4")
        self._sparse_rows = {"written": 0, "fetched": 0, "kernels": 0}
        self._param_shardings = None
        if mesh is not None:
            from ..parallel.sharding import decode_cache_shardings, param_shardings

            self._param_shardings = param_shardings(mesh, abstract_params, rules)
            self.cache = jax.device_put(
                self.cache, decode_cache_shardings(mesh, self.cache)
            )
        self._install(params)

        # host-side per-slot decode state (fixed [slots] arrays)
        # FREE from a slot's release to its next admission; in between a
        # token (>= 0) the host knows, or CARRY while the slot's next input
        # is the output of the step in flight: what the step programs and
        # the mirror below both go by
        self._next_tok = np.full(slots, FREE, np.int32)
        # mirror of cache idx: as the device will hold it once every step
        # dispatched so far has run
        self._cursor = np.zeros(slots, np.int64)
        self._flight: Optional[_Step] = None  # dispatched, tokens not read
        self._read_t = 0.0                    # monotonic of the last read
        # results of a read made outside step() (set_params, in_flight,
        # prefill_only), handed out by the next step()
        self._read_early: List[Result] = []
        # what `_decode` takes for the step before when none is in flight:
        # no row of such an upload holds CARRY
        self._no_prev = jnp.zeros(slots, jnp.int32)
        self._rng = np.random.default_rng(0)
        self._pending: Dict[str, _Pending] = {}
        self._completed_lock = threading.Lock()
        self.total_tokens = 0      # generated tokens, engine lifetime
        self.total_prefill_tokens = 0  # prefilled tokens (prefill tier signal)
        self.total_completed = 0
        # serving v2 composition
        self.prefix = prefix_cache
        self.spec = spec
        # rows of one KV block of the attention in `_decode` (one query row
        # a slot) and `_verify` (k): asked as the model asks when it is
        # traced, of a leaf the attention reads by position (a layer's K,
        # or the one latent leaf of a latent-attention sublayer).  None is
        # the dense einsum: max_len rows a slot, whatever the slot holds
        leaf = next(x for path, x in
                    jax.tree_util.tree_leaves_with_path(self.cache)
                    if getattr(path[-1], "key", None)
                    in ("cached_k", "cached_latent"))
        # (block-selected layers read their rows through a kernel of their
        # own, counted by `sparse_rows`: `kft_decode_attn` is not what runs)
        self._attn_block = {
            rows: (None if self.dcfg.attention == "full" or self._sparse
                   else kernel_block(rows, leaf.shape, leaf.dtype))
            for rows in {1, spec.k if spec is not None else 1}}
        self._grafts: Dict[str, tuple] = {}  # req_id -> (meta, rows) shipped KV
        self.params_version = 0

        model = self.model
        # a target-resident drafter continues from the target's hidden
        # states: the prefill and verify programs then return them too.
        # Without one they are the programs they always were
        want_hidden = self._want_hidden = bool(
            getattr(spec, "reads_hidden", False))
        stateful = self._stateful
        self._prefill_hidden = None  # the last cold prefill's, for `_admit_to`

        def _run(variables, tokens, **kw):
            """(logits, hidden or None, the collections the call updated)."""
            out, st = model.apply(variables, tokens,
                                  return_hidden=want_hidden, **kw)
            return (*out, st) if want_hidden else (out, None, st)

        def _fix_cursor(cache, true_len):
            def fix(path, leaf):
                name = getattr(path[-1], "key", None)
                if name == "idx":
                    return jnp.full_like(leaf, true_len)
                if name == "overflowed":
                    return jnp.zeros_like(leaf)
                return leaf

            return jax.tree_util.tree_map_with_path(fix, cache)

        # Every step program hands the host its greedy tokens (argmax of
        # the float32 logits: first index on ties, a NaN first, as
        # np.argmax) beside the logits themselves, which stay on the device
        # unless a request samples.  _decode and _verify_accept update the
        # slot cache in place: it is donated, as slots.py's programs donate
        # it, so the caller rebinds self.cache on the line that passes it.

        @jax.jit
        def _prefill(params, cache_small, tokens, n_new, total_len):
            # tokens [1, bucket]; right-padding is causally invisible to the
            # real positions, so logits at n_new-1 are exact.  cache_small is
            # the zeroed template on a cold start, or a warm cache whose
            # cursor sits at the prefix-cache hit length — the forward reads
            # positions from the cursor, so ONE program serves both.
            # a recurrence runs through what it is given: a model with
            # state is told how many of the bucket's tokens are real
            real = {"n_new": jnp.reshape(n_new, (1,))} if stateful else {}
            logits, hidden, st = _run(
                {"params": params, "cache": cache_small}, tokens,
                mutable=["cache"], **real
            )
            last = jax.lax.dynamic_index_in_dim(
                logits, n_new - 1, axis=1, keepdims=False
            )[0].astype(jnp.float32)  # [V]
            first = jnp.argmax(last).astype(jnp.int32)
            out = first, last, _fix_cursor(st["cache"], total_len)
            return (*out, hidden) if want_hidden else out

        def _apply_slots(params, cache, counters, toks):
            """The model over the slot cache: (logits, cache, counters,
            live, hidden).  A slot whose first token is FREE holds no
            request: the model is told (`live`), and looks up token 0 for
            it."""
            live = toks[:, 0] >= 0
            logits, hidden, st = _run(
                {"params": params, "cache": cache, **counters},
                jnp.maximum(toks, 0), live=live,
                mutable=["cache", *counters]
            )
            return (logits, st["cache"], {c: st[c] for c in counters}, live,
                    hidden)

        @partial(jax.jit, donate_argnums=(1, 2))
        def _decode(params, cache, counters, toks, prev=None):
            # toks [slots, 1] — THE fixed decode signature; a free slot's
            # row holds FREE, does no work and its output is never read.
            # prev [slots]: the tokens of the step before, which a row that
            # holds CARRY takes its token from.  The engine always gives
            # it (one signature); a caller with no step before leaves it
            # out and gets the program without the select
            if prev is not None:
                toks = jnp.where(toks == CARRY, prev[:, None], toks)
            logits, cache, counters, _, _ = _apply_slots(
                params, cache, counters, toks)
            last = logits[:, -1].astype(jnp.float32)  # [slots, V]
            greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
            return greedy, last, cache, counters

        @partial(jax.jit, donate_argnums=(1, 2))
        def _verify_accept(params, cache, counters, toks, proposals):
            # toks [slots, k] — the ONE extra compiled decode signature of
            # speculative decoding: per-slot cursors make a k-token call
            # exactly k chained 1-token calls.  Greedy acceptance and the
            # per-slot cursor rollback fold into the same program: one
            # dispatch, one host sync per speculative round.
            k = toks.shape[1]
            logits, cache, counters, live, hidden = _apply_slots(
                params, cache, counters, toks)
            g = jnp.argmax(
                logits.astype(jnp.float32), axis=-1
            ).astype(jnp.int32)  # [slots, k]: the target's own greedy run
            ok = (proposals == g[:, : k - 1]).astype(jnp.int32)
            n_acc = jnp.cumprod(ok, axis=1).sum(axis=1)  # accepted prefix

            def roll(path, leaf):
                # the apply advanced every live cursor by k; committed
                # length is n_acc + 1 (accepted drafts + the correction
                # token).  A free slot's did not move and does not
                if getattr(path[-1], "key", None) == "idx":
                    return leaf - jnp.where(live, k - 1 - n_acc, 0).astype(
                        leaf.dtype)
                return leaf

            cache2 = jax.tree_util.tree_map_with_path(roll, cache)
            out = g, n_acc, cache2, counters
            # [slots, k, d_model]: the drafter picks the committed
            # positions' by n_acc, on the device
            return (*out, hidden) if want_hidden else out

        # the program observatory holds the engine to its own compile
        # promises: one prefill program per bucket, ONE decode signature,
        # ONE speculative-verify signature.  A blown budget journals
        # sig_budget_exceeded instead of raising — the registry is a
        # witness, not a gate.  Re-wrapping per engine resets each promise.
        from ..monitor.programs import track

        self._prefill = track("serve.prefill", _prefill,
                              budget=len(self.buckets))
        self._decode = track("serve.decode", _decode, budget=1)
        self._verify = track("serve.verify", _verify_accept, budget=1)

    # -- submission ----------------------------------------------------------------

    def submit(self, req: Request, _grafted: bool = False) -> _Pending:
        """Admit a request; raises ValueError when it can never fit, returns
        a handle whose wait() yields the Result.  A full queue raises
        BackpressureError — the HTTP layer's 503."""
        need = len(req.prefill_tokens) + req.remaining_new_tokens
        if need > self.dcfg.max_len:
            raise ValueError(
                f"request needs {need} cache rows > max_len={self.dcfg.max_len}"
            )
        if not _grafted and len(req.prefill_tokens) > self.buckets[-1]:
            raise ValueError("prompt longer than the largest prefill bucket")
        pending = _Pending(req)
        with self._completed_lock:
            self._pending[req.req_id] = pending
        if not self.queue.put(req):
            with self._completed_lock:
                del self._pending[req.req_id]
            raise BackpressureError(f"queue full ({self.queue.capacity})")
        self._gauge()
        return pending

    def submit_prefilled(self, req: Request, meta: dict,
                         rows: Dict[tuple, Any]) -> _Pending:
        """Admit a request whose prefill already ran on another rank: the
        shipped KV rows + first token graft straight into a slot when one
        frees (the decode-tier half of disaggregation).  Re-ships of an
        already-known request (a prefill rank died mid-wait and the retry
        re-shipped) return the existing handle — the double-serve guard."""
        self._rows_only("shipped KV")
        with self._completed_lock:
            existing = self._pending.get(req.req_id)
        if existing is not None:
            return existing
        self._grafts[req.req_id] = (dict(meta), rows)
        try:
            return self.submit(req, _grafted=True)
        except Exception:
            self._grafts.pop(req.req_id, None)
            raise

    # -- the scheduler iteration ---------------------------------------------------

    def step(self) -> List[Result]:
        """One continuous-batching iteration: reject expired, admit+prefill
        into free slots, one decode step for the batch.  Returns the
        requests completed during this iteration (and by a read made
        outside step() since the last one)."""
        done, self._read_early = self._read_early, []
        for req in self.queue.drain_expired():
            done.append(self._finish(req, status="expired"))
        if self.tenants is not None:
            done.extend(self._maybe_preempt())
        if self.slot_mgr.active_count or self.queue.depth():
            # the span marks an iteration with work in it; one that finds
            # nothing to do is the worker loop's `serve:idle`
            with trace_scope("serve:step", cat="serving"):
                done.extend(self._admit_and_decode())
        if not self.slot_mgr.active_count:
            # nothing left that a step in flight could be for: it holds
            # only rows of requests that ended while it was dispatched
            done.extend(self._drain())
        for req in self.queue.drain_expired():
            done.append(self._finish(req, status="expired"))
        self._gauge()
        return done

    def _admit_and_decode(self) -> List[Result]:
        done: List[Result] = []
        if self.slot_mgr.free_count and self.queue.depth():
            # an admission writes a slot of the cache and pushes a token
            # from the host: the host has to have seen the last step
            done.extend(self._drain())
        while self.slot_mgr.free_count:
            req = self.queue.pop()
            if req is None:
                break
            if req.expired():
                done.append(self._finish(req, status="expired"))
                continue
            self._admit(req)
        if self.slot_mgr.active_count:
            done.extend(self._decode_step())
        return done

    def run_until_idle(self, timeout_s: float = 120.0) -> List[Result]:
        """Drive step() until queue and slots drain (test/bench harness)."""
        t0 = time.monotonic()
        out: List[Result] = []
        while self.queue.depth() or self.slot_mgr.active_count:
            out.extend(self.step())
            if time.monotonic() - t0 > timeout_s:
                raise TimeoutError("engine did not drain")
        return out

    # -- internals -----------------------------------------------------------------

    def _maybe_preempt(self) -> List[Result]:
        """Priority preemption: when every slot is busy and the queue's next
        request outranks the lowest-priority in-flight request, evict that
        slot.  Eviction is cheap by construction — the victim's generated
        tokens fold into `prior_tokens` (greedy decode is deterministic, so
        the resumed stream is byte-identical) and its KV rows enter the
        radix prefix cache, making the eventual re-prefill a warm hit.  At
        most ONE preemption per request (the `_preempted` flag), so a
        starved class degrades to at-least-half progress, never livelock.
        An eviction folds the victim's tokens and reads its cache rows, so
        a step in flight is read first and the choice made again on what
        that left: -> the requests the read completed."""
        if self.slot_mgr.free_count or not self.queue.depth():
            return []
        head_prio = self.queue.head_priority()
        if head_prio is None:
            return []
        victim_slot, victim, victim_prio = None, None, None
        for slot, req in self.slot_mgr.active().items():
            folded = len(req.prefill_tokens) + len(req.generated)
            if folded > self.buckets[-1]:
                # the folded resume prefix must fit a prefill bucket (a
                # prefix-cache hit usually shrinks it, but eviction can't
                # be ruled out) — an unresumable victim is not a victim
                continue
            p = self.tenants.classify(req.tenant).priority
            if victim_prio is None or p < victim_prio:
                victim_slot, victim, victim_prio = slot, req, p
        if (victim is None or head_prio <= victim_prio
                or getattr(victim, "_preempted", False)):
            return []
        if self._flight is not None:
            return self._drain() + self._maybe_preempt()
        self._preempt(victim_slot, victim, head_prio)
        return []

    def _preempt(self, slot: int, req: Request, head_prio: int) -> None:
        cursor = int(self._cursor[slot])
        # fold progress into the warm-resume prefix.  The cache holds
        # prefill + generated - 1 rows (the newest token is still pending in
        # _next_tok), i.e. exactly `cursor` rows — the prefix-cache key must
        # match that row count, not the full folded stream
        req.prior_tokens = tuple(req.prior_tokens) + tuple(req.generated)
        req.generated = []
        if self.prefix is not None and cursor > 0:
            self.prefix.insert(
                tuple(req.prefill_tokens[:cursor]),
                lambda: extract_slot_rows(self.cache, slot, cursor))
        self.slot_mgr.release(slot)
        self._reset_slot(slot)
        req._preempted = True  # type: ignore[attr-defined]
        # re-tag as a fresh arrival: the victim already consumed service, so
        # keeping its old (minimal) fair tag would pop it straight back into
        # the slot it just vacated, ahead of the request that preempted it
        req._wfq_tag = None  # type: ignore[attr-defined]
        self.preemptions += 1
        self._count("slot_preempted")
        journal_event("slot_preempted", slot=slot, req_id=req.req_id,
                      tenant=req.tenant, for_priority=head_prio,
                      warm_tokens=len(req.prior_tokens),
                      trace_id=req.trace_id)
        self.queue.requeue(req, count=False)

    def _rows_only(self, what: str) -> None:
        if self._stateful:
            raise ValueError(
                f"{what} moves cache rows between ranks; this model keeps "
                "recurrent state beside its rows, which has no rows to move")

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"no prefill bucket fits {n} tokens")

    def _req_ctx(self, req: Request) -> Optional[TraceContext]:
        """This request's hop context (the dispatcher's route span — or the
        shipping rank's kv_ship span — is the parent), or None untraced."""
        if not req.trace_id:
            return None
        return TraceContext(req.trace_id, req.parent_span)

    def _admit(self, req: Request) -> None:
        slot = self.slot_mgr.allocate(req)
        assert slot is not None
        with trace_scope("serve:admit", cat="serving",
                         args={"slot": slot,
                               "tokens": len(req.prefill_tokens)}):
            self._admit_to(slot, req)

    def _admit_to(self, slot: int, req: Request) -> None:
        """From the allocated slot to the request's first token pushed."""
        ctx = self._req_ctx(req)
        if ctx is not None:
            child_span("queue:wait", req.queued_t, trace_id=ctx.trace_id,
                       parent_id=ctx.span_id, cat="serving",
                       args={"req_id": req.req_id, "slot": slot})
        if getattr(req, "_preempted", False):
            # the resume half of the preemption pair: the folded prefix
            # re-prefills (warm, via the rows _preempt inserted) and the
            # stream continues byte-identically
            journal_event("preempted_readmitted", slot=slot,
                          req_id=req.req_id, tenant=req.tenant,
                          warm_tokens=len(req.prior_tokens),
                          trace_id=req.trace_id)
        graft = self._grafts.pop(req.req_id, None)
        if graft is not None:
            self._admit_prefilled(slot, req, *graft)
            return
        toks = req.prefill_tokens
        with trace_context(ctx):
            first, small, total, hit = self._run_prefill(toks, req.temperature)
        with trace_scope("serve:slot_write", cat="serving",
                         args={"slot": slot}):
            self.cache = write_slot(self.cache, small, slot)
        self._cursor[slot] = total
        if self.spec is not None:
            # the prompt's hidden states, if a cold prefill just made them
            # and the drafter reads them (None otherwise: spec.py)
            self.spec.prefill_slot(slot, toks, self._prefill_hidden)
            self._prefill_hidden = None
        req.ttft_s = time.monotonic() - req.submitted_t
        req.decode_t0 = time.monotonic()
        self._observe("ttft_ms", req.ttft_s * 1e3)
        self._push_token(slot, req, int(first))

    def _run_prefill(self, toks, temperature: float):
        """The shared prefill: prefix-cache match -> warm/cold batch-1
        forward over the suffix bucket -> insert the new rows back into the
        radix tree.  Returns (first_token, small_cache, total_len, hit)."""
        total = len(toks)
        hit, lease = 0, None
        if self.prefix is not None:
            hit, lease = self.prefix.match(toks)
        suffix = toks[hit:]
        bucket = self._bucket_for(len(suffix))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(suffix)] = suffix
        with trace_scope("serve:prefill", cat="serving",
                         args={"tokens": total, "hit": hit,
                               "bucket": bucket}):
            t0 = time.monotonic()
            with trace_scope("serve:prefill.dispatch", cat="serving"):
                small_in = self._small_cache0
                if lease is not None:
                    # device-resident, memoized per (prefix, hit): repeat
                    # hits of a hot prefix skip the host assembly entirely
                    small_in = self.prefix.warm_small(self._small_cache0,
                                                      lease)
                greedy, last_logits, small, *hidden = self._prefill(
                    self.params, small_in, jnp.asarray(padded),
                    len(suffix), total,
                )
                # of the whole prompt only when nothing of it was cached
                self._prefill_hidden = hidden[0] if hidden and not hit else None
            if self.prefix is not None:
                # lazy rows: the device->host copy only happens when the
                # insert actually creates a node (cache-hot admissions skip)
                self.prefix.insert(tuple(toks),
                                   lambda: extract_rows(small, total))
            if lease is not None:
                lease.release()
            with trace_scope("serve:prefill.fetch", cat="serving"):
                if temperature <= 0.0:
                    first = int(greedy)
                else:
                    first = self._sample(np.asarray(last_logits), temperature)
            dt = time.monotonic() - t0
        self.total_prefill_tokens += len(suffix)
        if self._stateful:
            self._count_scan("prefill", len(suffix))
        self._observe("prefill_ms", dt * 1e3)
        return first, small, total, hit

    def prefill_only(self, req: Request):
        """The prefill-tier surface: run the (prefix-cache-aware) prefill
        with NO slot and return what the decode tier needs — the first
        token, the KV rows, and the cursor.  Raises ValueError exactly as
        submit() would on a request that can never fit."""
        self._rows_only("a prefill tier")
        need = len(req.prefill_tokens) + req.remaining_new_tokens
        if need > self.dcfg.max_len:
            raise ValueError(
                f"request needs {need} cache rows > max_len={self.dcfg.max_len}"
            )
        if len(req.prefill_tokens) > self.buckets[-1]:
            raise ValueError("prompt longer than the largest prefill bucket")
        # as before an admission's prefill (a prefill tier decodes nothing)
        self._read_early.extend(self._drain())
        toks = req.prefill_tokens
        with trace_context(self._req_ctx(req)):
            first, small, total, hit = self._run_prefill(toks, req.temperature)
        return int(first), extract_rows(small, total), total, hit

    def _admit_prefilled(self, slot: int, req: Request, meta: dict,
                         rows: Dict[tuple, Any]) -> None:
        """Graft shipped KV rows into `slot` (no local prefill): build the
        warm batch-1 cache and write it through the same compiled program a
        prefix hit uses."""
        total = int(meta["cursor"])
        first = int(meta["first_token"])
        t0 = time.monotonic()
        with trace_context(self._req_ctx(req)):
            with trace_scope("serve:kv_graft", cat="serving",
                             args={"tokens": total,
                                   "req_id": req.req_id}):
                small = warm_small_cache(self._small_cache0, rows, total)
                self.cache = write_slot(self.cache, small, slot)
        self._cursor[slot] = total
        if self.spec is not None:
            self.spec.prefill_slot(slot, req.prefill_tokens)
        # TTFT: the first token was produced on the prefill rank; local
        # queue wait still counts (submitted_t is decode-side receipt)
        req.ttft_s = time.monotonic() - req.submitted_t
        req.decode_t0 = time.monotonic()
        self._observe("ttft_ms", req.ttft_s * 1e3)
        self._observe("kv_graft_ms", (time.monotonic() - t0) * 1e3)
        self._push_token(slot, req, first)

    def _decode_step(self) -> List[Result]:
        """One iteration of the decode loop: -> the requests one step's
        tokens completed.  With step N in flight it dispatches step N+1
        (N's tokens go into it on the device) and then reads N; with
        nothing in flight it dispatches N first.  A step with a sampling
        row is read before anything follows it: its tokens are drawn on
        the host."""
        if self._spec_step_ok():
            # a round reads the host's tokens.  (No plain step is in flight
            # here as things are: one leaves every slot it advanced stale
            # for the drafter until an admission, which drains)
            return self._drain() + self._spec_decode_step()
        return self._read_step(run_ahead=True)

    def _drain(self) -> List[Result]:
        """Read the step in flight, if there is one, and dispatch nothing:
        -> the requests that completed.  After it the host has seen every
        token the device made, and the device's cursors are the host's."""
        if self._flight is None:
            return []
        return self._read_step(run_ahead=False)

    def _read_step(self, run_ahead: bool) -> List[Result]:
        """Read one decode step, the one in flight or else one dispatched
        here, under one `serve:decode` span; with `run_ahead` the next is
        dispatched before the read.  -> the requests completed."""
        active = sorted(self.slot_mgr.active().items())
        targs: Dict[str, Any] = {"active": len(active)}
        ids = [r.trace_id for _, r in active if r.trace_id]
        if ids:
            # batch-level span: one decode round serves many requests, so
            # it carries the traces it advanced as links instead of
            # belonging to one tree; the assembler counts it as a decode
            # round for each listed trace
            targs["trace_ids"] = ids
        with trace_scope("serve:decode", cat="serving", args=targs,
                         track=bool(ids)):
            step = self._flight or self._dispatch_decode(active, None)
            self._flight = (self._dispatch_decode(active, step)
                            if run_ahead and not step.sampling else None)
            fetched = self._fetch(step)
        return self._push(step, *fetched)

    def _dispatch_decode(self, active: List[Tuple[int, Request]],
                         prev: Optional[_Step]) -> Optional[_Step]:
        """Send one decode step for the `active` (slot, request) pairs to
        the device and move the host's mirrors to where it will leave the
        device: -> the step, unread.  With `prev` in flight a row it
        advanced holds CARRY, or FREE when the token in flight is the last
        its request asked for (the row does no work and the slot stays
        allocated until `prev` is read); when that leaves no live row
        nothing is dispatched: -> None."""
        # a copy: the upload may still be reading it when `_next_tok` moves
        toks = self._next_tok.copy()
        if prev is not None:
            toks[[s for s, r in prev.rows
                  if len(r.generated) + 1 >= r.remaining_new_tokens]] = FREE
        live = toks != FREE
        if not live.any():
            return None
        rows = [(s, r) for s, r in active if live[s]]
        with trace_scope("serve:decode.upload", cat="serving"):
            toks_dev = jnp.asarray(toks[:, None])
        t0 = time.monotonic()
        with trace_scope("serve:decode.dispatch", cat="serving"), \
                self._dev_lock:
            greedy, logits, self.cache, self._dev_counters = self._decode(
                self.params, self.cache, self._dev_counters, toks_dev,
                self._no_prev if prev is None else prev.greedy)
        # every live row consumes one token, whose successor is this
        # step's own output until it is read; a free row's cursor stays
        self._next_tok[live] = CARRY
        before = self._cursor
        self._cursor = before + live
        self._count_step(before, 1, live)
        self._count_steps("synced" if prev is None else "ahead")
        for _, r in rows:
            r.decode_rounds += 1
        if self.spec is not None:
            # the target advanced without the draft: those slots' draft
            # caches are behind until their next admission
            self.spec.on_plain_step([s for s, _ in rows])
        # the host reads [slots] token ids; the [slots, vocab] logits come
        # back only from a step where a request samples from them
        return _Step(greedy, logits, rows,
                     any(r.temperature > 0.0 for _, r in rows), t0)

    def _fetch(self, step: _Step) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Wait for a dispatched step and copy out its [slots] tokens, and
        its logits if a row of it samples: -> (tokens, logits or None)."""
        logits = None
        with trace_scope("serve:decode.fetch", cat="serving"):
            greedy = np.asarray(step.greedy)
            if step.sampling:
                logits = np.asarray(step.logits)
        # what the step added to the gap between tokens: it could not
        # start before its dispatch, nor be read before the step before
        now = time.monotonic()
        self._observe("tok_latency_ms",
                      (now - max(step.t0, self._read_t)) * 1e3)
        self._read_t = now
        if step.sampling:
            self.decode_logit_fetches += 1
            self._count("decode_logit_fetches")
        return greedy, logits

    def _push(self, step: _Step, greedy: np.ndarray,
              logits: Optional[np.ndarray]) -> List[Result]:
        """Push a fetched step's tokens, each to the request its row was
        dispatched for if that request still holds the slot (one that
        ended on an `eos` the step could not know of does not: the row was
        computed for nothing, and is dropped): -> the requests completed."""
        done: List[Result] = []
        wasted = 0
        with trace_scope("serve:decode.sample", cat="serving"):
            for slot, req in step.rows:
                if self.slot_mgr.request_at(slot) is not req:
                    wasted += 1
                    continue
                if req.temperature <= 0.0:
                    nxt = int(greedy[slot])
                else:
                    nxt = self._sample(logits[slot], req.temperature)
                finished = self._push_token(slot, req, nxt,
                                            from_decode=True)
                if finished is not None:
                    done.append(finished)
            if self._flight is not None:
                # the step dispatched behind this one took these tokens on
                # the device: its rows' next input is its own output
                for slot, req in self._flight.rows:
                    if self.slot_mgr.request_at(slot) is req:
                        self._next_tok[slot] = CARRY
        if wasted:
            self._count_steps("wasted_rows", wasted)
        return done

    def _count_steps(self, kind: str, n: int = 1) -> None:
        # rebound whole, as `_count_step` does: a reader on another thread
        # sees the counts of one moment
        self._decode_steps = {**self._decode_steps,
                              kind: self._decode_steps[kind] + n}

    def _spec_step_ok(self) -> bool:
        """Speculate this iteration?  Needs: a decoder, at least one active
        slot with a fresh draft cache and healthy acceptance, every active
        request greedy (temperature 0 — acceptance is an argmax identity),
        and k rows of cache headroom on EVERY active slot (a verify that
        wrote past max_len would poison that slot's whole row, engine
        overflow semantics)."""
        if self.spec is None:
            return False
        active = self.slot_mgr.active()
        if not active:
            return False
        any_ready = False
        for slot, req in active.items():
            if req.temperature > 0.0:
                return False
            if not self.spec.headroom_ok(int(self._cursor[slot])):
                return False
            if self.spec.slot_ready(slot):
                any_ready = True
        return any_ready

    def _spec_decode_step(self) -> List[Result]:
        """One speculative round: draft k-1 proposals (one dispatch, draft
        cursor re-anchored in-program), verify + accept + roll back
        [slots, k] (one dispatch), commit each slot's accepted run + the
        target's correction token.  Acceptance is self-validating — a
        proposal commits only when it equals the target's own greedy token
        — so stale or garbage proposals can cost speed, never
        correctness."""
        k = self.spec.k
        t0_toks = self._next_tok.copy()
        active = sorted(self.slot_mgr.active().items())
        ids = [r.trace_id for _, r in active if r.trace_id]
        dargs: Dict[str, Any] = {"k": k}
        vargs: Dict[str, Any] = {"active": len(active), "k": k}
        if ids:
            dargs["trace_ids"] = ids
            vargs["trace_ids"] = ids
        with trace_scope("serve:draft", cat="serving", args=dargs,
                         track=bool(ids)):
            # the draft is told nothing of free slots: token 0 from row 0
            proposals = self.spec.propose(np.maximum(t0_toks, 0),
                                          self._cursor)
        ver = np.concatenate([t0_toks[:, None], proposals], axis=1)
        with trace_scope("serve:verify", cat="serving", args=vargs,
                         track=bool(ids)):
            t0 = time.monotonic()
            with trace_scope("serve:verify.dispatch", cat="serving"), \
                    self._dev_lock:
                ver_dev = jnp.asarray(ver.astype(np.int32))
                (g_dev, n_acc_dev, self.cache, self._dev_counters,
                 *hidden) = self._verify(
                    self.params, self.cache, self._dev_counters, ver_dev,
                    jnp.asarray(proposals.astype(np.int32)),
                )
                if hidden:
                    self.spec.after_verify(ver_dev, n_acc_dev, hidden[0])
            with trace_scope("serve:verify.fetch", cat="serving"):
                g = np.asarray(g_dev)
                n_acc = np.asarray(n_acc_dev)
            dt = time.monotonic() - t0
            if ids:
                # per-round acceptance, aligned with trace_ids (args is
                # serialized at scrape time, so filling it here is visible)
                vargs["accepted"] = [int(n_acc[s]) for s, r in active
                                     if r.trace_id]
        self._observe("tok_latency_ms", dt * 1e3)
        # every live slot's cursor moved to committed length: + accepted
        # drafts + the correction token; a free slot's stays
        live = t0_toks >= 0
        before = self._cursor
        self._cursor = before + (n_acc + 1) * live
        self._count_step(before, k, live)
        for _, r in active:
            r.decode_rounds += 1
        done: List[Result] = []
        for slot, req in active:
            budget = req.remaining_new_tokens - len(req.generated)
            run: List[int] = []
            for j in range(int(n_acc[slot]) + 1):
                tok = int(g[slot, j])
                run.append(tok)
                if len(run) >= budget or (req.eos_id >= 0
                                          and tok == req.eos_id):
                    break
            if self.spec.slot_ready(slot):
                self.spec.observe(slot, int(n_acc[slot]), len(run),
                                  trace_id=req.trace_id)
            for tok in run:
                finished = self._push_token(slot, req, tok, from_decode=True)
                if finished is not None:
                    done.append(finished)
                    break
        return done

    def _push_token(self, slot: int, req: Request, tok: int,
                    from_decode: bool = False) -> Optional[Result]:
        """Record one generated token for `slot`; frees the slot and returns
        the Result when the request is finished."""
        req.generated.append(tok)
        self.total_tokens += 1
        hit_eos = req.eos_id >= 0 and tok == req.eos_id
        if len(req.generated) >= req.remaining_new_tokens or hit_eos:
            self.slot_mgr.release(slot)
            self._reset_slot(slot)
            return self._finish(req, status="ok")
        self._next_tok[slot] = tok
        return None

    def _reset_slot(self, slot: int) -> None:
        """A released slot's row back to its free state: cursor 0 on the
        device and on the host, where both stay until the next admission,
        and FREE for its token."""
        with trace_scope("serve:slot_reset", cat="serving",
                         args={"slot": slot}):
            self.cache = reset_slot(self.cache, slot)
        self._next_tok[slot] = FREE
        self._cursor[slot] = 0
        if self.spec is not None:
            self.spec.release_slot(slot)

    def _sample(self, logits: np.ndarray, temperature: float) -> int:
        """One draw at `temperature` > 0 from the engine's own generator;
        greedy requests never come here (the step programs return their
        argmax)."""
        z = logits.astype(np.float64) / temperature
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._rng.choice(len(p), p=p))

    def _finish(self, req: Request, status: str) -> Result:
        self._grafts.pop(req.req_id, None)  # expired-before-admission ship
        req.finished_t = time.monotonic()
        if req.trace_id and req.decode_t0 is not None:
            # the per-request decode phase: first new token -> completion,
            # aggregated over every batch round that advanced this slot
            child_span("decode", req.decode_t0, req.finished_t,
                       trace_id=req.trace_id, parent_id=req.parent_span,
                       cat="serving",
                       args={"req_id": req.req_id,
                             "tokens": len(req.generated),
                             "rounds": req.decode_rounds,
                             "status": status})
        lat = (req.finished_t - req.submitted_t) * 1e3
        result = Result(
            req_id=req.req_id,
            tokens=tuple(req.all_tokens()) if status == "ok" else tuple(req.prompt),
            status=status,
            ttft_ms=round(req.ttft_s * 1e3, 3) if req.ttft_s is not None else None,
            latency_ms=round(lat, 3),
            requeues=req.requeues,
        )
        if status == "ok":
            self.total_completed += 1
            self._count("requests_completed")
        else:
            self._count("requests_expired")
        with self._completed_lock:
            pending = self._pending.pop(req.req_id, None)
        if pending is not None:
            pending._finish(result)
        return result

    def set_params(self, params: Any) -> None:
        """Install reloaded weights.  The radix prefix cache is a pure
        function of the params, so every cached row is invalidated; the
        per-slot KV of in-flight requests stays (their earlier tokens were
        produced by the old weights — the stream finishes consistently and
        fresh admissions use the new weights end to end).  A step in
        flight is read first: it ran on the weights it was dispatched
        with, and nothing is dispatched across the change."""
        self._read_early.extend(self._drain())
        self._install(params)
        if self._want_hidden:  # the drafter computes with the same tree
            self.spec.set_params(self.params)
        self.params_version += 1
        if self.prefix is not None:
            self.prefix.invalidate(reason="weight_reload")

    def _install(self, params: Any) -> None:
        """Every way weights arrive ends here: placed under the mesh's
        shardings if there is one, then kept in the resident form
        (models/transformer.py `resident_params`: each leaf in the dtype
        the programs read it in, so no step converts a whole weight again;
        a tree already resident, a buddy's /weights, comes through as it
        is).  `param_bytes` is what the device then holds by dtype, from
        the leaves' shapes: the host's knowledge, no device read."""
        if self._param_shardings is not None:
            params = jax.device_put(params, self._param_shardings)
        self.params = resident_params(self.dcfg, params)
        held: Dict[str, int] = {}
        for leaf in jax.tree.leaves(self.params):
            name = str(leaf.dtype)
            held[name] = held.get(name, 0) + leaf.size * leaf.dtype.itemsize
        self.param_bytes = held

    def in_flight(self) -> List[dict]:
        """Queued + slotted requests with their progress — the warm-resume
        snapshot a worker ships to its buddy (worker.py).  Progress is what
        the host has read, so a step in flight is read first."""
        self._read_early.extend(self._drain())
        out = []
        for req in self.slot_mgr.active().values():
            d = req.to_json()
            d["generated"] = list(req.generated)
            out.append(d)
        return out

    def _count_step(self, before: np.ndarray, query_rows: int,
                         live: np.ndarray) -> None:
        """Add one slot-cache step to `decode_attn_rows` and `decode_rows`:
        `before` the cursors it started from (`self._cursor` those it ended
        at), each slot bringing `query_rows` query rows, `live` [slots]
        bool the rows that did work; the others ("free") did none: their
        slot held no request, or one whose last token was in flight."""
        max_len = self.dcfg.max_len
        block = self._attn_block[query_rows]
        free = ~live
        if block is None:
            # the einsum reads every row of every slot
            fetched, fetched_free = self.n_slots * max_len, free.sum() * max_len
        else:
            # the list the kernels walk, as the program builds it: the
            # blocks of live slots, so none of a row that did no work
            first, last = live_blocks(np, before, before + query_rows - 1,
                                      block, max_len, self.dcfg.window)
            slot, _, n = visits(np, first, last, live,
                                self.n_slots * (max_len // block))
            fetched, fetched_free = n * block, free[slot[:n]].sum() * block
        written = np.minimum(self._cursor, max_len)
        add = (self.n_slots * max_len, written.sum(), written @ free,
               fetched, fetched_free)
        # rebound whole, so a reader on another thread (/metrics, a
        # profile capture) sees the totals of one step or of the next
        self._attn_rows = {kind: n + int(a) for (kind, n), a
                           in zip(self._attn_rows.items(), add)}
        n_live = int(live.sum())
        self._decode_rows = {
            "live": self._decode_rows["live"] + n_live,
            "free": self._decode_rows["free"] + self.n_slots - n_live}
        if self._stateful:
            self._count_scan("decode", n_live * query_rows)
        if self._sparse:
            # a live slot's query at `before` holds before + 1 rows, reads
            # the rows of at most topk of its blocks and scores the
            # compressed keys that lie wholly at or before it
            cfg, at = self.dcfg, before[live]
            blocks = np.minimum(at // cfg.sparse_block_size + 1,
                                cfg.sparse_topk)
            add = ((at + 1).sum(), blocks.sum() * cfg.sparse_block_size,
                   visible_kernels(np, at, 2 * cfg.sparse_kernel_stride,
                                   cfg.sparse_kernel_stride).sum())
            self._sparse_rows = {kind: n + int(a) for (kind, n), a
                                 in zip(self._sparse_rows.items(), add)}

    def _count_scan(self, kind: str, tokens: int) -> None:
        self._scan_tokens = {**self._scan_tokens,
                             kind: self._scan_tokens[kind] + tokens}

    def scan_tokens(self) -> Dict[str, int]:
        """Tokens the recurrent layers' scan walked so far, a layer:
        `prefill` the real tokens of the prefills (not the padding of their
        buckets), `decode` the live slot-steps of the decode steps.  Zeros
        for a model without such layers."""
        return dict(self._scan_tokens)

    def sparse_rows(self) -> Dict[str, int]:
        """Cache rows of the block-selected layers' attention, summed over
        the decode steps so far, a layer and a KV head: `written` the rows
        live slots held (their query's position + 1), `fetched` the rows
        of the blocks chosen for them (every block at or before the query
        while those are at most `sparse_topk`, that many after:
        `fetched` = `written` rounded up to blocks says no selection),
        `kernels` the compressed keys their selector scored.  From the
        cursors, as `select_blocks` counts; zeros for a model without such
        layers."""
        return dict(self._sparse_rows)

    def decode_attn_rows(self) -> Dict[str, int]:
        """Cache rows of the decode-step attention, summed over the decode
        and verify steps so far, a layer: `cache` the rows a step spans
        (slots x max_len), `written` the rows the cursors stand at after
        it and `written_free` those of them under the cursors of rows that
        did no work (so `written - written_free` is what the attention
        NEEDS to read): 0 for a slot that holds no request, whose cursor
        stays at 0, and the held cursor of a slot whose request's last
        token was in flight when the step was dispatched; `fetched` the
        rows the program reads (the blocks of the list its kernels walk,
        ops/decode_attn.py `visits`: the live blocks of the slots that did
        work; the whole cache when the program was built with the dense
        einsum, so `fetched == cache` says the kernel is not what runs),
        `fetched_free` those of them read for rows that did no work: 0
        under the kernels, which visit no block of such a slot, be it
        empty or its request ending; its whole slot under the einsum."""
        return dict(self._attn_rows)

    def decode_rows(self) -> Dict[str, int]:
        """Slot-steps of the decode and verify steps so far: `live` those
        that computed a token for a request, `free` those that did no work
        (cursor held, no expert, no cache block read by the attention
        kernels): the slot held no request, or a request whose last token
        was still in flight from the step before (`decode_steps`).
        `live + free` = slots x steps."""
        return dict(self._decode_rows)

    def decode_steps(self) -> Dict[str, int]:
        """Decode steps so far by what was in flight when each was
        dispatched: `ahead` with the step before it unread (its tokens
        went in on the device), `synced` with nothing in flight (the host
        had read every token); `wasted_rows` the rows of `ahead` steps
        computed for a request that had ended on an `eos` in the step
        before, read and dropped."""
        return dict(self._decode_steps)

    def device_counters(self, refresh: bool = True) -> Dict[str, Any]:
        """What the model counted on the device, copied to the host:
        {collection: tree of numpy} (empty for a model that counts nothing).
        The one read of these arrays, made when /metrics or a profile
        capture asks, never by a step.  The lock keeps the step programs
        from donating the arrays under the copy; the copy waits for the
        step in flight.  A caller that cannot have the lock soon (a step
        program is compiling under it) gets the last copy, so a scrape never
        outwaits its timeout here.  `refresh=False` is the last copy and
        touches neither lock nor device: /healthz, which the router probes
        four times a second beside the decode loop."""
        if refresh and self._dev_counters and self._dev_lock.acquire(timeout=0.25):
            try:
                self._dev_counters_host = jax.device_get(self._dev_counters)
            finally:
                self._dev_lock.release()
        return self._dev_counters_host

    def stats(self) -> Dict[str, Any]:
        out = {
            "queue_depth": self.queue.depth(),
            "active_slots": self.slot_mgr.active_count,
            "free_slots": self.slot_mgr.free_count,
            "total_tokens": self.total_tokens,
            "total_prefill_tokens": self.total_prefill_tokens,
            "total_completed": self.total_completed,
            "preemptions": self.preemptions,
            "decode_logit_fetches": self.decode_logit_fetches,
            "param_bytes": dict(self.param_bytes),
            "decode_attn_rows": self.decode_attn_rows(),
            "decode_rows": self.decode_rows(),
            "decode_steps": self.decode_steps(),
            "cache_bytes": dict(self.cache_bytes),
        }
        if self._stateful:
            out["scan_tokens"] = self.scan_tokens()
        if self._sparse:
            out["sparse_rows"] = self.sparse_rows()
        if self.prefix is not None:
            out["prefix"] = self.prefix.stats()
        if self.spec is not None:
            out["spec"] = self.spec.stats()
        return out

    def _observe(self, metric: str, value: float) -> None:
        if self.counters is not None:
            self.counters.observe_hist(metric, value)

    def _count(self, event: str) -> None:
        if self.counters is not None:
            self.counters.inc_event(event)

    def _gauge(self) -> None:
        if self.counters is not None:
            self.counters.set_gauge("queue_depth", float(self.queue.depth()))
            self.counters.set_gauge(
                "active_slots", float(self.slot_mgr.active_count)
            )


class BackpressureError(RuntimeError):
    """Admission queue full — callers translate to HTTP 503 + Retry-After."""
