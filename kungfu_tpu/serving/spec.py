"""Speculative decoding — draft k-1 tokens, verify them in one target step.

Greedy decode is latency-bound: every token pays one full [slots, 1] target
dispatch.  Speculation multiplies tokens per target step at bit-identical
output:

  * PROPOSE: a small draft model (same vocab, its own [slots, max_len] KV
    cache held in lockstep with the committed stream) greedily decodes k
    tokens per slot inside ONE jitted `lax.scan` — one dispatch regardless
    of k.  The scan consumes [t0, d1, ..., d_{k-1}] (k steps), so the draft
    cache rows cover even a full accept.
  * VERIFY: the target consumes [t0, d1, ..., d_{k-1}] as a single
    [slots, k] decode-mode forward — THE one new compiled target signature
    (models/transformer.py decode mode is verify-k native: per-slot cursors
    make a k-token call exactly k chained 1-token calls).  Greedy targets
    g_j = argmax(logits[:, j]) are what plain decode would have produced,
    so committing the accepted run g_0..g_{n_acc} is bit-exact by
    construction: d_j is accepted only while d_j == g_{j-1}, and the first
    rejected position is replaced by the target's own g_{n_acc}.
    Acceptance AND the per-slot cursor rollback both happen INSIDE the
    verify program (engine `_verify_accept`): one dispatch, one host sync
    per round — the overhead budget that decides whether speculation pays.
  * ROLLBACK: the verify wrote k rows and the program rolled each slot's
    cursor back to cursor + committed in the same dispatch; rows above a
    cursor are never attended, so rejected rows go stale harmlessly.  The
    draft cache needs no rollback at all: every propose re-anchors its
    cursor at the target's committed length in-program, and the rows below
    it are accepted history by construction.

Per-slot accept cursors: slots diverge — one slot may commit k tokens while
its neighbor commits one.  A slot whose rolling acceptance collapses below
`disable_below` is DISABLED for the rest of its request (journaled
`spec_disabled`): it keeps riding the fixed-shape verify but commits only
g_0 per round, and when every active slot is disabled the engine drops to
the plain [slots, 1] program (zero draft cost) until a fresh admission
re-enables speculation.  A slot that saw a plain step goes STALE (its draft
cache misses rows) and behaves like a disabled slot until its next
admission re-prefills the draft.

Two drafters stand behind the one interface the engine drives
(`prefill_slot`, `propose`, `observe`, `release_slot`, `slot_ready`,
`headroom_ok`, `on_plain_step`, `stats`), sharing their per-slot
bookkeeping (`_DraftBook`):

  * `SpecDecoder`: a separate draft model, as above.
  * `MTPDrafter`: the target's own multi-token-prediction module
    (models/transformer.py `MTPModule`, `cfg.mtp_layers` 1), verify width
    2.  It is part of the target: it shares the embedding and the head,
    reads the target's final hidden states (`reads_hidden`: the engine's
    prefill and verify programs then return them, on the device) and
    keeps one latent cache of its own.  Module row i is made from
    (h_i, t_{i+1}), so a round at committed length c runs the module over
    positions c-2 and c-1 in one two-row step: row c-2 is the one a full
    accept of the last round left unwritten (its hidden state did not
    exist before the verify; rewriting it after a reject is idempotent),
    row c-1 scores d1, the proposal for t_{c+1}.  The verify hands back
    the hidden states of both positions it consumed and `fold` keeps
    those of the last two COMMITTED positions, on the device.

Telemetry: `spec_accept_rate` histogram (per-round accepted fraction),
`spec_rounds` / `spec_accepted_tokens` / `spec_committed_tokens` /
`spec_disabled` counters, under the same names for both drafters.  See
docs/serving.md "Speculative decoding".
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import get_logger
from .slots import write_slot

log = get_logger("kungfu.serving")

DEFAULT_K = 4
DEFAULT_DISABLE_BELOW = 0.1
DEFAULT_DISABLE_AFTER = 4  # rounds of EMA warmup before a slot can disable


def _zero_cache(init, *args):
    """The "cache" collection a decode-mode module declares, all zeros,
    from the shapes of an abstract init: no parameter is made for it."""
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0), *args)["cache"]
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def _set_cursors(cache, idx):
    """`cache` with every cursor leaf at `idx` (a scalar or [B]) and every
    overflow flag cleared."""
    def fix(path, leaf):
        name = getattr(path[-1], "key", None)
        if name == "idx":
            return jnp.broadcast_to(idx, leaf.shape).astype(leaf.dtype)
        if name == "overflowed":
            return jnp.zeros_like(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache)


class _DraftBook:
    """What every drafter keeps on the host, a slot: the rolling acceptance
    and the disabled and stale flags the engine's `slot_ready` question is
    answered from, and the counters."""

    #: whether the engine's prefill and verify programs must return the
    #: target's final hidden states for this drafter
    reads_hidden = False

    def __init__(self, slots: int, k: int, max_len: int, counters,
                 disable_below: float, disable_after: int):
        assert k >= 2, "speculation needs a verify width of at least 2"
        self.k = int(k)
        self.n_slots = slots
        self.max_len = int(max_len)
        self.counters = counters
        self.disable_below = float(disable_below)
        self.disable_after = int(disable_after)
        self._ema = np.zeros(slots, np.float64)
        self._rounds = np.zeros(slots, np.int64)
        self._disabled = np.zeros(slots, bool)
        self._stale = np.ones(slots, bool)  # un-prefilled slots can't spec
        self.rounds = 0
        self.accepted_tokens = 0
        self.committed_tokens = 0

    def _arm(self, slot: int) -> None:
        """A fresh admission's draft state is in place: speculate on it."""
        self._ema[slot] = 1.0
        self._rounds[slot] = 0
        self._disabled[slot] = False
        self._stale[slot] = False

    def release_slot(self, slot: int) -> None:
        self._stale[slot] = True

    def slot_ready(self, slot: int) -> bool:
        """True when this slot's proposals are worth verifying."""
        return not (self._stale[slot] or self._disabled[slot])

    def headroom_ok(self, cursor: int) -> bool:
        return cursor + self.k <= self.max_len

    def observe(self, slot: int, accepted: int, committed: int,
                trace_id: str = "") -> None:
        """Per-slot acceptance bookkeeping after a verify round; disables
        the slot (journaled once) when its acceptance EMA collapses.
        `trace_id` names the request decoding in the slot so a collapse is
        attributable to the request whose stream caused it."""
        frac = accepted / max(1, self.k - 1)
        self.rounds += 1
        self.accepted_tokens += accepted
        self.committed_tokens += committed
        r = self._rounds[slot]
        self._ema[slot] = frac if r == 0 else 0.7 * self._ema[slot] + 0.3 * frac
        self._rounds[slot] = r + 1
        if self.counters is not None:
            self.counters.observe_hist("spec_accept_rate", frac)
            self.counters.inc_event("spec_rounds")
            if accepted:
                self.counters.inc_event("spec_accepted_tokens", accepted)
            if committed:
                self.counters.inc_event("spec_committed_tokens", committed)
            self.counters.set_gauge("spec_accept_ema",
                                    float(np.mean(self._ema)))
        if (not self._disabled[slot]
                and self._rounds[slot] >= self.disable_after
                and self._ema[slot] < self.disable_below):
            self._disabled[slot] = True
            from ..monitor.journal import journal_event

            journal_event("spec_disabled", slot=int(slot),
                          accept_ema=round(float(self._ema[slot]), 4),
                          rounds=int(self._rounds[slot]),
                          trace_id=trace_id)
            if self.counters is not None:
                self.counters.inc_event("spec_disabled")
            log.info("spec disabled on slot %d (accept ema %.3f)",
                     slot, self._ema[slot])

    def on_plain_step(self, active_slots) -> None:
        """A plain decode step advanced the target cache without the draft:
        those slots' draft rows are now behind — stale until re-admission."""
        for s in active_slots:
            self._stale[s] = True

    def accept_rate(self) -> float:
        denom = self.rounds * (self.k - 1)
        return self.accepted_tokens / denom if denom else 0.0

    def attn_rows(self) -> Dict[str, int]:
        """Cache rows this drafter's own attention needed so far, by kind,
        for `kft_serve_decode_attn_rows_total` beside the engine's (none
        for a drafter whose reads nobody counts)."""
        return {}

    def stats(self) -> Dict[str, Any]:
        return {
            "k": self.k,
            "rounds": self.rounds,
            "accepted_tokens": self.accepted_tokens,
            "committed_tokens": self.committed_tokens,
            "accept_rate": round(self.accept_rate(), 4),
            "disabled_slots": int(self._disabled.sum()),
        }


class SpecDecoder(_DraftBook):
    """Draft-model half of speculative decoding; the engine owns the verify
    step (its model, its cache) and drives propose/observe/rollback."""

    def __init__(self, draft_cfg, draft_params, slots: int,
                 k: int = DEFAULT_K,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 counters=None,
                 disable_below: float = DEFAULT_DISABLE_BELOW,
                 disable_after: int = DEFAULT_DISABLE_AFTER):
        from ..models.transformer import TransformerLM

        assert draft_cfg.rope, "the draft needs rope (decode cursors)"
        super().__init__(slots, k, draft_cfg.max_len, counters,
                         disable_below, disable_after)
        self.dcfg = dataclasses.replace(
            draft_cfg, decode=True, attention="auto", mesh=None, head="dense"
        )
        self.model = TransformerLM(self.dcfg)
        self.params = draft_params
        from .engine import default_buckets

        self.buckets = tuple(sorted(
            prefill_buckets or default_buckets(self.dcfg.max_len)
        ))

        probe = jnp.zeros((slots, 1), jnp.int32)
        self.cache = _zero_cache(self.model.init, probe)
        self._small0 = _zero_cache(self.model.init, probe[:1])

        model = self.model
        kk = self.k

        @jax.jit
        def _prefill(params, cache0, tokens, total_len):
            _, st = model.apply(
                {"params": params, "cache": cache0}, tokens, mutable=["cache"]
            )
            return _set_cursors(st["cache"], total_len)

        @jax.jit
        def _propose(params, cache, t0, start_idx):
            # Re-anchor every slot's draft cursor at the target's committed
            # length, then run k greedy draft steps in one program: consume
            # [t0, d1..d_{k-1}], emit [d1..dk].  The re-anchor is what makes
            # the draft cache rollback-free: rows below the committed cursor
            # were written by earlier propose rounds whose tokens were
            # accepted (or they predate the correction point, which the
            # re-anchored cursor now overwrites).  Emitting (and consuming)
            # through d_{k-1} keeps the rows complete for a full accept;
            # d_k itself is never verified and is discarded.
            cache = _set_cursors(cache, start_idx)

            def step(carry, _):
                cache, tok = carry
                logits, st = model.apply(
                    {"params": params, "cache": cache}, tok, mutable=["cache"]
                )
                nxt = jnp.argmax(
                    logits[:, -1].astype(jnp.float32), axis=-1
                ).astype(jnp.int32)[:, None]
                return (st["cache"], nxt), nxt

            (cache, _), toks = jax.lax.scan(
                step, (cache, t0), None, length=kk
            )
            return jnp.moveaxis(toks[..., 0], 0, 1), cache  # [slots, k]

        self._prefill = _prefill
        self._propose = _propose

    # -- per-slot lifecycle ----------------------------------------------------------

    def prefill_slot(self, slot: int, tokens: Tuple[int, ...],
                     hidden=None) -> None:
        """Prefill the draft cache for a fresh admission (full tokens — the
        draft never uses the prefix cache: it must mirror exactly the
        committed stream) and re-arm speculation for the slot.  `hidden`
        is for a drafter that reads the target's; this one has a model."""
        n = len(tokens)
        bucket = next(b for b in self.buckets if n <= b)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = tokens
        small = self._prefill(self.params, self._small0,
                              jnp.asarray(padded), n)
        self.cache = write_slot(self.cache, small, slot)
        self._arm(slot)

    # -- the round ---------------------------------------------------------------

    def propose(self, next_tok: np.ndarray,
                committed_cursor: np.ndarray) -> np.ndarray:
        """Draft proposals [slots, k-1] continuing each slot's pending
        token from its committed cursor (the in-program re-anchor makes a
        separate rollback dispatch unnecessary).  Free and stale slots ride
        along (a free one from cursor 0 and token 0: the draft is told
        nothing of liveness) — their proposals only ever COST acceptance,
        never correctness: a proposal commits only when it equals the
        target's own greedy token."""
        drafts, self.cache = self._propose(
            self.params, self.cache,
            jnp.asarray(next_tok[:, None].astype(np.int32)),
            jnp.asarray(committed_cursor.astype(np.int32)),
        )
        return np.asarray(drafts)[:, : self.k - 1]


class MTPDrafter(_DraftBook):
    """The target's own prediction module as the drafter, verify width 2
    (the module's docstring above has the round).  `params` is the tree
    the engine computes with (its resident form): the module's
    `params["mtp_0"]`, the embedding and the head are read from it, and
    nothing is copied."""

    reads_hidden = True

    def __init__(self, cfg, params, slots: int,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 counters=None,
                 disable_below: float = DEFAULT_DISABLE_BELOW,
                 disable_after: int = DEFAULT_DISABLE_AFTER):
        from ..models.transformer import MTPModule
        from .engine import default_buckets

        assert cfg.mtp_layers == 1 and "mtp_0" in params, (
            "the target carries no prediction module (mtp_layers 1)")
        super().__init__(slots, 2, cfg.max_len, counters, disable_below,
                         disable_after)
        self.dcfg = dataclasses.replace(
            cfg, decode=True, attention="auto", mesh=None, head="dense")
        self.params = params
        self.buckets = tuple(sorted(
            prefill_buckets or default_buckets(cfg.max_len)))
        self.draft_rows = 0  # latent rows the rounds' module steps needed
        module = MTPModule(self.dcfg)
        d = cfg.d_model

        def apply(params, cache, hidden, next_tokens):
            logits, st = module.apply(
                {"params": params["mtp_0"], "cache": cache}, hidden,
                next_tokens, params["embed"]["embedding"],
                params["lm_head"]["kernel"], mutable=["cache"])
            return logits, st["cache"]

        def zeros(batch: int):
            return _zero_cache(
                module.init, jnp.zeros((batch, 1, d), jnp.float32),
                jnp.zeros((batch, 1), jnp.int32),
                jnp.zeros((cfg.vocab_size, d), cfg.dtype),
                jnp.zeros((d, cfg.vocab_size), jnp.float32))

        self.cache, self._small0 = zeros(slots), zeros(1)
        # hidden states of each slot's last two committed positions and the
        # token at the last one: the module's inputs for rows c-2 and c-1
        self._h2 = jnp.zeros((slots, 2, d), jnp.float32)
        self._t_prev = jnp.zeros((slots,), jnp.int32)

        @jax.jit
        def _prefill(params, cache0, hidden, next_tokens, rows):
            # module rows 0..rows-1 from the prompt's hidden states and its
            # tokens moved one to the left; what lies beyond is padding
            # above the cursor
            _, cache = apply(params, cache0, hidden, next_tokens)
            return _set_cursors(cache, rows)

        @partial(jax.jit, donate_argnums=(0, 1))
        def _seat(h2, t_prev, hidden, last, tok, slot):
            # a fresh admission's state: the hidden states of its last two
            # prompt positions, its last prompt token
            pair = jax.lax.dynamic_slice_in_dim(hidden[0], last - 1, 2)
            return (jax.lax.dynamic_update_slice_in_dim(h2, pair[None], slot, 0),
                    t_prev.at[slot].set(tok))

        @partial(jax.jit, donate_argnums=(1,))
        def _propose(params, cache, h2, t_prev, t0, cursor):
            # re-anchored at c-2 in-program, as the draft model's cursor is:
            # rows below are accepted history, row c-2 is written again or
            # for the first time, row c-1 is new
            cache = _set_cursors(cache, jnp.maximum(cursor - 2, 0))
            logits, cache = apply(params, cache, h2,
                                  jnp.stack([t_prev, t0], axis=1))
            d1 = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
            return d1.astype(jnp.int32)[:, None], cache

        @partial(jax.jit, donate_argnums=(0, 1))
        def _fold(h2, t_prev, ver, n_acc, hidden):
            # the verify consumed ver = [t0, d1] at positions c, c+1 and
            # committed 1 + n_acc tokens: the last two committed positions
            # are (c, c+1) after an accept, (c-1, c) after a reject
            took = n_acc > 0
            h2 = jnp.where(took[:, None, None], hidden, jnp.concatenate(
                [h2[:, 1:], hidden[:, :1]], axis=1))
            return h2, jnp.maximum(jnp.where(took, ver[:, 1], ver[:, 0]), 0)

        self._prefill, self._seat = _prefill, _seat
        self._propose, self._fold = _propose, _fold

    def set_params(self, params) -> None:
        self.params = params

    def prefill_slot(self, slot: int, tokens: Tuple[int, ...],
                     hidden=None) -> None:
        """Fill the module's rows 0..n-2 from the prompt's hidden states
        (`hidden` [1, bucket, d_model] on the device, position i at row
        i).  Without them (a prefix-cache hit prefilled only the suffix, a
        grafted admission ran no prefill here) or for a one-token prompt
        the slot stays stale: it rides the rounds and commits the target's
        own token."""
        n = len(tokens)
        if hidden is None or n < 2:
            self._stale[slot] = True
            return
        nxt = np.zeros((1, hidden.shape[1]), np.int32)
        nxt[0, : n - 1] = tokens[1:]
        small = self._prefill(self.params, self._small0, hidden,
                              jnp.asarray(nxt), n - 1)
        self.cache = write_slot(self.cache, small, slot)
        self._h2, self._t_prev = self._seat(
            self._h2, self._t_prev, hidden, n - 1, int(tokens[-1]), slot)
        self._arm(slot)

    def propose(self, next_tok: np.ndarray,
                committed_cursor: np.ndarray) -> np.ndarray:
        """d1 [slots, 1] for each slot's pending token at its committed
        cursor.  Free and stale slots ride along on whatever state they
        have: a proposal commits only when it is the target's own token."""
        self.draft_rows += int(np.minimum(committed_cursor, self.max_len).sum())
        drafts, self.cache = self._propose(
            self.params, self.cache, self._h2, self._t_prev,
            jnp.asarray(next_tok.astype(np.int32)),
            jnp.asarray(committed_cursor.astype(np.int32)))
        return np.asarray(drafts)

    def after_verify(self, ver, n_acc, hidden) -> None:
        """The verify round's device arrays (its [slots, 2] input tokens,
        accepted counts, hidden states [slots, 2, d_model]): keep the last
        two committed positions' state.  One small dispatch, no read."""
        self._h2, self._t_prev = self._fold(
            self._h2, self._t_prev, ver, n_acc, hidden)

    def attn_rows(self) -> Dict[str, int]:
        """Latent rows the module's steps needed so far, under a kind of
        their own."""
        return {"draft_written": self.draft_rows}


def build_draft(preset_or_cfg, seed: int = 0, overrides_json: str = ""):
    """(draft_cfg, draft_params) from a worker preset name or an explicit
    TransformerConfig — the zoo path for serving workers (--spec-draft).
    The draft must share the target's vocab and max_len; presets here are
    the serving PRESETS table (serving/worker.py)."""
    from .worker import build_config, seed_params

    if isinstance(preset_or_cfg, str):
        cfg = build_config(preset_or_cfg, overrides_json)
    else:
        cfg = preset_or_cfg
    return cfg, seed_params(cfg, seed)
