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

Telemetry: `spec_accept_rate` histogram (per-round accepted fraction),
`spec_rounds` / `spec_accepted_tokens` / `spec_disabled` counters.  See
docs/serving.md "Speculative decoding".
"""
from __future__ import annotations

import dataclasses
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


class SpecDecoder:
    """Draft-model half of speculative decoding; the engine owns the verify
    step (its model, its cache) and drives propose/observe/rollback."""

    def __init__(self, draft_cfg, draft_params, slots: int,
                 k: int = DEFAULT_K,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 counters=None,
                 disable_below: float = DEFAULT_DISABLE_BELOW,
                 disable_after: int = DEFAULT_DISABLE_AFTER):
        from ..models.transformer import TransformerLM

        assert k >= 2, "speculation needs a verify width of at least 2"
        assert draft_cfg.rope, "the draft needs rope (decode cursors)"
        self.k = int(k)
        self.n_slots = slots
        self.counters = counters
        self.disable_below = float(disable_below)
        self.disable_after = int(disable_after)
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
        variables = self.model.init(jax.random.PRNGKey(0), probe)
        self.cache = variables["cache"]
        self._small0 = self.model.init(jax.random.PRNGKey(0), probe[:1])["cache"]

        model = self.model
        kk = self.k

        @jax.jit
        def _prefill(params, cache0, tokens, total_len):
            _, st = model.apply(
                {"params": params, "cache": cache0}, tokens, mutable=["cache"]
            )

            def fix(path, leaf):
                name = getattr(path[-1], "key", None)
                if name == "idx":
                    return jnp.full_like(leaf, total_len)
                if name == "overflowed":
                    return jnp.zeros_like(leaf)
                return leaf

            return jax.tree_util.tree_map_with_path(fix, st["cache"])

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
            def anchor(path, leaf):
                name = getattr(path[-1], "key", None)
                if name == "idx":
                    return start_idx.astype(leaf.dtype)
                if name == "overflowed":
                    return jnp.zeros_like(leaf)
                return leaf

            cache = jax.tree_util.tree_map_with_path(anchor, cache)

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

        # host-side per-slot state
        self._ema = np.zeros(slots, np.float64)
        self._rounds = np.zeros(slots, np.int64)
        self._disabled = np.zeros(slots, bool)
        self._stale = np.ones(slots, bool)  # un-prefilled slots can't spec
        self.rounds = 0
        self.accepted_tokens = 0
        self.committed_tokens = 0

    # -- per-slot lifecycle ----------------------------------------------------------

    def prefill_slot(self, slot: int, tokens: Tuple[int, ...]) -> None:
        """Prefill the draft cache for a fresh admission (full tokens — the
        draft never uses the prefix cache: it must mirror exactly the
        committed stream) and re-arm speculation for the slot."""
        n = len(tokens)
        bucket = next(b for b in self.buckets if n <= b)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = tokens
        small = self._prefill(self.params, self._small0,
                              jnp.asarray(padded), n)
        self.cache = write_slot(self.cache, small, slot)
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
        return cursor + self.k <= self.dcfg.max_len

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

    def stats(self) -> Dict[str, Any]:
        return {
            "k": self.k,
            "rounds": self.rounds,
            "accepted_tokens": self.accepted_tokens,
            "committed_tokens": self.committed_tokens,
            "accept_rate": round(self.accept_rate(), 4),
            "disabled_slots": int(self._disabled.sum()),
        }


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
