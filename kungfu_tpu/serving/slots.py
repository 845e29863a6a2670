"""KV-cache slot management for continuous batching.

The decode cache is one fixed-shape pytree of [slots, max_len, ...] arrays
(models/transformer.py decode mode, per-slot cursors).  `SlotManager` is the
host-side ledger binding batch rows to requests; the jitted helpers below do
the cache surgery:

  write_slot   graft a freshly prefilled single-request cache (batch row 0 of
               a [1, max_len, ...] tree) into the big cache at `slot`, cursor
               set to the request's true (un-padded) length
  reset_slot   zero a released slot's cursor + overflow flag: a free row
               stays there (the step programs do not advance a slot that
               holds no request, serving/engine.py), so its dummy k/v land
               on row 0 and its attention reads one block
  set_cursors  write every slot's cursor at once from a host [slots] array —
               the speculative-decoding rollback (serving/spec.py): a verify
               step advances every cursor by k, then per-slot acceptance
               rolls each back to its true committed length.  Rows above a
               cursor are never attended, so the rolled-back rows go stale
               harmlessly (the reset_slot precedent)

All compile once per cache shape (the shapes never change at runtime — that
is the no-recompile contract of the fixed-shape slot batch).

The host-side row helpers (`extract_rows` / `warm_small_cache`) move KV rows
between the device cache layout and plain numpy: the radix prefix cache
(serving/prefix.py) stores matched prefixes as row blocks, and the
disaggregation ship path (serving/disagg.py, ops/kv_ship.py) moves the same
blocks between prefill and decode ranks.  Position-indexed leaves are every
cache leaf except the `idx`/`overflowed` cursor state, whatever lies behind
the position axis: a layer's `[.., Hkv, D]` K and V (and int8 scales), or
the one `[.., rank + rope]` latent row of a latent-attention sublayer.

STATE leaves (`STATE_LEAVES`) are the third kind: what a recurrent layer
keeps for a slot (models/transformer.py `Mamba`: `ssm_state`
[slots, N, d_inner] and `conv_state` [slots, K - 1, d_inner];
`LightningAttention`: `lin_state` [slots, heads, e, e]), with a slot
axis and NO position axis.  `write_slot` replaces a slot whole, state
included, so an admission replaces the last tenant's state; `reset_slot`
leaves it where it is, as it leaves rows (a free slot's row is not live and
the model keeps its state untouched).  A state is the summary of every
token so far and cannot be cut at a position: the helpers that slice rows
by position or move cursors alone (`extract_rows`, `extract_slot_rows`,
`warm_small_cache`, `set_cursors`) raise on a tree that holds one, and the
engine refuses what is built on them (prefix reuse, speculation, shipped
KV) when it is made.

STRIDED leaves (`STRIDED_LEAVES`) are indexed by position at a stride of
their own (models/transformer.py `SparseAttention`: `k_cmp`
[slots, max_len / stride, Hkv x D], entry m a summary of rows
stride x m .. stride x (m + 2) - 1).  They are rows to the allocator
(`write_slot` moves them with the slot, `reset_slot` leaves them, entries
over rows above a cursor are never read, `cache_bytes` counts them as
`rows`), but the row helpers slice every leaf at ONE length: they raise on
a tree that holds one as they do for a state, and the engine refuses the
same things, until a helper knows the stride.
"""
from __future__ import annotations

import threading
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .request import Request

CURSOR_LEAVES = ("idx", "overflowed")
#: leaves a recurrent layer keeps a slot: no position axis
STATE_LEAVES = ("ssm_state", "conv_state", "lin_state")
#: leaves indexed by position at a stride of their own
STRIDED_LEAVES = ("k_cmp",)


def _leaf_name(path) -> Optional[str]:
    return getattr(path[-1], "key", None)


def has_state(cache) -> bool:
    """Whether the cache tree holds what the row helpers cannot cut at a
    position: a recurrent layer's state, or a leaf at a stride of its own."""
    return any(_leaf_name(path) in STATE_LEAVES + STRIDED_LEAVES
               for path, _ in jax.tree_util.tree_flatten_with_path(cache)[0])


def cache_bytes(cache) -> Dict[str, int]:
    """Bytes of the cache tree by kind: position-indexed `rows` (cursors
    beside them) and recurrent `state`, from the leaves' shapes."""
    out = {"rows": 0, "state": 0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        kind = "state" if _leaf_name(path) in STATE_LEAVES else "rows"
        out[kind] += leaf.size * leaf.dtype.itemsize
    return out


def _rows_only(cache, what: str) -> None:
    if has_state(cache):
        raise ValueError(
            f"{what}: the cache holds recurrent state (no position axis) or "
            "keys compressed at a stride of their own; a state is the "
            "summary of every token so far and cannot be cut at a position "
            "or rolled back by a cursor, and no helper slices a strided leaf")


@partial(jax.jit, donate_argnums=(0,))
def write_slot(big, small, slot):
    """big[slot] = small[0] for every cache leaf (cursor/overflow included —
    the prefill path already fixed those to (true_len, False))."""
    return jax.tree.map(
        lambda b, s: jax.lax.dynamic_update_slice_in_dim(
            b, s.astype(b.dtype), slot, axis=0
        ),
        big, small,
    )


@partial(jax.jit, donate_argnums=(0,))
def reset_slot(big, slot):
    """Zero `slot`'s cursor and overflow flag; K/V rows are left in place
    (never attended: the mask only reads rows at or below the cursor)."""

    def fix(path, leaf):
        name = _leaf_name(path)
        if name == "idx":
            return leaf.at[slot].set(0)
        if name == "overflowed":
            return leaf.at[slot].set(False)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, big)


def set_cursors(big, cursors):
    """Write every slot's cursor from `cursors` [slots] int32 — the per-slot
    speculative rollback.  K/V rows and overflow flags are untouched: rows
    above a cursor are never attended (reset_slot's contract), and the
    engine only speculates on slots with `cursor + k <= max_len`, so a
    rollback can never need to clear an overflow.  Raises on a tree with
    state leaves: a cursor cannot roll a recurrence back."""
    _rows_only(big, "set_cursors")
    return _set_cursors(big, cursors)


@partial(jax.jit, donate_argnums=(0,))
def _set_cursors(big, cursors):
    def fix(path, leaf):
        if _leaf_name(path) == "idx":
            return cursors.astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, big)


def extract_rows(small, n: int) -> Dict[tuple, np.ndarray]:
    """Host-copy the first `n` KV rows of a batch-1 cache tree: every
    position-indexed leaf (cached_k/v + int8 scales) sliced to [n, ...],
    keyed by its flattened path.  The storage format of the radix prefix
    cache and the cross-rank KV ship.  Whole leaves move in one batched
    device_get and the row slice happens on the HOST: an eager device
    slice (`leaf[0, :n]`) would compile one slice program per distinct
    prefix length — a compile storm on mixed traffic."""
    _rows_only(small, "extract_rows")
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(small)[0]:
        if _leaf_name(path) in CURSOR_LEAVES:
            continue
        out[tuple(str(p) for p in path)] = leaf
    return {k: np.ascontiguousarray(v[0, :n])
            for k, v in jax.device_get(out).items()}


def extract_slot_rows(big, slot: int, n: int) -> Dict[tuple, np.ndarray]:
    """extract_rows for one row of the BIG [slots, max_len, ...] cache:
    host-copy the first `n` KV rows of `slot`.  The preemption path feeds
    these to the radix prefix cache so the evicted request's re-prefill is
    a warm hit.  Same discipline as extract_rows — batched device_get,
    HOST-side slicing — so no per-(slot, length) slice programs compile."""
    _rows_only(big, "extract_slot_rows")
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(big)[0]:
        if _leaf_name(path) in CURSOR_LEAVES:
            continue
        out[tuple(str(p) for p in path)] = leaf
    return {k: np.ascontiguousarray(v[slot, :n])
            for k, v in jax.device_get(out).items()}


def warm_small_cache(template, rows: Dict[tuple, np.ndarray], n: int):
    """Build a batch-1 cache whose first `n` rows are `rows` and whose
    cursor sits at `n` — the graft input for a prefix-cache hit (prefill
    continues from the cached rows) or a shipped-KV admission (no prefill
    at all).  `template` is the engine's zeroed [1, max_len, ...] tree;
    output shapes/dtypes match it exactly, so the jitted prefill/graft
    programs never retrace."""
    _rows_only(template, "warm_small_cache")

    def fill(path, leaf):
        name = _leaf_name(path)
        if name == "idx":
            return jnp.full_like(leaf, n)
        if name == "overflowed":
            return jnp.zeros_like(leaf)
        arr = np.zeros(leaf.shape, np.dtype(leaf.dtype))
        block = rows[tuple(str(p) for p in path)]
        assert block.shape[0] == n, (block.shape, n)
        arr[0, :n] = block
        return jnp.asarray(arr)

    return jax.tree_util.tree_map_with_path(fill, template)


class SlotManager:
    """Free-list of batch rows; binds at most one request per slot."""

    def __init__(self, n_slots: int):
        if n_slots < 1:
            raise ValueError("need at least one slot")
        self.n_slots = n_slots
        self._lock = threading.Lock()
        self._free: List[int] = list(range(n_slots))
        self._active: Dict[int, Request] = {}

    def allocate(self, req: Request) -> Optional[int]:
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop(0)
            self._active[slot] = req
            return slot

    def release(self, slot: int) -> Request:
        with self._lock:
            req = self._active.pop(slot)
            self._free.append(slot)
            self._free.sort()  # deterministic reuse order (tests rely on it)
            return req

    def request_at(self, slot: int) -> Optional[Request]:
        with self._lock:
            return self._active.get(slot)

    def active(self) -> Dict[int, Request]:
        with self._lock:
            return dict(self._active)

    @property
    def free_count(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def active_count(self) -> int:
        with self._lock:
            return len(self._active)
