"""Scheduler core for the serving engine: admission + slot bookkeeping.

The old engine served in *waves*: admit up to ``max_batch`` equal-length
prompts, decode the whole batch ``max(max_new_tokens)`` steps, repeat.
Two well-known schedulers' diseases follow: head-of-line blocking (the
queue head's prompt length defines the wave, so one odd-length request
forces a tiny batch while a full batch's worth of other lengths waits)
and decode waste (every slot steps until the *longest* request in the
wave finishes). This module is the cure, split out of the engine so the
policy is inspectable and testable on its own:

``Scheduler``
    Pending requests live in prompt-length buckets (prefill needs equal
    lengths — the causal KV cache has no per-row padding mask).
    Admission picks the bucket that fills the free slots best, and
    orders requests *within* a bucket by ``max_new_tokens`` so a decode
    group finishes together instead of dragging finished slots through a
    long tail. The legacy ``fifo``/``wave`` policies keep the old
    head-of-line behavior for comparison benchmarks.

``SlotGroup``
    One admitted cohort mid-decode: its requests (row -> request), its
    KV caches, and the current token per row. Groups shrink as requests
    finish: :func:`gather_cache_rows` gathers the still-active rows into
    a smaller batch (``compact="pow2"`` snaps widths to powers of two so
    the decode jit compiles O(log max_batch) shapes, not one per width),
    and the freed slots go back to the engine's global budget — which is
    what lets the engine admit the next group *mid-decode* instead of at
    the end of the wave (continuous batching at group granularity).
"""
from __future__ import annotations

import dataclasses
import itertools
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.models.attention import KVCache
from repro.models.paged_cache import RESERVED_BLOCKS, SCRATCH_BLOCK

POLICIES = ("bucketed", "fifo", "wave")
COMPACTION = ("pow2", "exact", "off")
KV_LAYOUTS = ("paged", "contiguous")


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Admission + compaction policy for the serving engine.

    ``policy``:
      * ``bucketed`` (default) — fullest prompt-length bucket first,
        requests inside a bucket grouped by ``max_new_tokens``; new
        groups are admitted whenever slots are free, including
        mid-decode of other groups.
      * ``fifo`` — the oldest pending request's bucket, in arrival
        order (head-of-line semantics), but still admits mid-decode.
      * ``wave`` — the legacy engine verbatim: ``fifo`` admission, one
        group at a time, no compaction. Kept as the measurable baseline
        for ``benchmarks/serve_bench.py``.

    ``compact``: ``pow2`` (default) gathers a group's still-active rows
    into the next power-of-two width once that halves the batch;
    ``exact`` compacts to the exact active count on every finish (one
    decode retrace per width); ``off`` never compacts (legacy).

    ``kv_layout``:
      * ``paged`` (default) — KV lives in fixed-size blocks from a shared
        pool behind a per-row block table (:mod:`repro.models.paged_cache`);
        compaction rewrites the table (zero cache-row copies), common
        prompt heads share refcounted prefix blocks, and decode attention
        reads through the table. Models ``paged_compatible`` rejects
        (recurrent mixers, sliding windows) silently fall back to
        contiguous; the ``wave`` policy always serves contiguous (it *is*
        the legacy engine).
      * ``contiguous`` — the legacy per-slot ``(max_seq, ...)`` caches,
        ``gather_cache_rows`` compaction. Kept for bit-identical
        comparison; outputs match ``paged`` token-for-token.

    ``share_prefix``: reuse full prefix blocks (and the prefill compute)
    across identical prompt heads; paged only. Off = every row private.

    ``page_size``: tokens per KV block (paged only).

    ``prefill_chunk``: 0 disables; otherwise a block-multiple chunk size —
    prompts longer than this are prefilled ``prefill_chunk`` tokens per
    engine tick, interleaved with other groups' decode ticks instead of
    stalling them behind one long prefill (paged only, text-only models).

    ``debug_kv``: run the paged-KV sanitizer
    (:mod:`repro.analysis.kv_sanitizer`) at every scheduler quantum
    boundary — refcount/reachability/COW invariants over the whole
    allocator + live tables. Exact but host-side-only work per quantum;
    a violation raises ``KVSanitizerError`` from ``engine.step()``.
    The ``REPRO_DEBUG_KV=1`` environment variable turns it on without
    touching call sites (paged only; ignored for contiguous layouts).
    """

    policy: str = "bucketed"
    compact: str = "pow2"
    kv_layout: str = "paged"
    share_prefix: bool = True
    page_size: int = 16
    prefill_chunk: int = 0
    debug_kv: bool = False

    def __post_init__(self):
        if self.policy not in POLICIES:
            raise ValueError(f"unknown scheduler policy {self.policy!r}; "
                             f"policies: {list(POLICIES)}")
        if self.compact not in COMPACTION:
            raise ValueError(f"unknown compaction mode {self.compact!r}; "
                             f"modes: {list(COMPACTION)}")
        if self.kv_layout not in KV_LAYOUTS:
            raise ValueError(f"unknown kv layout {self.kv_layout!r}; "
                             f"layouts: {list(KV_LAYOUTS)}")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1 (got {self.page_size})")
        if self.prefill_chunk < 0 or (
                self.prefill_chunk and self.prefill_chunk % self.page_size):
            raise ValueError(
                f"prefill_chunk must be 0 or a positive multiple of "
                f"page_size={self.page_size} (got {self.prefill_chunk})")
        if self.prefill_chunk and self.kv_layout != "paged":
            raise ValueError("prefill_chunk requires kv_layout='paged'")


class Scheduler:
    """Prompt-length-bucketed admission over pending requests.

    The engine asks :meth:`select` for the next cohort each step; the
    scheduler answers with a list of equal-prompt-length requests sized
    to the free slots (or ``[]`` when nothing should be admitted yet).
    """

    def __init__(self, config: Optional[SchedulerConfig] = None):
        self.config = config or SchedulerConfig()
        self._buckets: Dict[int, Deque[Tuple[int, Any]]] = {}
        self._arrival = itertools.count()

    def __len__(self) -> int:
        return sum(len(b) for b in self._buckets.values())

    @property
    def pending(self) -> List[Any]:
        """All pending requests in arrival order (read-only snapshot)."""
        flat = [t for b in self._buckets.values() for t in b]
        return [r for _, r in sorted(flat, key=lambda t: t[0])]

    def submit(self, req) -> None:
        plen = len(req.prompt)
        self._buckets.setdefault(plen, deque()).append(
            (next(self._arrival), req))

    def _pick_bucket(self, free_slots: int) -> Optional[int]:
        live = {k: b for k, b in self._buckets.items() if b}
        if not live:
            return None
        if self.config.policy in ("fifo", "wave"):
            # head-of-line: the oldest pending request defines the cohort
            return min(live, key=lambda k: live[k][0][0])
        # bucketed: best fill of the free slots; ties go to the oldest head
        return max(live, key=lambda k: (min(len(live[k]), free_slots),
                                        -live[k][0][0]))

    def select(self, free_slots: int, *, live_groups: int = 0) -> List[Any]:
        """Admission decision: up to ``free_slots`` equal-length requests
        for one prefill, or ``[]``. ``wave`` policy refuses to admit
        while any group is still decoding (the legacy blocking drain)."""
        if free_slots <= 0 or not len(self):
            return []
        if self.config.policy == "wave" and live_groups > 0:
            return []
        key = self._pick_bucket(free_slots)
        if key is None:
            return []
        bucket = self._buckets[key]
        take = min(len(bucket), free_slots)
        if self.config.policy == "bucketed":
            # group similar decode lengths so the cohort finishes together
            # (the wave engine steps every slot max(max_new_tokens) times)
            ordered = sorted(bucket, key=lambda t: (t[1].max_new_tokens,
                                                    t[0]))
            chosen = ordered[:take]
            chosen_ids = {t[0] for t in chosen}
            rest = [t for t in bucket if t[0] not in chosen_ids]
            bucket.clear()
            bucket.extend(rest)
        else:
            chosen = [bucket.popleft() for _ in range(take)]
        return [r for _, r in chosen]


# ---------------------------------------------------------------------------
# Decode groups + cache-row gathering
# ---------------------------------------------------------------------------

def _gather(node, idx, axis: int):
    if isinstance(node, KVCache):
        # slot_pos is shared across rows (cache_len,) — only k/v have a
        # batch axis
        return node._replace(k=jnp.take(node.k, idx, axis=axis),
                             v=jnp.take(node.v, idx, axis=axis))
    if isinstance(node, dict):
        return {k: _gather(v, idx, axis) for k, v in node.items()}
    if isinstance(node, tuple) and hasattr(node, "_fields"):
        # recurrent states (RGLRUState/RWKVState): every field is
        # batch-axis aligned
        return type(node)(*(_gather(f, idx, axis) for f in node))
    if isinstance(node, tuple):
        return tuple(_gather(v, idx, axis) for v in node)
    return jnp.take(node, idx, axis=axis)


def gather_cache_rows(caches: Dict[str, Any], idx) -> Dict[str, Any]:
    """Select batch rows ``idx`` from a prefill/decode cache pytree.

    Stacked (scanned) layer caches carry a leading period axis, so their
    batch axis is 1; tail caches are batch-leading; the decode position
    is a scalar shared by every row and passes through unchanged."""
    idx = jnp.asarray(idx, jnp.int32)
    out = dict(caches)
    out["stack"] = _gather(caches["stack"], idx, 1)
    out["tail"] = _gather(caches["tail"], idx, 0)
    return out


def _pow2_at_least(n: int) -> int:
    if n <= 0:
        return 0  # a zero-active group compacts away entirely, not to width 1
    return 1 if n == 1 else 1 << (n - 1).bit_length()


class SlotGroup:
    """One admitted cohort mid-decode. ``requests[row]`` is the request
    fed by that batch row, or ``None`` for a pad row left by power-of-two
    compaction (its tokens are computed and discarded)."""

    #: engine-owned mutable dict {"rows": int} counting physically copied
    #: cache rows (the paged layout's zero-copy claim is asserted on it)
    copy_counter: Optional[Dict[str, int]] = None
    #: the engine's id for the admission that made the group; its spans
    #: (``serve.admit``, ``serve.decode``) carry it as ``cohort``
    cohort: int = -1

    def __init__(self, requests: List[Any], caches: Dict[str, Any], cur,
                 plen: int):
        self.requests: List[Optional[Any]] = list(requests)
        self.caches = caches
        self.cur = cur
        self.plen = plen
        self._temps: Optional[tuple] = None
        self._dev_temps = None

    @property
    def width(self) -> int:
        return len(self.requests)

    def device_temps(self):
        """The rows' sampling temperatures as a device ``(width,)``
        float32 array, 0 for pad and finished rows. Uploaded again only
        when the rows change (admission, retirement, compaction): on a
        TPU v5e host an upload adds 0.1-0.3 ms to a decode call, the
        comparison a few microseconds."""
        temps = tuple(r.temperature if r is not None else 0.0
                      for r in self.requests)
        if temps != self._temps:
            self._temps = temps
            self._dev_temps = jnp.asarray(np.asarray(temps, np.float32))
        return self._dev_temps

    @property
    def active_rows(self) -> List[int]:
        return [i for i, r in enumerate(self.requests)
                if r is not None and len(r.output) < r.max_new_tokens]

    @property
    def done(self) -> bool:
        return not self.active_rows

    def release(self) -> None:
        """Give the group's KV storage back (no-op for contiguous caches —
        they die with the last reference)."""
        self.caches = None
        self.cur = None

    def compact(self, mode: str) -> int:
        """Shrink the batch to the still-active rows per ``mode``;
        returns the number of slots freed (0 when nothing changed)."""
        if mode == "off":
            return 0
        active = self.active_rows
        if not active:
            # every row finished (or was a pad row) mid-tick: free the
            # whole group instead of gathering rows of an empty selection
            freed = self.width
            self.requests = []
            self.release()
            return freed
        target = len(active) if mode == "exact" else _pow2_at_least(
            len(active))
        if target >= self.width:
            return 0
        rows = active + [active[0]] * (target - len(active))
        freed = self.width - target
        self.requests = [self.requests[i] for i in active] \
            + [None] * (target - len(active))
        self.caches = gather_cache_rows(self.caches, rows)
        if self.copy_counter is not None:
            self.copy_counter["rows"] += len(rows)
        self.cur = jnp.take(self.cur, jnp.asarray(rows, jnp.int32), axis=0)
        return freed


class PagedSlotGroup(SlotGroup):
    """A cohort whose KV lives in pool blocks behind a per-row block
    table. ``table`` is host-side numpy ``(width, n_cols)`` int32 —
    compaction is a row-select on it plus refcount decrefs for blocks
    only the dropped rows referenced: zero cache-row copies. The device
    copy of the table (padded to a power-of-two column count so decode
    retraces O(log) shapes) is cached and rebuilt lazily on mutation."""

    def __init__(self, requests: List[Any], table, cur, plen: int, *,
                 allocator, block_size: int, pos: int):
        super().__init__(requests, caches=None, cur=cur, plen=plen)
        self.table = np.asarray(table, np.int32)
        self.alloc = allocator
        self.block_size = block_size
        self.pos = int(pos)              # next absolute decode position
        self._dev_table = None
        self._released = False
        # chunked-prefill bookkeeping (driven by the engine)
        self.chunks_done = 0
        self.n_chunks = 0
        self.prompt_padded: Optional[np.ndarray] = None

    @property
    def prefilling(self) -> bool:
        return self.chunks_done < self.n_chunks

    def device_table(self):
        if self._dev_table is None:
            W, nc = self.table.shape
            ncp = max(1, _pow2_at_least(nc))
            padded = np.zeros((W, ncp), np.int32)  # zero block: masked reads
            padded[:, :nc] = self.table
            self._dev_table = jnp.asarray(padded)
        return self._dev_table

    def ensure_frontier(self) -> None:
        """Make the table column for ``pos`` writable before a decode
        step lands there: a fresh private block per live row, the scratch
        block for pad rows (their writes are discarded garbage). Also
        upgrades chunk-padding scratch columns to real blocks as decode
        reaches them."""
        col = self.pos // self.block_size
        W, nc = self.table.shape
        changed = False
        if col >= nc:
            self.table = np.concatenate(
                [self.table, np.zeros((W, col + 1 - nc), np.int32)], axis=1)
            changed = True
        for i, r in enumerate(self.requests):
            if self.table[i, col] >= RESERVED_BLOCKS:
                continue
            self.table[i, col] = (self.alloc.alloc() if r is not None
                                  else SCRATCH_BLOCK)
            changed = True
        if changed:
            self._dev_table = None

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        for row in self.table:
            for bid in row:
                if bid >= RESERVED_BLOCKS:
                    self.alloc.decref(int(bid))
        self.table = self.table[:0]
        self._dev_table = None
        self.cur = None

    def compact(self, mode: str) -> int:
        if mode == "off":
            return 0
        active = self.active_rows
        if not active:
            freed = self.width
            self.requests = []
            self.release()
            return freed
        target = len(active) if mode == "exact" else _pow2_at_least(
            len(active))
        if target >= self.width:
            return 0
        W, nc = self.table.shape
        keep = set(active)
        for i in range(W):
            if i in keep:
                continue
            for bid in self.table[i]:
                if bid >= RESERVED_BLOCKS:
                    self.alloc.decref(int(bid))
        n_pad = target - len(active)
        # pad rows write (and read back) only scratch garbage; their
        # sampled tokens are discarded with the row
        pad = np.full((n_pad, nc), SCRATCH_BLOCK, np.int32)
        self.table = np.concatenate([self.table[active], pad], axis=0)
        self.requests = [self.requests[i] for i in active] + [None] * n_pad
        rows = active + [active[0]] * n_pad
        self.cur = jnp.take(self.cur, jnp.asarray(rows, jnp.int32), axis=0)
        self._dev_table = None
        return W - target
