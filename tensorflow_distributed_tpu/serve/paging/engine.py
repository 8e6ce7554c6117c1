"""PagedSlotEngine: the slot engine over a page pool + prefix cache.

A drop-in :class:`~tensorflow_distributed_tpu.serve.engine.
SlotDecodeEngine` subclass (``--serve.paged``): the KV cache becomes a
``[num_pages, page_size, ...]`` pytree, slots hold page tables
(``[num_slots, max_pages]`` int32 fed to the jitted programs), and the
decode/verify/prefill executables gather pages through the table
INSIDE the same static-shape one-program discipline the dense engine
keeps (censused as ``serve_decode_paged`` / ``serve_verify_paged`` /
``serve_prefill_paged`` — zero collectives, drift-gated).

What paging buys (as counts, tests/test_serve_ahead.py; on the chip
it has no cell yet, ROADMAP.md C3):

- **no over-reserving**: a slot holds pages for its ACTUAL trajectory
  (prompt + budget, rounded up to pages), not a dense ``[max_len]``
  row — more slots fit a fixed HBM budget;
- **no recomputing**: the radix prefix cache maps a request's longest
  cached prefix (shared system prompts, few-shot headers, multi-turn
  ``session`` conversations) to refcounted pages, so prefill runs
  only on the uncached tail (bucketed as always) and TTFT on warm
  prefixes collapses. A hit attaches the ORIGINAL pages — the KV
  bytes are the ones recompute would produce, never approximated.

Correctness mechanics:

- **reserve-at-admit**: every page a request can ever touch is
  allocated (after prefix attach, after LRU eviction under pressure)
  before its prefill dispatches, so decode/verify never allocate
  mid-flight and a deterministic workload allocates deterministically;
- **copy-on-write**: when the matched prefix ends mid-page and that
  partial page is shared (refcount > 1), the engine copies it to a
  fresh page (one jitted traced-index program) before the tail
  overwrites it — the shared bytes survive for every other holder;
- **quarantine composes**: ``poison_slot`` NaN-fills only the slot's
  PRIVATE pages (shared prefix pages survive via refcounts), and a
  quarantined slot's private pages are scrubbed to zero before
  returning to the free list so poison can never leak into a later
  request through a masked column;
- **freed slots ride harmlessly**: a freed slot's table resets to the
  write-off page 0 (pool.GARBAGE_PAGE), the paged equivalent of the
  dense engine's own-row garbage writes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from tensorflow_distributed_tpu.analysis import runtime as graftcheck
from tensorflow_distributed_tpu.models.generate import lookup_program
from tensorflow_distributed_tpu.observe import device as observe_device
from tensorflow_distributed_tpu.observe.registry import emit_event
from tensorflow_distributed_tpu.serve.buckets import pick_bucket
from tensorflow_distributed_tpu.serve.engine import (
    SlotDecodeEngine, shard_cache, step_inputs, step_out_shardings)
from tensorflow_distributed_tpu.serve.paging.pool import (
    GARBAGE_PAGE, PagePool)
from tensorflow_distributed_tpu.serve.paging.radix import RadixCache


@functools.lru_cache(maxsize=64)
def _compiled_prefill_paged(model, bucket: int):
    """One jitted paged-prefill program per (model, bucket): the tail
    tokens write THROUGH the slot's page table into the pool at
    positions ``start .. start + bucket`` (``start`` = the matched
    prefix length; cached pages to the left are attended, never
    recomputed), and the greedy first token comes from the TRUE last
    tail position. Unlike the dense prefill there is no separate row
    insert — the scatter through the table IS the insert."""

    def run(params, cache, prompt, positions, table, true_len):
        logits, state = model.apply(
            {"params": params, "cache": cache}, prompt, decode=True,
            positions=positions, page_table=table, mutable=["cache"])
        last = jax.lax.dynamic_index_in_dim(
            logits, true_len - 1, axis=1, keepdims=False)   # [1, V]
        return (state["cache"],
                jnp.argmax(last, axis=-1).astype(jnp.int32))

    return observe_device.instrument_jit(
        f"serve_prefill_paged_b{bucket}", run, donate_argnums=(1,))


@functools.lru_cache(maxsize=8)
def _compiled_step_paged(model, tokens_at=None):
    """THE paged decode program: the dense step plus the page-table
    input — attention gathers each slot's pages back into the same
    [num_slots, max_len] logical layout, so the math (and the per-slot
    finiteness flag) is the dense program's (census-pinned: zero
    collectives)."""

    def run(params, cache, prev, host, tables):
        tok, pos = step_inputs(prev, host)
        logits, state = model.apply(
            {"params": params, "cache": cache}, tok[:, None],
            decode=True, positions=pos[:, None], page_table=tables,
            mutable=["cache"])
        last = logits[:, -1, :]
        ok = jnp.isfinite(last).all(axis=-1)
        return (state["cache"],
                jnp.argmax(last, axis=-1).astype(jnp.int32), ok)

    return observe_device.instrument_jit(
        "serve_decode_paged", run, donate_argnums=(1,),
        **step_out_shardings(tokens_at, stats=False))


@functools.lru_cache(maxsize=8)
def _compiled_verify_paged(model, k: int):
    """THE paged speculative verify: identical to the dense verify
    (k + 1 fed positions, argmax chain, per-slot ok) with writes and
    reads routed through the page tables. Verify writes land in pages
    exactly like decode writes — rollback-on-reject stays position
    bookkeeping."""

    def run(params, cache, toks, pos, tables):
        positions = pos[:, None] + jnp.arange(k + 1)[None, :]
        logits, state = model.apply(
            {"params": params, "cache": cache}, toks, decode=True,
            positions=positions, page_table=tables, mutable=["cache"])
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ok = jnp.isfinite(logits).all(axis=(-1, -2))
        return state["cache"], nxt, ok

    return observe_device.instrument_jit(f"serve_verify_paged_k{k}",
                                         run, donate_argnums=(1,))


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page_jit(cache, src, dst):
    """Copy one physical page (all cache leaves) — the COW program.
    ``src``/``dst`` are traced scalars: one executable for the
    engine's lifetime."""

    def cp(c):
        if getattr(c, "ndim", 0):
            return c.at[dst].set(c[src])
        return c

    return jax.tree_util.tree_map(cp, cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scrub_pages_jit(cache, pids):
    """Zero-fill the listed pages (every cache leaf, int8 included) —
    quarantined slots' private pages are scrubbed before re-entering
    the free list so NaN poison cannot leak into a later request
    through a masked column. ``pids`` pads with the write-off page 0
    (zeroing it is harmless — it must stay finite)."""

    def z(c):
        if getattr(c, "ndim", 0):
            return c.at[pids].set(jnp.zeros((), c.dtype))
        return c

    return jax.tree_util.tree_map(z, cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def _poison_pages_jit(cache, pids):
    """NaN-fill the float leaves of the listed pages (the slot_nan
    drill routed at PRIVATE pages only — shared prefix pages must
    survive a quarantine). ``pids`` pads by REPEATING a private page,
    never page 0 (the write-off page must stay finite)."""

    def bad(c):
        if (getattr(c, "ndim", 0)
                and jnp.issubdtype(c.dtype, jnp.floating)):
            return c.at[pids].set(jnp.full((), jnp.nan, c.dtype))
        return c

    return jax.tree_util.tree_map(bad, cache)


class PagedSlotEngine(SlotDecodeEngine):
    """The slot engine over a page pool (see module docstring). Extra
    ctor knobs: ``page_size`` (tokens per page; must divide the
    model's max_len), ``num_pages`` (pool size incl. the write-off
    page; 0 = auto: twice the dense worst case, half serving and half
    prefix cache), ``radix`` (False = paging without the prefix
    cache — pure allocation, for A/Bs)."""

    #: serve/scheduler.py keys admission context (max_new_tokens,
    #: session) and retention on this.
    paged = True

    def __init__(self, model, params, num_slots: int,
                 page_size: int = 16, num_pages: int = 0,
                 radix: bool = True, **kw):
        cfg = model.cfg
        if page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {page_size}")
        if cfg.max_len % page_size:
            raise ValueError(
                f"page_size {page_size} must divide the model's "
                f"max_len {cfg.max_len} (serve/run.py rounds --seq-len "
                f"up for you)")
        max_pages = cfg.max_len // page_size
        if num_pages <= 0:
            num_pages = 1 + 2 * num_slots * max_pages
        if num_pages < 1 + max_pages:
            raise ValueError(
                f"num_pages {num_pages} cannot hold even one "
                f"full-depth request ({max_pages} pages + the "
                f"write-off page)")
        self.page_size = int(page_size)
        self.max_pages = int(max_pages)
        # The paged model: same params, same family — only the cache
        # collection's layout (and the page_table input) differ.
        pcfg = dataclasses.replace(cfg, kv_page_size=int(page_size),
                                   kv_num_pages=int(num_pages))
        paged_model = type(model)(pcfg, model.mesh)
        self.pool = PagePool(num_pages, page_size)
        self.radix: Optional[RadixCache] = (RadixCache(self.pool)
                                            if radix else None)
        self.tables = np.zeros((num_slots, max_pages), np.int32)
        self.page_count = np.zeros((num_slots,), np.int32)
        # First position each slot may WRITE (the matched prefix
        # length): verify-fallback re-feeds must never dip into shared
        # pages (see verify_fallback_slots).
        self.private_start = np.zeros((num_slots,), np.int32)
        self._poisoned: set = set()
        # Slots whose decode flag went non-finite (sticky until the
        # slot is released or re-admitted): release() must not trust a
        # STALE _last_ok row for a slot that finished without another
        # decode step.
        self._flagged: set = set()
        # Cached device copy of the page tables (invalidated by the
        # two mutation sites: prefill and release).
        self._tables_dev = None
        self.prompt_tokens = 0
        self.prefix_hit_tokens = 0
        self.prefix_hits = 0
        self.prefill_tokens_computed = 0
        self.prefill_tokens_dense = 0
        self.cow_copies = 0
        self.page_evictions = 0
        # Peak DISTINCT pages held by live slots (shared prefix pages
        # counted once) — the serving working set an HBM budget must
        # actually cover; cached (radix/session) pages are evictable
        # under pressure and sit outside it. Slots at a budget is the
        # dense reservation divided by this.
        self.slot_pages_peak = 0
        super().__init__(paged_model, params, num_slots, **kw)

    # -- programs ----------------------------------------------------------

    def _build_programs(self) -> None:
        self._step_fn = lookup_program(_compiled_step_paged, self.model,
                                       self._tokens_at)
        self._verify_fn = (lookup_program(_compiled_verify_paged,
                                          self.model, self.spec_tokens)
                           if self.spec_tokens else None)

    def _tables_device(self):
        """Device-resident page tables, re-uploaded only after an
        admission/release mutated them — the decode loop must not pay
        a host-to-device table transfer per step (and, like the dense
        engine's slot scalars, the upload stays OUTSIDE the transfer
        guard: it is the designed input path)."""
        if self._tables_dev is None:
            self._tables_dev = self._h2d(self.tables)
        return self._tables_dev

    def _dispatch_step(self, prev, host):
        tables = self._tables_device()
        with graftcheck.transfer_guard(self._check):
            return self._step_fn(self.params, self.cache, prev, host,
                                 tables)

    def _dispatch_verify(self, tok, pos):
        tables = self._tables_device()
        with graftcheck.transfer_guard(self._check):
            return self._verify_fn(self.params, self.cache, tok, pos,
                                   tables)

    def _zero_cache(self):
        tok = jnp.zeros((self.num_slots, 1), jnp.int32)
        pos = jnp.zeros((self.num_slots, 1), jnp.int32)
        pt = jnp.zeros((self.num_slots, self.max_pages), jnp.int32)
        shapes = jax.eval_shape(
            lambda p, t, q, g: self.model.apply(
                {"params": p}, t, decode=True, positions=q,
                page_table=g, mutable=["cache"])[1]["cache"],
            self.params, tok, pos, pt)
        # The paged pool's head axis sits at dim 2 like the dense
        # cache's ([num_pages, page_size, nk, dh]) — the same TP
        # placement applies (no-op at width 1).
        return shard_cache(self.model, jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes))

    # -- accounting --------------------------------------------------------

    def page_bytes(self) -> int:
        """PER-DEVICE HBM per page summed over the cache leaves (int8
        scale leaves included) — the unit the "choosing num_slots
        under an HBM budget" arithmetic multiplies (README "Paged
        KV"). Under TP every pool leaf is head-sharded over "model"
        (shard_cache), so each device holds ``1/tp_width`` of a page's
        logical bytes — exact division, no-op at width 1."""
        return sum(
            int(np.prod(c.shape[1:])) * c.dtype.itemsize
            for c in jax.tree_util.tree_leaves(self.cache)
            if getattr(c, "ndim", 0)
            and c.shape[:1] == (self.pool.num_pages,)) // self.tp_width

    def cache_bytes_per_slot(self) -> int:
        """WORST-CASE bytes per slot (a full-depth request holds
        ``max_pages`` pages) — comparable to the dense engine's
        number. The paged win is that real requests hold
        ``ceil(trajectory / page_size)`` pages and shared prefixes
        are held once; ``paging_stats()`` carries the measured
        occupancy."""
        return self.page_bytes() * self.max_pages

    def pages_for(self, prompt_len: int, max_new_tokens: int) -> int:
        """Pages a request's full trajectory reserves at admission."""
        horizon = min(prompt_len + max(1, max_new_tokens),
                      self.max_len)
        return -(-horizon // self.page_size)

    def can_admit(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Worst-case admission check (ignores prefix hits, which only
        reduce the need): the pool — after evicting every reclaimable
        cached page — can cover the reservation, PLUS the one extra
        page a copy-on-write may consume when a cached match ends
        mid-page (attached pages stop being evictable, so without the
        +1 a tight pool could pass here and still exhaust inside
        prefill — found in review, pinned in tests/test_paging.py).
        The scheduler defers admission while this is False and live
        slots will free pages; False with an IDLE engine means the
        pool is simply too small (loud error, never a silent hang)."""
        need = self.pages_for(prompt_len, max_new_tokens)
        if (self.radix is not None
                and self.radix.cached_pages > 0):
            need += 1                      # the potential COW page
        if need <= self.pool.free_count:   # fast path: no tree walk
            return True
        avail = self.pool.free_count + (
            self.radix.reclaimable_pages if self.radix is not None
            else 0)
        return need <= avail

    def reservation_fits(self, prompt_len: int, max_new_tokens: int
                         ) -> bool:
        """Could the pool EVER hold this request's reservation: with the
        prefix cache fully evicted, +1 for the worst-case COW page while
        the radix cache is armed (``can_admit``'s rule)? The scheduler
        rejects a fed request that cannot, where an idle engine's
        admission would raise."""
        need = self.pages_for(prompt_len, max_new_tokens)
        return need + (self.radix is not None) <= self.pool.capacity

    def paging_stats(self) -> dict:
        """The page-pool / prefix-cache view folded into
        ``serve_summary`` and ``metrics_snapshot`` (the fleet router's
        capacity feed)."""
        out = {
            "page_size": self.page_size,
            "num_pages": self.pool.capacity,
            "page_bytes": self.page_bytes(),
            "pages_per_max_len": self.max_pages,
            **self.pool.stats(),
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prompt_tokens": self.prompt_tokens,
            "prefix_hit_rate": round(
                self.prefix_hit_tokens / max(1, self.prompt_tokens),
                4),
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "prefill_tokens_dense": self.prefill_tokens_dense,
            "cow_copies": self.cow_copies,
            "page_evictions": self.page_evictions,
            "slot_pages_peak": self.slot_pages_peak,
        }
        if self.radix is not None:
            out["cached_pages"] = self.radix.cached_pages
            out["sessions"] = self.radix.sessions_live
        return out

    # -- allocation --------------------------------------------------------

    def _acquire(self, n: int):
        """``n`` fresh pages, evicting LRU cached entries under
        pressure (each eviction emits a ``page_evict`` record)."""
        if n <= 0:
            return []
        evicted = 0
        while (self.pool.free_count < n and self.radix is not None
               and self.radix.evict_one()):
            evicted += 1
        if evicted:
            self.page_evictions += evicted
            emit_event("page_evict", evicted=evicted,
                       reason="pressure",
                       pages_free=self.pool.free_count,
                       pages_in_use=self.pool.pages_in_use)
        return self.pool.alloc(n)

    # -- admission ---------------------------------------------------------

    def prefill(self, prompt: np.ndarray, slot: int,
                max_new_tokens: int = 0, session: str = "",
                fetch: bool = True) -> Optional[int]:
        """Admit a request: longest-cached-prefix attach (radix or
        session), copy-on-write of a shared partial page, full-
        trajectory page reservation, then a bucketed prefill of ONLY
        the uncached tail. Returns the first generated token; with
        ``fetch=False`` nothing, and :meth:`first_token` fetches it
        (the dense engine's two halves of an admission)."""
        # graftcheck: disable=host-sync-in-loop -- normalizes the HOST
        # prompt the scheduler handed in; no device value involved
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        plen = len(prompt)
        if plen < 1:
            raise ValueError("empty prompt")
        if self.active[slot]:
            raise ValueError(f"slot {slot} is occupied")
        ps = self.page_size
        need_total = self.pages_for(plen, max_new_tokens)
        pages, m = [], 0
        if self.radix is not None:
            # At least one tail token must run (cap = plen - 1): the
            # first-token logits come from a computed position.
            pages, m, _src = self.radix.lookup(session, prompt,
                                               cap=plen - 1)
        fresh = self._acquire(need_total - len(pages))
        if m % ps and pages:
            # The tail's first write lands inside the matched chain's
            # last page. Shared -> copy-on-write (the cached bytes
            # survive for every other holder); sole-owned (a consumed
            # session's partial tail) -> write in place.
            li = m // ps
            if self.pool.ref[pages[li]] > 1:
                dst = self._acquire(1)[0]
                self.cache = _copy_page_jit(
                    self.cache, jnp.asarray(int(pages[li]), jnp.int32),
                    jnp.asarray(int(dst), jnp.int32))
                self.pool.release([pages[li]])
                pages[li] = dst
                self.cow_copies += 1
        table = [int(p) for p in pages] + fresh
        self.tables[slot, :] = GARBAGE_PAGE
        self.tables[slot, :len(table)] = table
        self.page_count[slot] = len(table)
        self.private_start[slot] = m
        self._tables_dev = None
        tail = prompt[m:]
        tlen = len(tail)
        bucket = pick_bucket(tlen, self.buckets)
        with self.spans.span("serve.prefill_launch", bucket=bucket):
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :tlen] = tail
            positions = m + np.arange(bucket, dtype=np.int32)[None, :]
            fn = lookup_program(_compiled_prefill_paged, self.model,
                                bucket)
            self._buckets_used.add(bucket)
            self.cache, first = fn(
                self.params, self.cache, jnp.asarray(padded),
                jnp.asarray(positions),
                jnp.asarray(self.tables[slot:slot + 1]),
                jnp.asarray(tlen, jnp.int32))
        self._dispatched(slot, plen, first)
        self.prompt_tokens += plen
        self.prefill_tokens_computed += bucket
        self.prefill_tokens_dense += pick_bucket(
            plen, self.buckets) if plen <= max(self.buckets) else plen
        if m:
            self.prefix_hits += 1
            self.prefix_hit_tokens += m
            emit_event("prefix_hit", slot=slot, prompt_len=plen,
                       hit_tokens=m, tail_bucket=bucket,
                       session=session or None)
        return self.first_token() if fetch else None

    def first_token(self) -> int:
        first_tok = super().first_token()
        live = {int(p)
                for s in range(self.num_slots) if self.active[s]
                for p in self.tables[s, :int(self.page_count[s])]}
        self.slot_pages_peak = max(self.slot_pages_peak, len(live))
        return first_tok

    # -- release / retention ----------------------------------------------

    def release(self, slot: int, tokens=None, session: str = ""
                ) -> None:
        """Free a slot. With ``tokens`` (the request's full
        prompt + emitted sequence) the WRITTEN prefix is retained:
        full blocks into the radix tree, the whole thing (partial tail
        page included) under ``session`` when set. A slot whose last
        step flagged non-finite (or was poison-drilled) retains
        nothing — its private pages are scrubbed to zero before
        re-entering the free list; its SHARED pages survive untouched
        (refcounts guarantee no write ever reached them)."""
        n = int(self.page_count[slot])
        ids = [int(p) for p in self.tables[slot, :n]]
        bad = slot in self._poisoned or slot in self._flagged
        if bad and ids:
            priv = [p for p in ids if self.pool.ref[p] == 1]
            if priv:
                pids = np.full((self.max_pages,), GARBAGE_PAGE,
                               np.int32)
                pids[:len(priv)] = priv
                self.cache = _scrub_pages_jit(self.cache,
                                              jnp.asarray(pids))
        elif tokens is not None and self.radix is not None and ids:
            written = int(self.pos[slot])
            toks = [int(t) for t in tokens][:written]
            if toks:
                cover = -(-len(toks) // self.page_size)
                self.radix.insert(toks, ids)
                if session:
                    self.radix.session_store(session, toks,
                                             ids[:cover])
        self.pool.release(ids)
        self.tables[slot, :] = GARBAGE_PAGE
        self.page_count[slot] = 0
        self.private_start[slot] = 0
        self._poisoned.discard(slot)
        self._flagged.discard(slot)
        self._tables_dev = None
        super().free(slot)

    def free(self, slot: int) -> None:
        """Plain free: ``release`` with nothing to retain."""
        self.release(slot)

    def take_bad_slots(self):
        out = super().take_bad_slots()
        self._flagged.update(out)
        return out

    # -- speculation -------------------------------------------------------

    def verify_fallback_slots(self):
        """Like the dense engine's, plus one paged guard: a fallback
        re-feed writes positions ``pos - k .. pos``, and if that dips
        below the slot's first PRIVATE position (a shared prefix page
        would be rewritten — bit-identity across programs is not a
        promise worth betting shared pages on), the whole batch takes
        the plain step instead."""
        out = super().verify_fallback_slots()
        if not out:
            return out
        k = self.spec_tokens
        for s in out:
            if self.pos[s] - k < self.private_start[s]:
                return None
        return out

    # -- fire drills -------------------------------------------------------

    def poison_slot(self, slot: int) -> None:
        """slot_nan drill, paged: NaN-fill the slot's PRIVATE pages
        only (refcount 1 — shared prefix pages must survive the
        quarantine; the satellite test pins that a later request
        still hits them and decodes correctly). Every admitted slot
        owns at least its tail page, so the poison always reaches an
        attended position."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(
                f"slot_nan slot {slot} out of range [0, "
                f"{self.num_slots})")
        floats = sum(
            1 for c in jax.tree_util.tree_leaves(self.cache)
            if getattr(c, "ndim", 0)
            and jnp.issubdtype(c.dtype, jnp.floating))
        if not floats:
            raise ValueError(
                "slot_nan: the decode cache has no float leaves to "
                "poison")
        n = int(self.page_count[slot])
        priv = [int(p) for p in self.tables[slot, :n]
                if self.pool.ref[p] == 1]
        if not priv:
            raise ValueError(
                f"slot_nan: slot {slot} holds no private pages "
                f"(is it admitted?)")
        self.drain()
        pids = np.full((self.max_pages,), priv[0], np.int32)
        pids[:len(priv)] = priv
        self.cache = _poison_pages_jit(self.cache, jnp.asarray(pids))
        self._poisoned.add(slot)

    # -- warmup ------------------------------------------------------------

    def warmup(self, speculator=None) -> None:
        """Dispatch every paged program once (each bucket's prefill,
        the decode step, the verify when armed, the COW copy, the
        poison and the scrub) against the write-off page, then drop
        the cache and build a zero one — same contract as the dense
        warmup: one cache alive at every point, a warmed engine equals
        a fresh one, and pool/table bookkeeping is untouched (warmup
        never allocates)."""
        self._warmup_guard()
        t1 = jnp.zeros((1, self.max_pages), jnp.int32)
        for b in self.buckets:
            fn = lookup_program(_compiled_prefill_paged, self.model, b)
            self.cache, _ = fn(
                self.params, self.cache, jnp.zeros((1, b), jnp.int32),
                jnp.zeros((1, b), jnp.int32), t1,
                jnp.asarray(1, jnp.int32))
        self.cache, _, _ = self._step_fn(
            self.params, self.cache, *self._step_args(None),
            jnp.asarray(self.tables))
        if self._verify_fn is not None:
            self.cache, _, _ = self._verify_fn(
                self.params, self.cache,
                jnp.zeros((self.num_slots, self.spec_tokens + 1),
                          jnp.int32),
                jnp.zeros((self.num_slots,), jnp.int32),
                jnp.asarray(self.tables))
        zero = jnp.asarray(0, jnp.int32)
        pids = jnp.zeros((self.max_pages,), jnp.int32)
        self.cache = _copy_page_jit(self.cache, zero, zero)
        self.cache = _poison_pages_jit(self.cache, pids)
        self.cache = _scrub_pages_jit(self.cache, pids)
        self._rezero_cache()
        warm = getattr(speculator, "warmup", None)
        if warm is not None:
            warm()


# -- num_pages auto-sizing (serve/run.py; README "Paged KV") ---------------

def page_bytes_estimate(cfg, page_size: int, tp: int = 1) -> int:
    """PER-DEVICE bytes one page will occupy, from the model CONFIG
    alone — so ``--serve.num-pages`` can be sized BEFORE any cache (or
    compiled program) exists. Mirrors the cache leaves
    models/transformer.py creates (K + V rows in the cache dtype, plus
    the f32 per-(token, head) absmax scales under int8), divided by
    the TP width ``tp`` (the pool is head-sharded over "model" —
    shard_cache); parity with the built engine's measured
    :meth:`PagedSlotEngine.page_bytes` is pinned in
    tests/test_fleet.py."""
    nk = cfg.n_kv_heads or cfg.n_heads
    dh = cfg.d_model // cfg.n_heads
    if cfg.kv_cache_quant == "int8":
        per_tok = 2 * nk * dh + 2 * nk * 4   # int8 rows + f32 scales
    else:
        per_tok = 2 * nk * dh * np.dtype(cfg.compute_dtype).itemsize
    return int(page_size) * int(cfg.n_layers) * int(per_tok) \
        // max(1, int(tp))


def auto_num_pages(*, num_slots: int, need_pages: int,
                   page_bytes: int, budget_bytes: int = 0,
                   reserved_bytes: int = 0, observed_peak: int = 0):
    """The ``--serve.num-pages`` default: ``(num_pages, rationale)``.

    Sizing, replacing the old blind ``1 + 2 * slots * max_pages``
    heuristic:

    - **serving reservation** ``S = num_slots * need_pages`` — what
      reserve-at-admit can pin with every slot holding a worst-case
      trajectory (``need_pages`` = the workload bound in pages);
    - **prefix-cache headroom** — ``observed_peak`` (a previous run's
      measured ``slot_pages_peak``: the distinct-page working set
      live slots actually held) when available, else ``S``: the cache
      gets room for about one measured working set instead of a
      second dense worst case;
    - **pool** = 1 write-off page + S + headroom, floored at
      ``2 + S`` (one COW page above the reservation — below that
      admission could never clear);
    - an ``hbm_budget_gb`` cap bounds the pool at
      ``(budget - reserved) / page_bytes`` (``reserved`` = the
      non-cache resident bytes, in practice the params), never below
      the floor — the pool must still hold the reservation.

    The rationale lines are printed by serve/run.py so a sizing
    decision is always auditable in the run log.
    """
    serving = int(num_slots) * int(need_pages)
    floor = 2 + serving
    headroom = int(observed_peak) if observed_peak else serving
    pool = 1 + serving + headroom
    lines = [
        f"serving reservation: {num_slots} slots x {need_pages} "
        f"pages = {serving} pages",
        ("prefix-cache headroom: observed slot_pages_peak "
         f"{observed_peak}" if observed_peak else
         f"prefix-cache headroom: {serving} pages (no observed "
         f"slot_pages_peak — worst case)"),
    ]
    if budget_bytes:
        avail = max(0, int(budget_bytes) - int(reserved_bytes))
        cap = avail // max(1, int(page_bytes))
        lines.append(
            f"hbm budget: ({budget_bytes} - {reserved_bytes} "
            f"reserved) / {page_bytes} B/page = {cap} pages")
        pool = min(pool, cap)
    pool = max(pool, floor)
    lines.append(f"num_pages = {pool} (floor {floor})")
    return pool, lines
