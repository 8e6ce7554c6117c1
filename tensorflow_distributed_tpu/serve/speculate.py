"""Speculative decoding for the slot engine: propose k, verify once.

The decode loop's cost is one program dispatch per token per slot.
Speculation changes the exchange rate: a cheap DRAFT proposes
``spec_tokens`` tokens per slot, and ONE jitted verify program
(serve/engine.py::_compiled_verify) scores every proposal against the
target model in a single forward over the slot's KV cache — the
longest greedy-consistent prefix is accepted, plus the verify's own
next token (the "bonus"), so each dispatch yields ``accepted + 1``
tokens instead of 1. Output is TOKEN-IDENTICAL to non-speculative
greedy decode by construction: every emitted token is the target
model's own argmax given the accepted prefix; the draft only decides
how many of them one dispatch gets to emit (pinned in
tests/test_serve_slo.py and, on the real engines, in
tests/test_serve_ahead.py).

Two proposers:

- :class:`SelfDraft` (the default, ``--serve.draft-config`` unset):
  k-gram prompt-lookup over the request's OWN history (prompt + tokens
  so far) — find the most recent earlier occurrence of the current
  ``spec_kgram``-token suffix and propose what followed it. Pure host
  work, no second model, no extra device programs; repetitive greedy
  tails (the common case) make it accurate.
- :class:`DraftSpeculator` (``--serve.draft-config "tiny"`` or
  ``"size=tiny,n_layers=1"``): a smaller model of the same transformer
  family runs its own slot cache in lockstep (mirrored prefill/insert
  via the engine's program factories, one jitted ``serve_draft_k*``
  scan per proposal round). Fresh-init params — the draft's QUALITY
  only moves the accept rate, never the output.

Static-shape discipline: the draft scan and the verify program are
fixed-shape per (model, k) and censused in the jaxpr goldens
(``serve_verify``); rollback-on-reject is position bookkeeping, not a
program — rejected cache rows sit PAST every slot's authoritative
position and are overwritten by the next verify's writes before
anything can attend them (see ``SlotDecodeEngine.verify_step``).

Known draft-model limitation (ROADMAP item 1 follow-up): plain-step
FALLBACK rounds (engine.can_verify false) advance the engine without
running the draft, so ``DraftSpeculator.sync_from`` adopts positions
whose draft-cache rows were never written. Output stays correct (the
draft only proposes), but subsequent draft attends read those holes
and the accept rate can quietly degrade after fallback rounds — a
draft re-prefill on resync would close it.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tensorflow_distributed_tpu.models.generate import (
    decode_token, lookup_program)
from tensorflow_distributed_tpu.observe import device as observe_device


def accept_length(props: np.ndarray, nxt: np.ndarray) -> int:
    """Longest greedy-consistent prefix: how many leading proposals
    match the target model's own argmax chain. ``props`` [K] is what
    the draft proposed, ``nxt`` [K+1] is the verify program's argmax at
    each fed position (``nxt[j]`` = the target's token after consuming
    the prefix through proposal j-1). Pure host, jax-free — the fake
    engines share it."""
    props = np.asarray(props).reshape(-1)
    nxt = np.asarray(nxt).reshape(-1)
    k = len(props)
    if len(nxt) != k + 1:
        raise ValueError(
            f"verify returned {len(nxt)} tokens for {k} proposals "
            f"(want k + 1: one per proposal plus the bonus)")
    a = 0
    while a < k and props[a] == nxt[a]:
        a += 1
    return a


def kgram_propose(history: Sequence[int], k: int, g: int = 3
                  ) -> List[int]:
    """Prompt-lookup proposal: find the most recent EARLIER occurrence
    of the history's last-``g`` suffix and propose the ``k`` tokens
    that followed it (a continuation shorter than ``k`` pads by
    repeating its final token). No match — or history shorter than the
    suffix — falls back to repeating the last token, which is exactly
    right for the degenerate argmax loops fresh-init models settle
    into."""
    hist = [int(t) for t in history]
    if not hist:
        return [0] * k
    n = len(hist)
    g = min(g, n)
    suffix = hist[n - g:]
    # Scan right-to-left for the most recent earlier match (the suffix
    # itself ends at n, so candidate starts end before n - 1).
    for i in range(n - g - 1, -1, -1):
        if hist[i:i + g] == suffix:
            out = hist[i + g:i + g + k]
            while len(out) < k:
                out.append(out[-1] if out else hist[-1])
            return out
    return [hist[-1]] * k


class SelfDraft:
    """k-gram self-draft (no draft model): proposals come from each
    live request's own token history. Host-only; the scheduler feeds
    histories per live slot."""

    #: The scheduler builds per-slot history lists only for proposers
    #: that read them (O(prompt + decoded) host work per step).
    needs_histories = True

    def __init__(self, num_slots: int, k: int, g: int = 3):
        if k < 1:
            raise ValueError(f"spec_tokens must be >= 1, got {k}")
        self.num_slots = num_slots
        self.k = k
        self.g = g

    def set_k(self, k: int) -> None:
        """Live depth change (autopilot loop 3): the k-gram proposer
        is host-only, so a new k is just a wider/narrower lookup."""
        if k < 1:
            raise ValueError(f"spec_tokens must be >= 1, got {k}")
        self.k = int(k)

    def propose(self, histories: Dict[int, Sequence[int]]
                ) -> np.ndarray:
        """[num_slots, k] int32 proposals; rows without a history
        (inactive slots) are zeros — the verify program runs them as
        padding the scheduler never reads."""
        props = np.zeros((self.num_slots, self.k), np.int32)
        for slot, hist in histories.items():
            props[slot] = kgram_propose(hist, self.k, self.g)
        return props

    # Lifecycle hooks the scheduler calls uniformly; the self-draft
    # carries no device state, so they are no-ops.
    def observe_admit(self, slot, prompt, first_tok):  # pragma: no cover
        pass

    def observe_free(self, slot):  # pragma: no cover
        pass

    def sync_from(self, engine):  # pragma: no cover
        pass

    def warmup(self):  # pragma: no cover - nothing to compile
        pass


@functools.lru_cache(maxsize=8)
def _compiled_draft(model, k: int):
    """The draft proposal program: ``k`` greedy tokens for every slot
    at its own depth, one ``lax.scan`` under jit. The scan runs k + 1
    decode ticks: the extra tick FEEDS the last proposal so its K/V
    lands in the draft cache — without it, a fully-accepted round
    leaves a permanent hole at the old frontier that every later draft
    step would attend (the target cache never has this problem: its
    verify always re-feeds the pending token)."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(params, cache, tok, pos):
        def body(carry, _):
            cache, tok, pos = carry
            last, cache = decode_token(model, params, cache, tok, pos)
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
            return (cache, nxt, pos + 1), nxt

        (cache, _, _), toks = jax.lax.scan(
            body, (cache, tok, pos), None, length=k + 1)
        return cache, toks.T[:, :k]            # [S, k]

    return observe_device.instrument(f"serve_draft_k{k}", run)


def parse_draft_config(spec: str) -> dict:
    """``--serve.draft-config`` grammar: a bare size preset ("tiny")
    or comma-separated ``key=value`` TransformerConfig overrides with
    an optional ``size=`` entry (ints parsed, everything else kept as
    a string). Returns {"size": ..., "overrides": {...}}."""
    spec = spec.strip()
    if not spec:
        raise ValueError("empty draft_config")
    if "=" not in spec:
        return {"size": spec, "overrides": {}}
    size = "tiny"
    overrides = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"draft_config entry {part!r} is not key=value (or "
                f"pass a bare size preset like 'tiny')")
        key, val = (x.strip() for x in part.split("=", 1))
        if key == "size":
            size = val
            continue
        try:
            overrides[key] = int(val)
        except ValueError:
            overrides[key] = val
    return {"size": size, "overrides": overrides}


class DraftSpeculator:
    """A draft MODEL proposing ``k`` tokens per round from its own
    mirrored slot cache. The mirror reuses the engine's program
    factories (bucketed prefill + traced-slot row insert), so the
    draft admits with the same bounded-program discipline; its
    positions re-sync from the engine after every verify, and rejected
    draft rows are overwritten before attention can see them — the
    same argument as the target cache (module docstring)."""

    needs_histories = False   # the draft's cache IS its history

    def __init__(self, model, params, num_slots: int,
                 buckets: Sequence[int], k: int):
        from tensorflow_distributed_tpu.serve.engine import (
            _insert_row, _compiled_prefill, zero_cache)
        if k < 1:
            raise ValueError(f"spec_tokens must be >= 1, got {k}")
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.buckets = tuple(buckets)
        self.k = k
        self._insert = _insert_row
        self._prefill_factory = _compiled_prefill
        self._zero_cache = functools.partial(zero_cache, model, params,
                                             num_slots)
        self.cache = self._zero_cache()
        self.tok = np.zeros((num_slots,), np.int32)
        self.pos = np.zeros((num_slots,), np.int32)
        self._propose_fn = lookup_program(_compiled_draft, model, k)

    def set_k(self, k: int) -> None:
        """Live depth change (autopilot loop 3): rebind the proposal
        scan at the new k through the same ``lookup_program`` cache
        the ctor used — a revisited k is a dict hit, a new one
        compiles on the next propose. The draft cache/positions are
        untouched: the scan length is the only thing k shapes."""
        if k < 1:
            raise ValueError(f"spec_tokens must be >= 1, got {k}")
        if int(k) == self.k:
            return
        self.k = int(k)
        self._propose_fn = lookup_program(_compiled_draft, self.model,
                                          self.k)

    def observe_admit(self, slot: int, prompt, first_tok: int) -> None:
        """Mirror an engine admission: prefill the draft cache row for
        ``slot``; the pending token is the TARGET's first token (the
        draft's own prediction is discarded — it proposes, never
        emits)."""
        from tensorflow_distributed_tpu.serve.buckets import pick_bucket
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        bucket = pick_bucket(len(prompt), self.buckets)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        fn = lookup_program(self._prefill_factory, self.model, bucket)
        row, _ = fn(self.params, jnp.asarray(padded),
                    jnp.asarray(len(prompt), jnp.int32))
        self.cache = self._insert(self.cache, row,
                                  jnp.asarray(slot, jnp.int32))
        self.tok[slot] = first_tok
        self.pos[slot] = len(prompt)

    def observe_free(self, slot: int) -> None:
        self.tok[slot] = 0
        self.pos[slot] = 0

    def warmup(self) -> None:
        """Dispatch every draft-mirror program once — each bucket's
        prefill, the row insert, the proposal scan — so the FIRST
        speculative round pays compute, not compile.
        ``SlotDecodeEngine.warmup(speculator)`` calls this right after
        warming its own programs. Like the engine's, the programs
        consume the live cache, which is dropped before a zero one is
        built, so a warmed draft equals a fresh one (compile-counter
        pinned in tests/test_serve_observe.py)."""
        for b in self.buckets:
            fn = lookup_program(self._prefill_factory, self.model, b)
            row, _ = fn(self.params, jnp.zeros((1, b), jnp.int32),
                        jnp.asarray(1, jnp.int32))
            self.cache = self._insert(self.cache, row,
                                      jnp.asarray(0, jnp.int32))
        self.cache, _ = self._propose_fn(
            self.params, self.cache, jnp.asarray(self.tok),
            jnp.asarray(self.pos))
        # graftcheck: disable=host-sync-in-loop -- startup-only drain
        # of the warmup dispatches; runs once per process, never in
        # the decode loop
        jax.block_until_ready(self.cache)
        self.cache = None
        self.cache = self._zero_cache()

    def sync_from(self, engine) -> None:
        """Adopt the engine's authoritative pending token/position per
        slot after a verify (or fallback plain step) retired — the
        draft's cache rows past these positions are dead and will be
        overwritten by its next propose."""
        self.tok[:] = engine.tok
        self.pos[:] = engine.pos

    def propose(self, histories: Dict[int, Sequence[int]]
                ) -> np.ndarray:
        """[num_slots, k] proposals from the draft model (histories
        are ignored — the draft's cache IS its history)."""
        self.cache, props = self._propose_fn(
            self.params, self.cache, jnp.asarray(self.tok),
            jnp.asarray(self.pos))
        # graftcheck: disable=host-sync-in-loop -- the draft's OUTPUT:
        # proposals must reach the host to drive the verify call; one
        # [num_slots, k] fetch per proposal round is the contract
        return np.asarray(jax.device_get(props), np.int32)


def build_speculator(cfg, model, params_seed: int, num_slots: int,
                     buckets: Sequence[int]) -> Optional[object]:
    """serve_run's factory: ``spec_tokens == 0`` -> None;
    ``draft_config`` unset -> :class:`SelfDraft`; otherwise build the
    draft model (same family/vocab/max_len as the target, fresh-init
    params — draft quality moves accept rate, never output) and wrap
    it in a :class:`DraftSpeculator`. The draft is built MESH-LESS,
    matching today's single-device-set engine; threading the serve
    mesh through is part of ROADMAP item 1's open sharded-serving
    half."""
    serve = cfg.serve
    if not serve.spec_tokens:
        return None
    if not serve.draft_config:
        return SelfDraft(num_slots, serve.spec_tokens,
                         g=serve.spec_kgram)
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    parsed = parse_draft_config(serve.draft_config)
    overrides = dict(parsed["overrides"])
    overrides.setdefault("vocab_size", model.cfg.vocab_size)
    overrides.setdefault("max_len", model.cfg.max_len)
    overrides.setdefault("compute_dtype", model.cfg.compute_dtype)
    draft = gpt_lm(mesh=None, size=parsed["size"], dropout_rate=0.0,
                   **overrides)
    params = draft.init(
        jax.random.key(params_seed),
        jnp.zeros((1, 8), jnp.int32))["params"]
    return DraftSpeculator(draft, params, num_slots, buckets,
                           serve.spec_tokens)
