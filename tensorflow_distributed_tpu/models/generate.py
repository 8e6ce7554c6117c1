"""Autoregressive generation with a KV cache.

The reference's "inference" was a timed validation pass over MNIST
(mnist_single.py:124-134) — classification only. The LM family here
gets the real thing: prefill the prompt in one pass, then decode one
token per step against per-layer KV caches ([B, max_len, H, Dh],
static shapes, updated in place via dynamic_update_slice), the whole
loop a single ``lax.scan`` under jit — no per-token host round-trips,
no recompilation, O(L) attention per new token instead of O(L^2)
re-forwarding.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from tensorflow_distributed_tpu.observe import device as observe_device
from tensorflow_distributed_tpu.observe.registry import emit_event

# --- compiled-program cache accounting ---------------------------------
#
# Every jitted program here is built by an lru_cache'd factory; a MISS
# means a fresh trace + XLA compile (seconds to minutes), a HIT reuses
# the executable. Retrace storms — e.g. a caller cycling max_new_tokens
# or sampler knobs per request — show up as a climbing miss count, so
# the counts are queryable (compile_cache_stats) and each miss emits a
# "compile_cache" record through the active observe registry.

_compile_events = {"hits": 0, "misses": 0}


def compile_cache_stats() -> dict:
    """Cumulative compiled-program cache hits/misses (process-wide,
    all program factories in this module plus serve/engine.py's
    bucketed prefill)."""
    return dict(_compile_events)


def lookup_program(factory, *key):
    """Fetch ``factory(*key)`` counting lru_cache hits/misses; a miss
    (a fresh trace+compile) also emits a ``compile_cache`` observe
    record naming the factory, so retrace storms are visible in the
    run's JSONL instead of only as mysterious wall time."""
    before = factory.cache_info().misses
    fn = factory(*key)
    if factory.cache_info().misses > before:
        _compile_events["misses"] += 1
        emit_event("compile_cache", program=factory.__name__,
                   result="miss", **_compile_events)
    else:
        _compile_events["hits"] += 1
    return fn


def prefill_cache(model, params, prompt: jax.Array,
                  positions: Optional[jax.Array] = None, **model_kw):
    """One forward pass over ``prompt`` [B, P] that populates every
    layer's KV cache — THE prefill, shared by greedy decoding, beam
    search, and the serving engine's bucketed prefill programs
    (serve/engine.py). Returns (logits [B, P, V], cache pytree).

    ``positions`` defaults to arange(P) (a fresh cache); pass explicit
    positions to prefill at an offset. ``model_kw`` goes to the model
    (a family that can compute the logits of one position only takes
    ``logits_at``)."""
    if positions is None:
        positions = jnp.arange(prompt.shape[1])[None, :]
    logits, state = model.apply(
        {"params": params}, prompt, decode=True,
        positions=positions, mutable=["cache"], **model_kw)
    return logits, state["cache"]


def decode_token(model, params, cache, tok: jax.Array,
                 positions: jax.Array, stats: bool = False):
    """One single-token decode step against the cache — THE decode
    step, shared by greedy decoding, beam search, and the serving
    engine. ``tok`` [B] int32; ``positions`` [B] (per-row cache
    depths — the serving engine's slots differ) or [1] (every row in
    lockstep). Returns (last-position logits [B, V], updated cache);
    with ``stats`` also what the model sowed into its ``stats``
    collection this step."""
    pos = jnp.asarray(positions, jnp.int32)
    if pos.ndim == 0:
        pos = pos[None]
    logits, state = model.apply(
        {"params": params, "cache": cache}, tok[:, None], decode=True,
        positions=pos[:, None],
        mutable=["cache", "stats"] if stats else ["cache"])
    if stats:
        return logits[:, -1, :], state["cache"], state.get("stats", {})
    return logits[:, -1, :], state["cache"]


def _filter_logits(logits: jax.Array, top_k: int, top_p: float
                   ) -> jax.Array:
    """Mask logits outside the top-k / nucleus (top-p) candidate set.

    Both filters are static-shape TPU-friendly: top-k keeps the k-th
    value as a threshold (no gather/scatter of dynamic extent); top-p
    sorts once, finds the smallest prefix with cumulative probability
    >= p, and thresholds on that boundary logit. Filtered entries go to
    -inf so ``jax.random.categorical`` never picks them.
    """
    if top_k > 0 and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep the minimal prefix whose mass reaches p (always >= 1
        # token: the first prefix that crosses p is included).
        keep = cum - probs < top_p
        # Smallest kept logit bounds the nucleus from below.
        boundary = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
            keepdims=True)
        logits = jnp.where(logits < boundary, -jnp.inf, logits)
    return logits


@functools.lru_cache(maxsize=32)
def _compiled(model, max_new_tokens: int, temperature: float,
              top_k: int, top_p: float):
    """One jitted prefill+decode program per (model, N, sampler knobs).

    Cached so repeat generate() calls reuse the compiled executable
    (jit's cache is keyed on the function object — a closure rebuilt
    per call would retrace every time). Flax modules are frozen
    dataclasses, hence hashable cache keys.
    """

    def run(params, prompt, key):
        P = prompt.shape[1]
        # Prefill: one pass over the prompt populates every layer cache.
        logits, cache = prefill_cache(model, params, prompt)

        def pick(last, key):
            if temperature == 0.0:
                return jnp.argmax(last, axis=-1).astype(jnp.int32)
            last = _filter_logits(last / temperature, top_k, top_p)
            return jax.random.categorical(
                key, last, axis=-1).astype(jnp.int32)

        def step(carry, _):
            cache, tok, pos, key = carry
            key, sub = jax.random.split(key)
            last, cache = decode_token(model, params, cache, tok,
                                       pos[None])
            nxt = pick(last, sub)
            return (cache, nxt, pos + 1, key), nxt

        key, sub = jax.random.split(key)
        first = pick(logits[:, -1, :], sub)
        (_, _, _, _), toks = jax.lax.scan(
            step, (cache, first, jnp.asarray(P, jnp.int32), key),
            None, length=max_new_tokens - 1)
        return jnp.concatenate([first[:, None], toks.T], axis=1)

    # The registry name carries the FULL lru key beyond the model:
    # distinct sampler knobs are distinct resident executables, and
    # aliasing them under one name would make the HBM budget rollup
    # undercount what actually stays loaded.
    name = f"generate_n{max_new_tokens}"
    if temperature != 0.0:
        name += f"_t{temperature:g}_k{top_k}_p{top_p:g}"
    return observe_device.instrument_jit(name, run)


def generate(model, params, prompt: jax.Array, max_new_tokens: int, *,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
             key: Optional[jax.Array] = None) -> jax.Array:
    """Continue ``prompt`` [B, P] by ``max_new_tokens`` greedy
    (temperature 0) or sampled tokens. Returns [B, max_new_tokens].

    ``model`` is a causal TransformerLM (models/transformer.py). The
    mesh's seq axis must be 1 (single-token steps can't be
    seq-sharded); batch stays sharded over "data" as usual.

    Sampling knobs (active only with ``temperature > 0``):
    ``top_k > 0`` restricts to the k highest-logit tokens; ``top_p <
    1.0`` restricts to the smallest nucleus whose probability mass
    reaches p (Holtzman et al.); both may be combined (k first, then p
    over the survivors).
    """
    cfg = model.cfg
    if not cfg.causal:
        raise ValueError("generate() needs a causal model")
    B, P = prompt.shape
    if P + max_new_tokens > cfg.max_len:
        raise ValueError(
            f"prompt {P} + {max_new_tokens} new > max_len {cfg.max_len}")
    if temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    key = key if key is not None else jax.random.key(0)
    if temperature == 0.0:
        # Greedy ignores the sampler knobs — normalize them so the
        # compile cache isn't fragmented by values the program never
        # reads.
        top_k, top_p = 0, 1.0
    return lookup_program(_compiled, model, max_new_tokens, temperature,
                          top_k, float(top_p))(params, prompt, key)


@functools.lru_cache(maxsize=32)
def _compiled_beam(model, max_new_tokens: int, num_beams: int,
                   length_penalty: float, eos_id: int):
    """One jitted beam-search program per (model, N, K, penalty, eos).

    TPU-native shape discipline: beams ride a flat [B*K] batch through
    the SAME cached decode path greedy uses (prefill once per beam,
    one token per step under lax.scan, static shapes everywhere); the
    per-step reindex after top-k is a batched gather of the cache
    pytree along the flat beam dim.
    """

    def run(params, prompt):
        B, P = prompt.shape
        K = num_beams
        V = model.cfg.vocab_size
        NEG = jnp.asarray(-1e30, jnp.float32)

        # Prefill ONCE per batch row, then tile the cache to [B*K]:
        # the K beam copies are byte-identical, so repeating the
        # cache leaves costs 1/K of the prompt-dominant prefill
        # FLOPs and HBM traffic that repeating the PROMPT would.
        logits, pre = prefill_cache(model, params, prompt)
        cache = jax.tree_util.tree_map(
            lambda c: jnp.repeat(c, K, axis=0)
            if getattr(c, "ndim", 0) and c.shape[0] == B else c,
            pre)
        logp0 = jax.nn.log_softmax(
            logits[:, -1, :].astype(jnp.float32))      # [B, V]
        # First expansion: B x top-K over the vocab seeds the beams.
        scores, tok0 = jax.lax.top_k(logp0, K)         # [B, K]
        toks0 = tok0.reshape(B * K).astype(jnp.int32)
        alive0 = (toks0.reshape(B, K) != eos_id) if eos_id >= 0 else \
            jnp.ones((B, K), bool)

        def step(carry, i):
            cache, scores, alive, tok = carry
            # Fed token sits AT position P + i.
            last, cache = decode_token(model, params, cache, tok,
                                       jnp.full((1,), P + i))
            logp = jax.nn.log_softmax(
                last.astype(jnp.float32)).reshape(B, K, V)
            # Finished beams emit ONLY eos at zero cost, so they keep
            # their score and stay comparable with live beams.
            if eos_id >= 0:
                frozen = jnp.full((V,), NEG).at[eos_id].set(0.0)
                logp = jnp.where(alive[..., None], logp, frozen)
            cand = scores[..., None] + logp            # [B, K, V]
            flat_scores, flat_idx = jax.lax.top_k(
                cand.reshape(B, K * V), K)             # [B, K]
            beam_idx = flat_idx // V                   # [B, K]
            new_tok = (flat_idx % V).astype(jnp.int32)
            gather = (jnp.arange(B)[:, None] * K
                      + beam_idx).reshape(B * K)       # flat reindex
            cache = jax.tree_util.tree_map(
                lambda c: jnp.take(c, gather, axis=0)
                if getattr(c, "ndim", 0) and c.shape[0] == B * K else c,
                cache)
            alive = jnp.take_along_axis(alive, beam_idx, axis=1)
            if eos_id >= 0:
                alive = jnp.logical_and(alive, new_tok != eos_id)
            return ((cache, flat_scores, alive,
                     new_tok.reshape(B * K)),
                    (new_tok, beam_idx))

        (_, scores, _, _), (toks, parents) = jax.lax.scan(
            step, (cache, scores, alive0, toks0),
            jnp.arange(max_new_tokens - 1))

        # Backtrack parents to materialize each beam's token path.
        def back(carry, sp):
            ptr = carry                                # [B, K]
            t, par = sp
            tok_here = jnp.take_along_axis(t, ptr, axis=1)
            ptr = jnp.take_along_axis(par, ptr, axis=1)
            return ptr, tok_here

        ptr0 = jnp.tile(jnp.arange(K)[None], (B, 1))
        ptr, rev = jax.lax.scan(back, ptr0, (toks, parents),
                                reverse=True)
        first = jnp.take_along_axis(tok0, ptr, axis=1) # [B, K]
        seq = jnp.concatenate([first[:, :, None],
                               jnp.moveaxis(rev, 0, 2)], axis=2)
        # Length-normalized ranking (GNMT-style): finished beams are
        # shorter than max_new_tokens only when eos fired; count real
        # tokens up to and including the first eos.
        if eos_id >= 0:
            is_eos = seq == eos_id
            any_eos = is_eos.any(axis=2)
            first_eos = jnp.argmax(is_eos, axis=2)
            length = jnp.where(any_eos, first_eos + 1, seq.shape[2])
        else:
            length = jnp.full((B, K), seq.shape[2])
        norm = scores / (length.astype(jnp.float32) ** length_penalty)
        order = jnp.argsort(-norm, axis=1)
        seq = jnp.take_along_axis(seq, order[:, :, None], axis=1)
        return seq, jnp.take_along_axis(norm, order, axis=1)

    return observe_device.instrument_jit(
        f"beam_search_n{max_new_tokens}_k{num_beams}"
        f"_lp{length_penalty:g}_eos{eos_id}", run)


def beam_search(model, params, prompt: jax.Array, max_new_tokens: int,
                *, num_beams: int = 4, length_penalty: float = 1.0,
                eos_id: Optional[int] = None):
    """Beam-search continuation of ``prompt`` [B, P]: returns
    (sequences [B, num_beams, max_new_tokens], scores [B, num_beams]),
    beams sorted best-first by length-normalized log-probability
    (GNMT ``length_penalty``; 0 disables normalization).

    ``eos_id``: beams that emit it freeze (score kept, eos-padded) —
    the standard early-finish semantics; None runs every beam to the
    full budget. num_beams=1 is exactly greedy decoding (tested).
    Same requirements as ``generate`` (causal model, mesh seq 1)."""
    cfg = model.cfg
    if not cfg.causal:
        raise ValueError("beam_search() needs a causal model")
    B, P = prompt.shape
    if P + max_new_tokens > cfg.max_len:
        raise ValueError(
            f"prompt {P} + {max_new_tokens} new > max_len {cfg.max_len}")
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    if num_beams > cfg.vocab_size:
        raise ValueError(
            f"num_beams {num_beams} > vocab_size {cfg.vocab_size} "
            "(the first expansion is a top-k over the vocabulary)")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if eos_id is not None and not 0 <= eos_id < cfg.vocab_size:
        raise ValueError(f"eos_id {eos_id} outside vocab "
                         f"[0, {cfg.vocab_size})")
    return lookup_program(_compiled_beam, model, max_new_tokens,
                          num_beams, float(length_penalty),
                          -1 if eos_id is None else int(eos_id))(
        params, prompt)
