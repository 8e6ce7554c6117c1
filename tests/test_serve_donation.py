"""The serve KV cache is donated (PR 26): every program that returns a
cache consumes the one it was handed, so nobody may hold a cache
object across a dispatch.

jax honours donation on CPU (a donated array raises "Array has been
deleted"), so these run in the default tier against the REAL engines
at tiny size: the dense and the paged engine, warmup with and without
a draft model, the NaN quarantine and the live weight swap. The
one-token Pallas write (ops/kv_write.py) runs here in interpret mode,
alone and inside the engine.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_distributed_tpu.serve.scheduler import Request, Scheduler

BUCKETS = (8, 16)
K = 2


@pytest.fixture(scope="module")
def lm():
    from tensorflow_distributed_tpu.models.transformer import gpt_lm

    model = gpt_lm(None, size="tiny", max_len=32, dropout_rate=0.0,
                   compute_dtype=jnp.float32)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture()
def fresh_compiles():
    """Only a FRESH compile reliably reports alias bytes (an
    executable read back from the persistent cache can say 0)."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _engine(kind, lm, **kw):
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    from tensorflow_distributed_tpu.serve.paging.engine import (
        PagedSlotEngine)

    model, params = lm
    if kind == "paged":
        return PagedSlotEngine(model, params, 2, page_size=8,
                               buckets=BUCKETS, **kw)
    return SlotDecodeEngine(model, params, 2, buckets=BUCKETS, **kw)


def _arrays(cache):
    """The leaves a program reads: the K/V (and scale) arrays. The
    scalar compat ``index`` leaves are written, never read, so jit
    prunes them from the arguments and there is nothing to donate."""
    return [c for c in jax.tree_util.tree_leaves(cache)
            if getattr(c, "ndim", 0)]


def _dead(cache) -> bool:
    return all(c.is_deleted() for c in _arrays(cache))


def _nbytes(cache) -> int:
    return sum(c.nbytes for c in _arrays(cache))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 64, size=n).astype(np.int32)


def _admit(eng, prompt, slot) -> int:
    """prefill with the paged engine's page reservation covering the
    steps these tests take."""
    if getattr(eng, "paged", False):
        return eng.prefill(prompt, slot, max_new_tokens=8)
    return eng.prefill(prompt, slot)


# --- (a) every dispatch consumes the cache it was handed ----------------

@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_dispatch_consumes_the_cache(kind, lm):
    eng = _engine(kind, lm, spec_tokens=K)
    for call in (lambda: _admit(eng, _prompt(5), 0),
                 eng.step,
                 lambda: eng.verify_step(np.zeros((2, K), np.int32)),
                 lambda: eng.poison_slot(0)):
        held = eng.cache
        call()
        assert _dead(held), f"{kind}: a program kept its input cache"
        assert not any(c.is_deleted() for c in _arrays(eng.cache))


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_programs_alias_the_whole_cache(kind, lm, fresh_compiles):
    """The compiled decode, verify and insert (paged: prefill, which
    IS its insert) report at least the cache's bytes as aliased."""
    from tensorflow_distributed_tpu.models.generate import lookup_program
    from tensorflow_distributed_tpu.serve import engine as dense
    from tensorflow_distributed_tpu.serve.paging import engine as paged

    eng = _engine(kind, lm, spec_tokens=K)
    pos = jnp.asarray(eng.pos)
    prev, host = eng._step_args(None)
    toks = jnp.zeros((2, K + 1), jnp.int32)
    one = jnp.asarray(1, jnp.int32)
    if kind == "paged":
        tables = jnp.asarray(eng.tables)
        fill = lookup_program(paged._compiled_prefill_paged, eng.model, 8)
        programs = {
            "step": (eng._step_fn,
                     (eng.params, eng.cache, prev, host, tables)),
            "verify": (eng._verify_fn,
                       (eng.params, eng.cache, toks, pos, tables)),
            "prefill": (fill, (eng.params, eng.cache,
                               jnp.zeros((1, 8), jnp.int32),
                               jnp.zeros((1, 8), jnp.int32),
                               tables[:1], one)),
        }
    else:
        row = dense.zero_cache(eng.model, eng.params, 1)
        programs = {
            "step": (eng._step_fn, (eng.params, eng.cache, prev, host)),
            "verify": (eng._verify_fn,
                       (eng.params, eng.cache, toks, pos)),
            "insert": (dense._insert_row, (eng.cache, row, one)),
        }
    want = _nbytes(eng.cache)
    for name, (fn, args) in programs.items():
        mem = fn.lower(*args).compile().memory_analysis()
        assert mem.alias_size_in_bytes >= want, (kind, name)


# --- (b) warmup leaves a fresh engine -----------------------------------

def _draft():
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.serve.speculate import (
        DraftSpeculator)

    draft = gpt_lm(None, size="tiny", n_layers=1, max_len=32,
                   dropout_rate=0.0, compute_dtype=jnp.float32)
    dparams = draft.init(jax.random.key(1),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    return DraftSpeculator(draft, dparams, 2, BUCKETS, K)


def _serve(eng, speculator=None):
    reqs = [Request(rid=i, prompt=_prompt(n, seed=i), max_new_tokens=6)
            for i, n in enumerate((4, 11, 7))]
    done = Scheduler(eng, decode_priority=2,
                     speculator=speculator).run(reqs)
    return {c.rid: list(c.tokens) for c in done}


def _assert_zero(cache, zero):
    for got, want in zip(jax.tree_util.tree_leaves(cache),
                         jax.tree_util.tree_leaves(zero)):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert not np.asarray(got).any()


@pytest.mark.parametrize("kind,drafted", [
    ("dense", False), ("dense", True), ("paged", False)])
def test_warmup_leaves_a_fresh_engine(kind, drafted, lm):
    spec = K if drafted else 0
    eng = _engine(kind, lm, spec_tokens=spec)
    drafter = _draft() if drafted else None
    held = eng.cache, (drafter.cache if drafted else None)
    eng.warmup(drafter)
    assert _dead(held[0]), "warmup kept the pre-warmup cache alive"
    _assert_zero(eng.cache, eng._zero_cache())
    if drafted:
        assert _dead(held[1])
        _assert_zero(drafter.cache, drafter._zero_cache())
        assert not drafter.tok.any() and not drafter.pos.any()
    assert not eng.active.any() and not eng.tok.any()
    assert not eng.pos.any()
    assert (eng.prefills, eng.decode_steps, eng.verify_steps) == (0, 0, 0)
    if kind == "paged":
        assert eng.pool.pages_in_use == 0 and not eng.page_count.any()
    cold = _engine(kind, lm, spec_tokens=spec)
    assert _serve(eng, drafter) == _serve(
        cold, _draft() if drafted else None)


def test_warmup_refuses_a_live_engine(lm):
    eng = _engine("dense", lm)
    _admit(eng, _prompt(5), 0)
    with pytest.raises(RuntimeError, match="before the first admission"):
        eng.warmup()


# --- (c) quarantine and weight swap between donated steps ---------------

@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_quarantine_and_swap_with_the_old_cache_dead(kind, lm):
    """slot_nan drill -> take_bad_slots -> free + re-prefill, and a
    live weight swap between steps: token-identical to an undisturbed
    engine, with every pre-call cache reference dead on the way."""
    model, params = lm
    prompts = {0: _prompt(6, seed=1), 1: _prompt(9, seed=2)}

    def run(disturb):
        eng = _engine(kind, lm)
        out = {s: [_admit(eng, p, s)] for s, p in prompts.items()}
        for i in range(6):
            if disturb and i == 2:
                held = eng.cache
                eng.poison_slot(1)
                assert _dead(held)
                eng.step()
                assert eng.take_bad_slots() == [1]
                held = eng.cache
                eng.free(1)
                # Re-prefill what the slot had emitted so far, as the
                # scheduler's quarantine does.
                redo = np.concatenate(
                    [prompts[1], np.asarray(out[1][:-1], np.int32)])
                assert _admit(eng, redo, 1) == out[1][-1]
                assert _dead(held)
                # Slot 0 advanced through the poisoned step untouched.
                out[0].append(int(eng.tok[0]))
                continue
            if disturb and i == 4:
                eng.swap_params(jax.tree_util.tree_map(
                    lambda p: p + 0, params))
            held = eng.cache
            nxt = eng.step()
            assert _dead(held)
            for s in prompts:
                if eng.step_valid[s]:
                    out[s].append(int(nxt[s]))
        return out

    plain, fired = run(False), run(True)
    assert fired[0] == plain[0]
    # Slot 1 lost the poisoned step, and the step that was in flight
    # when it was re-admitted has no token for it (step_valid): two
    # tokens behind, same stream.
    assert fired[1] == plain[1][:len(fired[1])]
    assert len(fired[1]) == len(plain[1]) - 2


# --- the one-token Pallas write (interpret mode) ------------------------

def _ref_write(buf, new, start):
    return jax.vmap(lambda b, n, s: jax.lax.dynamic_update_slice(
        b, n, (s, 0, 0)))(buf, new, start)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_token_write_matches_the_vmapped_update(dtype):
    from tensorflow_distributed_tpu.ops.kv_write import token_write

    B, T, nk, dh = 5, 256, 3, 16
    rng = np.random.default_rng(0)
    buf = jnp.asarray(rng.normal(size=(B, T, nk, dh)), dtype)
    new = jnp.asarray(rng.normal(size=(B, 1, nk, dh)), dtype)
    # Block edges, both ends, and a start past the end (clamps).
    start = jnp.asarray([0, 127, 128, 255, 400], jnp.int32)
    got = token_write(buf, new, start, interpret=True)
    want = _ref_write(buf, new, start)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_token_write_gate():
    from tensorflow_distributed_tpu.ops import kv_write

    ok = (16, 1024, 20, 64)
    assert kv_write.supported(ok, jnp.bfloat16)
    assert kv_write.supported(ok, jnp.float32)
    assert not kv_write.supported(ok, jnp.int8)             # quantized
    assert not kv_write.supported((16, 1024, 20), jnp.float32)  # scales
    assert not kv_write.supported((16, 1000, 20, 64), jnp.bfloat16)
    assert not kv_write.supported((16, 1024, 8, 128), jnp.bfloat16)
    assert not kv_write.supported((16, 1024, 64, 96), jnp.float32)
    # Off the TPU the XLA form stays, whatever the shape.
    assert not kv_write.use_token_write(ok, jnp.bfloat16)


def test_engine_with_the_token_write_equals_generate(monkeypatch):
    """The decode path with the kernel switched in (as on the chip;
    interpret mode here) serves the tokens one-shot generate() gives
    with the XLA write."""
    from tensorflow_distributed_tpu.models.generate import generate
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.ops import kv_write
    from tensorflow_distributed_tpu.serve import engine as engine_mod
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

    model = gpt_lm(None, size="tiny", max_len=128, dropout_rate=0.0,
                   compute_dtype=jnp.float32)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    prompts = [_prompt(n, seed=n) for n in (5, 12)]
    want = [np.asarray(generate(model, params, jnp.asarray(p[None]),
                                8))[0] for p in prompts]
    calls = []
    real = kv_write.token_write

    def counted(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(kv_write, "token_write", counted)
    monkeypatch.setattr(
        kv_write, "use_token_write",
        lambda shape, dtype, mesh=None: kv_write.supported(shape, dtype))
    # The decode program is cached by model: drop any traced with the
    # XLA form before, and the one traced with the kernel after.
    engine_mod._compiled_step.cache_clear()
    try:
        eng = SlotDecodeEngine(model, params, 2, buckets=(16,))
        done = Scheduler(eng, decode_priority=2).run(
            [Request(rid=i, prompt=p, max_new_tokens=8)
             for i, p in enumerate(prompts)])
    finally:
        engine_mod._compiled_step.cache_clear()
    assert calls, "the decode step did not take the kernel"
    for c in done:
        np.testing.assert_array_equal(np.asarray(c.tokens), want[c.rid])
