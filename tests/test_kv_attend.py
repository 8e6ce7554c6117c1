"""ops/kv_attend.py (PR 48), in interpret mode: the decode attend over a
``[B, T, nk, dh]`` K/V cache that reads each live row to its depth equals
``full_attention`` under the position mask at every depth and shape the
dense slot engine hands it; its visits are what its grid fetches; its
gate is the one-token write's; the engine serves the same tokens with the
kernel switched in and counts on the host what its launches' attends
covered. What Mosaic makes of it is tests/test_tpu_compile.py's."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_distributed_tpu.ops import kv_attend
from tensorflow_distributed_tpu.ops.latent_attention import (
    dense_attend_schedule)
from tensorflow_distributed_tpu.parallel.ring_attention import full_attention
from tensorflow_distributed_tpu.serve.scheduler import Request, Scheduler

# (B, T, nk, dh, query heads): GPT-2's tiny (4 heads of 8, one block), the
# serve cells' leaf at fewer slots and heads (two 512-position blocks),
# the leaf itself, and a grouped cache (3 queries a key-value head).
SHAPES = {"tiny": (4, 128, 4, 8, 4), "large_small": (4, 1024, 5, 64, 5),
          "large": (16, 1024, 20, 64, 20), "grouped": (4, 384, 2, 64, 6)}


def _depths(case: str, B: int, T: int):
    edge = {"free": 0, "one": 1, "below_edge": 127, "edge": 128 % T,
            "past_edge": 129 % T, "last": T - 1}
    if case in edge:
        return [edge[case]] * B
    # live rows of every kind with free slots first, between and last
    mixed = [0, T - 1, 0, 1, 129 % T, 0, 127, 128 % T]
    return [mixed[i % len(mixed)] for i in range(B)]


def _case(shape, case, dtype=jnp.bfloat16, seed=0):
    B, T, nk, dh, h = shape
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, 1, h, dh)), dtype)
    kc = jnp.asarray(rng.normal(size=(B, T, nk, dh)), dtype)
    vc = jnp.asarray(rng.normal(size=(B, T, nk, dh)), dtype)
    pos = jnp.asarray(_depths(case, B, T), jnp.int32)
    return q, kc, vc, pos


def _masked_full_attention(q, kc, vc, pos):
    """models/transformer.py's XLA attend: the bias over the whole
    leaf, the cache widened to the query heads."""
    g = q.shape[2] // kc.shape[2]
    bias = jnp.where(jnp.arange(kc.shape[1])[None, None, :]
                     <= pos[:, None, None], 0.0, -1e30)
    return full_attention(q, jnp.repeat(kc, g, axis=2),
                          jnp.repeat(vc, g, axis=2), bias)


@pytest.mark.parametrize("case", ["free", "one", "below_edge", "edge",
                                  "past_edge", "last", "mixed"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_decode_attend_is_full_attention_under_the_position_mask(
        monkeypatch, shape, case):
    # 128-position blocks: every shape walks several, and 127, 128 and
    # 129 fall on both sides of an edge
    monkeypatch.setattr(kv_attend, "BLOCK_T", 128)
    q, kc, vc, pos = _case(SHAPES[shape], case)
    v_new = jnp.take_along_axis(vc, pos[:, None, None, None], axis=1)
    got = kv_attend.decode_attend(q, kc, vc, pos, v_new, interpret=True)
    want = _masked_full_attention(q, kc, vc, pos)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


@pytest.mark.parametrize("block", [128, 256, 512])
def test_decode_attend_in_float32_and_in_every_block(monkeypatch, block):
    monkeypatch.setattr(kv_attend, "BLOCK_T", block)
    q, kc, vc, pos = _case(SHAPES["large_small"], "mixed", jnp.float32)
    assert kv_attend.block(kc.shape, kc.dtype) == block
    v_new = jnp.take_along_axis(vc, pos[:, None, None, None], axis=1)
    got = kv_attend.decode_attend(q, kc, vc, pos, v_new, interpret=True)
    np.testing.assert_allclose(
        got, _masked_full_attention(q, kc, vc, pos), atol=2e-5)


def test_a_row_at_position_zero_is_what_the_step_wrote_not_the_cache():
    """Position 0 sees one key: the result is ``v_new`` whatever the
    cache row holds (a free slot's is never read)."""
    q, kc, vc, _ = _case(SHAPES["tiny"], "free")
    pos = jnp.zeros((q.shape[0],), jnp.int32)
    v_new = jnp.full_like(vc[:, :1], 3.0)
    got = kv_attend.decode_attend(q, kc, jnp.full_like(vc, jnp.nan), pos,
                                  v_new, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32), 3.0)


@pytest.mark.parametrize("pos", [
    [0, 1, 127, 0, 128, 129, 511, 0], [0, 0, 0, 5], [300, 0, 0, 7],
    [0, 0, 0, 0], [511, 511]])
def test_visits_are_what_the_kernels_grid_fetches(monkeypatch, pos):
    """The kernel's grid walked on the host with its own schedule and its
    own predicate (tests/test_exaone_moe.py's walk): the blocks it
    computes on and the blocks its index map makes it fetch both cover
    ``visits`` positions, none in a free slot or past a row's depth."""
    monkeypatch.setattr(kv_attend, "BLOCK_T", 128)
    shape = (len(pos), 512, 4, 8)
    bt = kv_attend.block(shape, jnp.bfloat16)
    p = jnp.asarray(pos, jnp.int32)
    row, lo, hi = (np.asarray(a) for a in dense_attend_schedule(p, bt))
    computed, fetched, held = 0, 0, None
    for b in range(len(pos)):
        for j in range(shape[1] // bt):
            block = (int(row[b]), int(np.clip(j, lo[b], hi[b])))
            if block != held:
                fetched += 1
                held = block
                assert pos[block[0]] > 0 or not any(pos)
                assert block[1] * bt <= pos[block[0]]
            if pos[b] > 0 and j * bt <= pos[b]:
                computed += 1
                assert block == (b, j)
    visits = int(kv_attend.visits(p, shape, jnp.bfloat16))
    assert computed * bt == visits
    assert fetched * bt == (visits if any(pos) else bt)
    assert visits == sum((x // bt + 1) * bt for x in pos if x > 0)


def test_gate_and_block():
    ok = (16, 1024, 20, 64)
    assert kv_attend.supported(ok, jnp.bfloat16)
    assert kv_attend.supported(ok, jnp.float32)
    assert kv_attend.block(ok, jnp.bfloat16) == kv_attend.BLOCK_T
    assert kv_attend.block((4, 128, 4, 8), jnp.float32) == 128
    assert kv_attend.block((4, 640, 4, 8), jnp.float32) == 128
    assert not kv_attend.supported(ok, jnp.int8)              # quantized
    assert not kv_attend.supported((16, 1000, 20, 64), jnp.bfloat16)
    assert not kv_attend.supported((16, 1024, 8, 128), jnp.bfloat16)
    assert not kv_attend.supported((64, 32, 20, 64), jnp.bfloat16)  # pages
    # a block of K and of V must fit the kernel's VMEM
    assert not kv_attend.supported((2, 1024, 256, 96), jnp.float32)
    # Off the TPU the XLA attend stays, whatever the shape.
    assert not kv_attend.use_kv_attend(ok, jnp.bfloat16)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 64, size=n).astype(
        np.int32)


def _serve_tiny(monkeypatch, kernel: bool):
    """Five requests over two slots of the tiny GPT (max_len 128): each
    slot is freed and filled again, and a step often has one live row and
    one free. Returns ({rid: tokens}, the run's summary, kernel calls)."""
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.serve import engine as engine_mod
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

    model = gpt_lm(None, size="tiny", max_len=128, dropout_rate=0.0,
                   compute_dtype=jnp.float32)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    calls = []
    real = kv_attend.decode_attend

    def counted(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(kv_attend, "decode_attend", counted)
    if kernel:
        monkeypatch.setattr(
            kv_attend, "use_kv_attend",
            lambda shape, dtype, mesh=None: kv_attend.supported(shape,
                                                                dtype))
    # The decode program is cached by model: drop any traced in the other
    # form before, and the one traced here after.
    engine_mod._compiled_step.cache_clear()
    try:
        eng = SlotDecodeEngine(model, params, 2, buckets=(16,))
        sched = Scheduler(eng, decode_priority=2)
        done = sched.run(
            [Request(rid=i, prompt=_prompt(n, seed=i), max_new_tokens=new)
             for i, (n, new) in enumerate(
                 [(5, 9), (12, 3), (7, 6), (3, 12), (16, 4)])])
    finally:
        engine_mod._compiled_step.cache_clear()
    return ({c.rid: np.asarray(c.tokens) for c in done}, sched.summary,
            len(calls))


def test_engine_serves_the_same_tokens_with_the_kernel_on_and_off(
        monkeypatch):
    off, _, off_calls = _serve_tiny(monkeypatch, kernel=False)
    on, summary, on_calls = _serve_tiny(monkeypatch, kernel=True)
    assert off_calls == 0 and on_calls > 0, "the step did not take the kernel"
    assert sorted(on) == sorted(off) == [0, 1, 2, 3, 4]
    for rid in off:
        np.testing.assert_array_equal(on[rid], off[rid])
    # slots were used again: five requests over two slots
    assert summary["requests"] == 5


def test_engine_counts_what_its_launches_attends_cover(monkeypatch):
    """The host's two counters: over the rows a launch hands the program
    at a position past 0, blocks covered and positions seen, a layer.
    With one 128-position block a row (max_len 128) every live row-step
    covers exactly 128 positions a layer and sees its position + 1."""
    _, s, _ = _serve_tiny(monkeypatch, kernel=False)
    visited, seen = (s["kv_attend_positions_visited"],
                     s["kv_attend_positions_seen"])
    assert visited % (2 * 128) == 0                 # two layers
    row_steps = visited // (2 * 128)
    # every decoded token was a launched live row-step (a request's first
    # token is its prefill's); launches dropped at a drain or for a row
    # that had ended are counted too
    assert row_steps >= s["decoded_tokens"] - s["requests"] > 0
    # a row is between 3 and 28 deep here: it sees 4 to 29 of its 128
    assert 4 * 2 * row_steps <= seen <= 29 * 2 * row_steps


@pytest.mark.parametrize("how", ["paged", "window", "int8"])
def test_engines_of_another_cache_kind_count_nothing(how):
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

    over = {"paged": {}, "window": {"attn_window": 32},
            "int8": {"kv_cache_quant": "int8"}}
    model = gpt_lm(None, size="tiny", max_len=128, dropout_rate=0.0,
                   compute_dtype=jnp.float32, **over[how])
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    if how == "paged":
        from tensorflow_distributed_tpu.serve.paging.engine import (
            PagedSlotEngine)
        eng = PagedSlotEngine(model, params, 2, buckets=(16,),
                              page_size=16)
    else:
        eng = SlotDecodeEngine(model, params, 2, buckets=(16,))
    assert eng._kv_attends is None
    assert "kv_attend_positions_visited" not in eng.model_stats()


def test_a_leaf_the_kernel_does_not_take_counts_every_slots_whole_row():
    """``max_len`` 96 is no whole lane tile: the XLA attend reads all of
    the two slots' 96 positions at every launch, whatever is live."""
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

    model = gpt_lm(None, size="tiny", max_len=96, dropout_rate=0.0,
                   compute_dtype=jnp.float32)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = SlotDecodeEngine(model, params, 2, buckets=(16,))
    assert eng._kv_attends == (2, 96, 0)
    eng.prefill(_prompt(5), 1)
    eng.step()                     # launches this step and the one ahead
    stats = eng.model_stats()
    assert stats["kv_attend_positions_visited"] == 2 * (2 * 2 * 96)
    assert stats["kv_attend_positions_seen"] == 2 * (6 + 7)
