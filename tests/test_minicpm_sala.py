"""The ``minicpm_sala`` family (models/minicpm_sala.py, ops/hybrid_attention
.py) against its plain reference (perfbench/models/minicpm_sala.py), at the
benchmark configuration's REHEARSAL sizes on the CPU, seeded weights.

What is held: prefill then decode through the cache gives the reference's
full forward pass (logits; float32 compute at a tolerance that bfloat16
fails, bfloat16 at one that fp8 fails; contexts past ``dense_len`` and
under it); the chunked scan is the token recurrence and returns the state
AT ``true_len``; each Pallas kernel (interpret mode) is its XLA form; a
prompt prefilled in two buckets leaves one state and one next logits; a
step the engine drops and computes again, and a row that changes hands
under a step in flight, leave logits and state as an undisturbed run does;
the pooled-key leaf is the windows' means as decoding crosses stride
boundaries; the selection keeps its forced blocks, exactly ``topk``, a
group at a time; the 8-layer configuration is published layers 9-16 under
the residual scale of 32; ``config.py`` refuses what is not implemented,
by name; ``state_rows_stepped`` is the count made by hand.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_distributed_tpu.models import minicpm_sala as M
from tensorflow_distributed_tpu.models.generate import (
    decode_token, prefill_cache)
from tensorflow_distributed_tpu.ops import hybrid_attention as ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "perfbench", "configs",
                      "minicpm-sala-serve.json")
MAX_LEN = 448
# float32 compute against the float32 reference: both sum the same
# products in float32 and differ by the order of the sums (measured 3e-8
# on logits of magnitude 0.1). bfloat16 operands read 2e-4 and more.
TOL_F32 = 2e-6
# bfloat16 operands, float32 accumulation, against the float32 reference:
# measured 3e-4 to 9e-4 over the cases below; the reference with fp8
# operands reads 4e-3 and more.
TOL_BF16 = 2.5e-3


def _reference():
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    try:
        from harness.loader import load_model
        return load_model("minicpm_sala")
    finally:
        sys.path.remove(os.path.join(ROOT, "perfbench"))


@pytest.fixture(scope="module")
def ref():
    return _reference()


@pytest.fixture(scope="module")
def src():
    return dict(M.load_source(CONFIG + "#rehearsal.sizes"),
                max_position_embeddings=MAX_LEN)


@pytest.fixture(scope="module")
def weights(ref, src):
    sizes = ref.sizes(src)
    return sizes, jax.jit(lambda k: ref.make_params(k, sizes))(
        jax.random.key(35))


def _model(src, dtype=jnp.float32):
    return M.MiniCpmSalaLM(M.config_from_source(src, compute_dtype=dtype))


def _tokens(n, seed=0, rows=1):
    return np.random.default_rng(seed).integers(
        0, 96, size=(rows, n)).astype(np.int32)


def _through_the_cache(model, params, toks, prompt, bucket):
    """Logits of ``toks`` [B, n] from a prefill of the first ``prompt``
    tokens padded to ``bucket`` and one decode step a further token:
    [B, n - prompt + 1, V] for positions prompt - 1 .. n - 1."""
    B, n = toks.shape
    padded = np.zeros((B, bucket), np.int32)
    padded[:, :prompt] = toks[:, :prompt]
    logits, cache = prefill_cache(
        model, params, jnp.asarray(padded),
        logits_at=jnp.full((B,), prompt - 1), true_len=jnp.asarray(prompt))
    out = [np.asarray(logits[:, 0])]
    step = jax.jit(lambda c, t, p: decode_token(model, params, c, t, p))
    for t in range(prompt, n):
        last, cache = step(cache, jnp.asarray(toks[:, t]),
                           jnp.full((B,), t))
        out.append(np.asarray(last))
    return np.stack(out, axis=1), cache


# -- against the reference ---------------------------------------------------

def test_the_tree_is_the_references(ref, src, weights):
    sizes, params = weights
    model = _model(src)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]

    def flat(tree):
        return {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in
                jax.tree_util.tree_leaves_with_path(tree)}
    assert flat(shapes) == flat(params)
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) \
        == ref.param_count(sizes)


@pytest.mark.parametrize("prompt,new,bucket", [(300, 24, 320),
                                               (100, 24, 128)],
                         ids=["past_dense_len", "under_dense_len"])
def test_prefill_then_decode_is_the_references_forward_pass(
        ref, src, weights, prompt, new, bucket):
    """Rehearsal ``dense_len`` is 256: 300 + 24 positions select at every
    decode step and at the prefill positions past 256, 100 + 24 never."""
    sizes, params = weights
    toks = _tokens(prompt + new, seed=prompt, rows=2)
    want = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes))[
        :, prompt - 1:]
    scale = np.abs(want).max()
    got, _ = _through_the_cache(_model(src), params, toks, prompt, bucket)
    assert np.abs(got - want).max() < TOL_F32 * max(scale, 1.0)
    low, _ = _through_the_cache(_model(src, jnp.bfloat16), params, toks,
                                prompt, bucket)
    err = np.abs(low - want).max()
    # bfloat16 for float32 fails the first tolerance and passes the second
    assert TOL_F32 < err < TOL_BF16, err
    # fp8 for bfloat16 fails the second
    fp8 = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes,
                                   "fp8"))[:, prompt - 1:]
    assert np.abs(fp8 - want).max() > TOL_BF16


def test_the_forward_pass_without_a_cache_agrees_too(ref, src, weights):
    sizes, params = weights
    toks = _tokens(290, seed=4)
    want = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes))
    got = np.asarray(_model(src).apply({"params": params},
                                       jnp.asarray(toks)))
    assert np.abs(got - want).max() < TOL_F32


# -- the linear layers' scan and state ---------------------------------------

def _recurrence(q, k, v, slopes, n):
    """Token by token, in numpy float64: (o [n, H, d], S after token n -
    1)."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    lam = np.exp(-np.asarray(slopes, np.float64))[:, None, None]
    S = np.zeros((q.shape[1], q.shape[2], q.shape[2]))
    out = []
    for t in range(n):
        S = lam * S + k[t][:, :, None] * v[t][:, None, :]
        out.append(np.einsum("hd,hde->he", q[t], S))
    return np.stack(out), S


@pytest.mark.parametrize("true_len", [37, 64, 96])
def test_the_chunked_scan_is_the_token_recurrence_at_true_len(true_len):
    rng = np.random.default_rng(true_len)
    L, H, d = 96, 4, 8
    q, k, v = (jnp.asarray(rng.standard_normal((1, L, H, d)), jnp.float32)
               for _ in range(3))
    slopes = ops.decay_slopes(H)
    o, S = ops._chunk_scan_xla(q, k, v, slopes, jnp.asarray([true_len]), 32)
    want_o, want_S = _recurrence(q[0], k[0], v[0], slopes, true_len)
    np.testing.assert_allclose(np.asarray(o[0, :true_len]), want_o,
                               rtol=1e-4, atol=1e-4)
    # the state AT true_len: the padding neither entered nor decayed it
    np.testing.assert_allclose(np.asarray(S[0]), want_S, rtol=1e-4,
                               atol=1e-4)


def _bf16(rng, *shape, scale=1.0):
    return jnp.asarray(scale * rng.standard_normal(shape),
                       jnp.float32).astype(jnp.bfloat16)


def test_chunk_scan_kernel_is_the_xla_form():
    rng = np.random.default_rng(1)
    B, L, H, d = 2, 512, 2, 128
    q, k, v = _bf16(rng, B, L, H, d), _bf16(rng, B, L, H, d), \
        _bf16(rng, B, L, H, d, scale=0.5)
    slopes, n = ops.decay_slopes(H), jnp.asarray([300, 512])
    assert ops.chunk_scan_supported(q, 256)
    o1, S1 = ops._chunk_scan_xla(q, k, v, slopes, n, 256)
    o2, S2 = ops.chunk_scan_kernel(q, k, v, slopes, n, 256, interpret=True)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), rtol=2e-3,
                               atol=2e-2)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S1), rtol=2e-3,
                               atol=2e-2)


@pytest.mark.parametrize("pos", [[0, 5, 0, 9, 3, 0, 0, 7], [0] * 8],
                         ids=["some_live", "none_live"])
def test_state_step_kernel_is_the_xla_form_and_skips_free_rows(pos):
    rng = np.random.default_rng(2)
    B, H, d = 8, 8, 128
    S = jnp.asarray(rng.standard_normal((B, H, d, d)), jnp.float32)
    q, k, v = (_bf16(rng, B, H, d) for _ in range(3))
    pos = jnp.asarray(pos)
    fold = jnp.asarray([0, 1, 0, 1, 0, 0, 0, 1]).astype(bool) & (pos > 0)
    slopes = ops.decay_slopes(H)
    assert ops.state_step_supported(S)
    S1, o1 = ops._state_step_xla(S, q, k, v, slopes, fold, pos)
    S2, o2 = ops.state_step_kernel(S, q, k, v, slopes, fold, pos,
                                   interpret=True)
    live = np.asarray(pos) > 0
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S1), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(o2)[live], np.asarray(o1)[live],
                               rtol=1e-5, atol=1e-3)
    # a free row's state is as it was, bit for bit; so is that of a live
    # row that does not fold
    still = ~np.asarray(fold)
    assert np.array_equal(np.asarray(S2)[still], np.asarray(S)[still])


SP = ops.SparseConfig(kernel_size=32, kernel_stride=16, init_blocks=1,
                      block_size=64, window_size=256, topk=8,
                      dense_len=512)


def _scored(rng, pos, T=2048, B=4, G=2, h=16, d=128):
    q, pooled = _bf16(rng, B, G, h, d), _bf16(rng, B, SP.pooled_len(T),
                                              G * d)
    pos = jnp.asarray(pos)
    return q, pooled, pos, SP.windows_seen(pos)


def test_block_scores_kernel_is_the_xla_form():
    rng = np.random.default_rng(3)
    q, pooled, pos, seen = _scored(rng, [0, 700, 1500, 2047])
    assert ops.block_scores_supported(q, pooled)
    x = ops.sparse_block_scores(q, pooled, seen, pos, 128 ** -0.5)
    y = ops.block_scores_kernel(q, pooled, seen, pos, 128 ** -0.5,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-5,
                               atol=1e-6)
    assert float(y[0].max()) == -1.0          # a free row: nothing seen
    # a group's probabilities over the windows seen sum to its 16 heads
    assert np.allclose(np.asarray(x[2]).clip(0).sum(-1), 16.0, atol=1e-3)


def test_block_attend_kernel_is_the_xla_form():
    rng = np.random.default_rng(4)
    q, pooled, pos, seen = _scored(rng, [0, 700, 1500, 2047])
    kv = _bf16(rng, 4, 2048, 2 * 2 * 128)
    grp = ops.sparse_block_scores(q, pooled, seen, pos, 128 ** -0.5)
    idx, valid = ops.decode_selection(grp, pos, SP, 2048 // 64)
    assert ops.block_attend_supported(q, kv, SP)
    x = ops.sparse_block_attend(q, kv, idx, valid, pos, SP, 128 ** -0.5)
    y = ops.sparse_block_attend(q, kv, idx, valid, pos, SP, 128 ** -0.5,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(y)[1:], np.asarray(x)[1:],
                               rtol=2e-2, atol=2e-3)
    assert not np.asarray(y)[0].any()         # a free row reads nothing


# -- the selection -----------------------------------------------------------

def test_forced_blocks_are_kept_exactly_topk_and_a_group_at_a_time():
    rng = np.random.default_rng(5)
    T, G = 2048, 2
    n_blocks = T // SP.block_size
    pos = jnp.asarray([700, 1500, 2047, 1023])
    grp = jnp.asarray(rng.random((4, G, SP.pooled_len(T))), jnp.float32)
    seen = np.asarray(SP.windows_seen(pos))
    grp = jnp.where(jnp.arange(grp.shape[-1])[None, None, :]
                    < seen[:, None, None], grp, -1.0)
    idx, valid = ops.decode_selection(grp, pos, SP, n_blocks)
    idx, valid = np.asarray(idx), np.asarray(valid)
    assert idx.shape == (4, G, SP.topk) and valid.all()
    for b, p in enumerate(np.asarray(pos)):
        mine = p // SP.block_size
        forced = {0} | set(range(mine - SP.local_blocks + 1, mine + 1))
        for g in range(G):
            kept = set(idx[b, g].tolist())
            assert len(kept) == SP.topk                  # exactly topk
            assert forced <= kept                        # forced kept
            assert max(kept) <= mine                     # none ahead
            # the rest are the best-scoring of the others
            score = np.asarray(ops.block_scores(grp, pos, SP, n_blocks))[
                b, g]
            free = sorted((m for m in range(mine + 1) if m not in forced),
                          key=lambda m: -score[m])
            assert kept - forced == set(free[:SP.topk - len(forced)])
        # different scores a group: the groups choose for themselves
        assert set(idx[b, 0].tolist()) != set(idx[b, 1].tolist())
    # a block's score is the largest of the 5 windows that overlap it
    score = np.asarray(ops.block_scores(grp, pos, SP, n_blocks))
    m = 3
    assert score[0, 1, m] == pytest.approx(
        float(np.asarray(grp)[0, 1, 4 * m - 1:4 * m + 4].max()))


def test_prefill_and_decode_select_the_same_blocks(src, weights):
    """The mask a prefill's query t keeps is the set a decode step at t
    picks, from the same pooled keys."""
    rng = np.random.default_rng(6)
    sp = M.config_from_source(src).sparse
    L, G, h, d = 320, 2, 4, 8
    q = jnp.asarray(rng.standard_normal((L, G, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((L, G * d)), jnp.float32)
    kc = ops.pool_keys(k, sp)
    keep = np.asarray(ops.prefill_selection(q, kc.reshape(-1, G, d), sp,
                                            d ** -0.5))    # [G, L, nb]
    n_blocks = L // sp.block_size
    pooled = jnp.zeros((1, sp.pooled_len(L), G * d)).at[0, :kc.shape[0]
                                                        ].set(kc)
    for t in (255, 256, 300, 319):
        pos = jnp.asarray([t])
        grp = ops.sparse_block_scores(q[t][None], pooled,
                                      sp.windows_seen(pos), pos, d ** -0.5)
        idx, valid = ops.decode_selection(grp, pos, sp, n_blocks)
        for g in range(G):
            picked = set(np.asarray(idx)[0, g][np.asarray(valid)[0, g]]
                         .tolist())
            mask = set(np.nonzero(keep[g, t])[0].tolist())
            if t + 1 <= sp.dense_len:
                assert mask == set(range(t // sp.block_size + 1))
            else:
                assert mask == picked and len(mask) == sp.topk


def test_the_pooled_keys_are_the_windows_means_across_strides(src,
                                                              weights):
    """Prefill 41 tokens (stride 4, window 8), decode 30 more: every
    window complete so far is the mean of its K rows in the cache, one
    more every fourth step."""
    _, params = weights
    model = _model(src)
    sp = model.cfg.sparse
    toks = _tokens(71, seed=7)
    _, cache = _through_the_cache(model, params, toks[:, :41], 41, 64)
    step = jax.jit(lambda c, t, p: decode_token(model, params, c, t, p))
    width = model.cfg.num_key_value_heads * model.cfg.head_dim
    for t in range(41, 71):
        _, cache = step(cache, jnp.asarray(toks[:, t]), jnp.full((1,), t))
        for lay in (c for c in cache.values() if isinstance(c, dict)
                    and "pooled_keys" in c.get("mixer", {})):
            rows = np.asarray(lay["mixer"]["kv"])[0, :, :width]
            got = np.asarray(lay["mixer"]["pooled_keys"])[0]
            n_w = (t + 1 - sp.kernel_size) // sp.kernel_stride + 1
            want = np.stack([rows[sp.kernel_stride * j:sp.kernel_stride * j
                                  + sp.kernel_size].mean(0)
                             for j in range(n_w)])
            np.testing.assert_allclose(got[:n_w], want, rtol=1e-6,
                                       atol=1e-6)
    assert n_w == 16


# -- buckets, and steps computed again ---------------------------------------

def test_one_prompt_in_two_buckets_leaves_one_state(src, weights):
    _, params = weights
    model = _model(src)
    toks = _tokens(301, seed=8)
    a, ca = _through_the_cache(model, params, toks, 300, 320)
    b, cb = _through_the_cache(model, params, toks, 300, 384)
    np.testing.assert_allclose(b, a, rtol=0, atol=TOL_F32)
    for i, kind in enumerate(model.cfg.mixers):
        if kind == "lightning-attn":
            np.testing.assert_allclose(
                np.asarray(cb[f"layer_{i}"]["mixer"]["state"]),
                np.asarray(ca[f"layer_{i}"]["mixer"]["state"]),
                rtol=1e-5, atol=1e-6)
    assert int(ca["state_pos"][0]) == int(cb["state_pos"][0]) == 301
    # without the true length the state is the one at the bucket's end
    padded = np.zeros((1, 320), np.int32)
    padded[:, :300] = toks[:, :300]
    _, wrong = prefill_cache(model, params, jnp.asarray(padded))
    assert int(wrong["state_pos"][0]) == 320


@pytest.fixture(scope="module")
def served(src, weights):
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    _, params = weights
    model = _model(src)

    def engine(slots=2):
        return SlotDecodeEngine(model, params, slots, buckets=(320, 384))
    return model, params, engine


def _state_of(eng, slot):
    """The slot's recurrent states and their stamp, after draining."""
    eng.drain()
    cache = jax.device_get(eng.cache)
    return ([np.asarray(c["mixer"]["state"])[slot]
             for c in cache.values() if isinstance(c, dict)
             and "state" in c.get("mixer", {})],
            int(np.asarray(cache["state_pos"])[slot]))


def _logits_now(model, params, eng, slot):
    """What the next plain step would read for ``slot`` (not donated: the
    engine's cache stays its own)."""
    last, _ = decode_token(model, params, eng.cache, jnp.asarray(eng.tok),
                           jnp.asarray(eng.pos))
    return np.asarray(last)[slot]


def test_a_step_dropped_and_computed_again_folds_its_token_once(served):
    model, params, engine = served
    prompt = _tokens(300, seed=9)[0]
    calm, jumpy = engine(), engine()
    for eng in (calm, jumpy):
        eng.prefill(prompt, 0)
    got = {id(calm): [], id(jumpy): []}
    for i in range(9):
        for eng in (calm, jumpy):
            nxt = eng.step()
            assert eng.step_valid[0]
            got[id(eng)].append(int(nxt[0]))
        if i % 2 == 0:
            # the step in flight has folded its token into the states:
            # dropped here, the next launch computes it again
            assert jumpy._ahead is not None
            jumpy.drain()
    assert got[id(jumpy)] == got[id(calm)]
    assert jumpy.ahead_rows_dropped >= 5
    a, b = _state_of(calm, 0), _state_of(jumpy, 0)
    assert a[1] == b[1] == 300 + 9 + 1    # one step ahead, folded once
    for x, y in zip(a[0], b[0]):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(_logits_now(model, params, calm, 0),
                                  _logits_now(model, params, jumpy, 0))


def test_a_row_that_changes_hands_under_a_step_in_flight(served):
    """Slot 1's owner leaves while a step computed for it is in flight
    (it folded that owner's token into slot 1's states); the next owner's
    insert replaces states and stamp together, and its stream and state
    are those of an engine where nothing was in flight."""
    model, params, engine = served
    first, second, other = (_tokens(n, seed=s)[0] for n, s in (
        (290, 10), (310, 11), (305, 12)))
    busy, calm = engine(), engine()
    busy.prefill(other, 0)
    busy.prefill(first, 1)
    for _ in range(3):
        busy.step()
    assert busy._ahead is not None and busy._ahead.rows[1]
    busy.free(1)                          # the step in flight ran for it
    assert busy.ahead_rows_dropped == 1
    busy.prefill(second, 1)
    calm.prefill(second, 1)
    got, want = [], []
    for _ in range(6):
        nxt = busy.step()
        if busy.step_valid[1]:
            got.append(int(nxt[1]))
    for _ in range(len(got)):
        nxt = calm.step()
        assert calm.step_valid[1]
        want.append(int(nxt[1]))
    assert got == want and len(got) >= 5
    a, b = _state_of(calm, 1), _state_of(busy, 1)
    assert a[1] == b[1]
    for x, y in zip(a[0], b[0]):
        np.testing.assert_allclose(y, x, rtol=1e-6, atol=1e-6)


def test_the_scheduler_serves_one_shot_greedy_tokens(served):
    """Admissions under a step in flight and every freed slot re-admitted
    at once: each request's tokens are ``generate()``'s."""
    from tensorflow_distributed_tpu.models.generate import generate
    from tensorflow_distributed_tpu.serve.scheduler import (
        Request, Scheduler)
    model, params, engine = served
    reqs = [Request(rid=i, prompt=_tokens(n, seed=20 + i)[0],
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(300, 6), (290, 3), (310, 5),
                                        (295, 4)])]
    eng = engine()
    done = Scheduler(eng).run(reqs)
    assert eng._ahead is None and eng.steps_ahead > 0
    for r in reqs:
        want = np.asarray(generate(model, params,
                                   jnp.asarray(r.prompt[None, :]),
                                   r.max_new_tokens))[0]
        got = next(c for c in done if c.rid == r.rid)
        assert [int(t) for t in got.tokens] == [int(t) for t in want], r.rid


def test_state_rows_stepped_is_the_count_made_by_hand(served):
    model, params, engine = served
    eng = engine(slots=3)
    eng.prefill(_tokens(300, seed=30)[0], 0)
    for _ in range(4):                    # one live row
        eng.step()
    eng.prefill(_tokens(290, seed=31)[0], 2)
    for _ in range(3):                    # the first of these was launched
        eng.step()                        # before the second admission
    stats = eng.model_stats()
    # steps RETURNED: 4 + 1 with slot 0 alone, 2 with slots 0 and 2
    live = 5 * 1 + 2 * 2
    assert stats["decode_live_rows"] == live
    assert stats["state_rows_stepped"] == live * model.cfg.n_lightning
    assert stats["sparse_rows_dense"] == 0          # every row past 256
    assert stats["sparse_blocks_kept"] == live * 2 * model.cfg.sparse.topk
    assert stats["state_bytes_per_slot"] == 2 * 4 * 8 * 8 * 4
    assert stats["select_keys_kept"] < stats["select_keys_available"]
    assert set(stats["cache_bytes_per_slot_by_kind"]) == {
        "kv", "pooled_keys", "state", "state_pos"}
    assert stats["cache_bytes_per_slot_by_kind"]["state"] \
        == stats["state_bytes_per_slot"]


# -- the configuration and what config.py refuses ----------------------------

def test_the_stage_is_published_layers_9_to_16_under_the_scale_of_32():
    import json
    with open(CONFIG) as f:
        src = json.load(f)
    cfg = M.config_from_source(src)
    assert len(src["mixer_types"]) == 32
    assert cfg.mixers == tuple(src["mixer_types"][9:17]) == (
        "minicpm4",) + ("lightning-attn",) * 6 + ("minicpm4",)
    assert cfg.published_layers == 32 and len(cfg.mixers) == 8
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert cfg.state_bytes_per_slot == 6 * 32 * 128 * 128 * 4
    assert cfg.sparse == ops.SparseConfig(32, 16, 1, 64, 2048, 64, 8192)
    with pytest.raises(ValueError, match="mixer_types"):
        M.config_from_source(dict(src, first_layer_held=30))
    with pytest.raises(ValueError, match="attn_use_rope"):
        M.config_from_source(dict(src, attn_use_rope=True))


def _cfg(**kw):
    from tensorflow_distributed_tpu.config import TrainConfig
    cfg = TrainConfig(model="minicpm_sala", mode="serve",
                      model_config=CONFIG)
    for k, v in kw.items():
        obj, *rest = k.split("__")
        if rest:
            setattr(getattr(cfg, obj), rest[0], v)
        else:
            setattr(cfg, obj, v)
    return cfg


@pytest.mark.parametrize("kw,message", [
    ({"mode": "train"}, "the minicpm_sala family has no training path"),
    ({"model_config": ""}, "takes its sizes from --model-config"),
    ({"model_size": "tiny"}, "no --model-size preset"),
    ({"serve__paged": True}, "no paging over a state"),
    ({"serve__spec_tokens": 2}, "cannot roll a state back"),
    ({"serve__mesh_model": 2}, "--serve.mesh-model"),
    ({"kv_cache_quant": "int8"}, "int8 KV cache"),
], ids=["train", "no_config", "preset", "paged", "spec", "mesh_model",
        "int8"])
def test_config_refuses_by_name(kw, message):
    with pytest.raises(ValueError, match=message):
        _cfg(**kw).validate()


def test_config_takes_the_family_and_no_other_takes_model_config():
    from tensorflow_distributed_tpu.config import (
        SOURCE_CONFIG_MODELS, TrainConfig)
    _cfg().validate()
    assert SOURCE_CONFIG_MODELS[:3] == ("glm_moe_dsa", "axk1",
                                        "minicpm_sala")
    with pytest.raises(ValueError, match="takes presets and flags"):
        TrainConfig(model="gpt_lm", mode="serve",
                    model_config=CONFIG).validate()
