"""The ``granitemoehybrid`` family (models/granitemoehybrid.py, ops/
state_space.py) against its plain reference (perfbench/models/
granitemoehybrid.py), at the benchmark configuration's REHEARSAL sizes on
the CPU, seeded weights.

What is held: prefill then decode through the cache gives the reference's
full forward pass (logits; float32 compute at a tolerance that bfloat16
fails, bfloat16 at one that fp8 fails); the chunked scan is the token
recurrence and leaves the state AT ``true_len``; each Pallas kernel
(interpret mode) is its XLA form, with padding past ``true_len``, a free
row and a row that does not fold; the convolution's ring is the last four
inputs by position, and writing a token twice changes nothing; a prompt
prefilled in two buckets leaves one state and one ring; a step the engine
drops and computes again, and a row that changes hands under a step in
flight, leave logits, ``state`` and ``conv`` as an undisturbed run does; the
two chips' shares of a layer (experts 0-3 and 4-7 here, the shared expert
counted once) add up to the uncut reference's layer; the router is a
sort-based top-k with ties to the lower index; the 10-layer configuration
is published layers 0-9 with experts 0-35; ``config.py`` refuses what is not
implemented, by name; the counters are the counts made by hand; the family
runs through ``cli.main``.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_distributed_tpu.models import granitemoehybrid as M
from tensorflow_distributed_tpu.models.generate import (
    decode_token, prefill_cache)
from tensorflow_distributed_tpu.ops import hybrid_attention as H
from tensorflow_distributed_tpu.ops import state_space as ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "perfbench", "configs",
                      "granite-4.0-h-small-serve.json")
MAX_LEN = 448
# float32 compute against the float32 reference: both sum the same
# products in float32 and differ by the order of the sums (measured 5e-9
# on logits of magnitude 0.014). bfloat16 operands read 2.4e-4.
TOL_F32 = 2e-7
# bfloat16 operands, float32 accumulation, against the float32 reference:
# measured 2.1e-4 to 2.5e-4 over the cases below; the reference with fp8
# operands reads 1.7e-3 to 2.9e-3.
TOL_BF16 = 7e-4


def _reference():
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    try:
        from harness.loader import load_model
        return load_model("granitemoehybrid")
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
        jax.random.key(41))


def _model(src, dtype=jnp.float32):
    return M.GraniteMoeHybridLM(M.config_from_source(src,
                                                     compute_dtype=dtype))


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
    # jitted, as the engine's prefill program is
    logits, cache = jax.jit(lambda p, n: prefill_cache(
        model, params, p, logits_at=jnp.broadcast_to(n - 1, (B,)),
        true_len=n))(jnp.asarray(padded), jnp.asarray(prompt))
    out = [np.asarray(logits[:, 0])]
    step = jax.jit(lambda c, t, p: decode_token(model, params, c, t, p))
    for t in range(prompt, n):
        last, cache = step(cache, jnp.asarray(toks[:, t]),
                           jnp.full((B,), t))
        out.append(np.asarray(last))
    return np.stack(out, axis=1), cache


def _mamba_leaves(cache, kind):
    return [np.asarray(c["mixer"][kind]) for _, c in sorted(cache.items())
            if isinstance(c, dict) and kind in c.get("mixer", {})]


# -- against the reference ---------------------------------------------------

def test_the_tree_is_the_references(ref, src, weights):
    sizes, params = weights
    model = _model(src)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    mine = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in
            jax.tree_util.tree_leaves_with_path(shapes)}
    theirs = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in
              jax.tree_util.tree_leaves_with_path(params)}
    assert mine == theirs
    assert all(d == jnp.bfloat16 for _, d in mine.values())
    assert ref.param_count(sizes) == sum(
        int(np.prod(s)) for s, _ in mine.values())
    assert model.cfg.layers == sizes["layers"] == (
        "mamba", "attention", "mamba", "mamba")
    # the decays differ a head: A_log is not N(0, 0.02)
    a_log = np.asarray(params["layer_0"]["mixer"]["A_log"]["value"],
                       np.float32)
    assert a_log.min() >= 0 and a_log.max() <= np.log(16) + 0.01 \
        and np.ptp(a_log) > 0.5


@pytest.mark.parametrize("prompt,new,bucket,dtype,tol", [
    (300, 24, 320, jnp.float32, TOL_F32),
    (257, 8, 384, jnp.float32, TOL_F32),
    (300, 24, 320, jnp.bfloat16, TOL_BF16)],
    ids=["f32", "f32_one_past_a_chunk", "bf16"])
def test_prefill_then_decode_is_the_references_forward_pass(
        ref, src, weights, prompt, new, bucket, dtype, tol):
    sizes, params = weights
    toks = _tokens(prompt + new, seed=prompt, rows=2)
    got, _ = _through_the_cache(_model(src, dtype), params, toks, prompt,
                                bucket)
    want = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes))
    err = np.abs(got - want[:, prompt - 1:]).max()
    assert err < tol, err
    if dtype == jnp.bfloat16:
        # what the tolerance tells apart: fp8 operands fail it
        low = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes,
                                       "fp8"))
        assert np.abs(low - want).max() > 2 * tol


def test_the_forward_pass_without_a_cache_agrees_too(ref, src, weights):
    sizes, params = weights
    toks = _tokens(320, seed=3)
    got = np.asarray(_model(src).apply({"params": params},
                                       jnp.asarray(toks)))
    want = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes))
    assert np.abs(got - want).max() < TOL_F32


# -- the scan, the step and the convolution ----------------------------------

def _recurrence(x, dt, A, Bm, Cm, n):
    """Token by token, in numpy float64, head h reading group h // (H /
    G) of Bm, Cm [L, G, N]: (y [n, H, P], S^T [N, H P] after token n -
    1)."""
    x, dt, A, Bm, Cm = (np.asarray(a, np.float64)
                        for a in (x, dt, A, Bm, Cm))
    H, P = x.shape[1:]
    G, N = Bm.shape[1:]
    S = np.zeros((H, P, N))
    out = []
    for t in range(n):
        bh, ch = (np.repeat(m[t], H // G, axis=0) for m in (Bm, Cm))
        S = np.exp(dt[t] * A)[:, None, None] * S \
            + (dt[t][:, None] * x[t])[:, :, None] * bh[:, None, :]
        out.append(np.einsum("hpn,hn->hp", S, ch))
    return np.stack(out), S.transpose(2, 0, 1).reshape(N, H * P)


def _scan_inputs(rng, B, L, H, P, N, dtype=jnp.float32, G=1):
    x = jnp.asarray(rng.standard_normal((B, L, H, P)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, L, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, L, G, N)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.3, (B, L, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), jnp.float32)
    return x.astype(dtype), dt, A, Bm.astype(dtype), Cm.astype(dtype)


@pytest.mark.parametrize("true_len", [37, 64, 96])
def test_the_chunked_scan_is_the_token_recurrence_at_true_len(true_len):
    rng = np.random.default_rng(true_len)
    x, dt, A, Bm, Cm = _scan_inputs(rng, 1, 96, 4, 8, 16)
    masked = jnp.where(jnp.arange(96)[None, :, None] < true_len, dt, 0.0)
    y, S = ops._chunk_scan_xla(x, masked, A, Bm, Cm, 32)
    want_y, want_S = _recurrence(x[0], dt[0], A, Bm[0], Cm[0], true_len)
    np.testing.assert_allclose(np.asarray(y[0, :true_len]), want_y,
                               rtol=1e-4, atol=1e-4)
    # the state AT true_len: the padding neither entered nor decayed it
    np.testing.assert_allclose(np.asarray(S[0]), want_S, rtol=1e-4,
                               atol=1e-4)


def test_chunk_scan_kernel_is_the_xla_form_with_padding():
    rng = np.random.default_rng(1)
    B, L, H, P, N = 2, 512, 8, 64, 128
    x, dt, A, Bm, Cm = _scan_inputs(rng, B, L, H, P, N, jnp.bfloat16)
    n = jnp.asarray([300, 512])
    dt = jnp.where(jnp.arange(L)[None, :, None] < n[:, None, None], dt, 0.0)
    assert ops.chunk_scan_supported(x, Bm, 256)
    y1, S1 = ops._chunk_scan_xla(x, dt, A, Bm, Cm, 256)
    y2, S2 = ops.chunk_scan_kernel(x, dt, A, Bm, Cm, 256, interpret=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), rtol=2e-3,
                               atol=2e-2)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S1), rtol=2e-3,
                               atol=2e-2)
    # and both are the recurrence over the first 300 tokens of row 0, as
    # closely as bfloat16 operands allow
    f = lambda a: np.asarray(a, np.float32)                # noqa: E731
    want_y, want_S = _recurrence(f(x[0]), f(dt[0]), f(A), f(Bm[0]),
                                 f(Cm[0]), 300)
    np.testing.assert_allclose(np.asarray(S2[0]), want_S, rtol=3e-2,
                               atol=3e-2)
    np.testing.assert_allclose(np.asarray(y2[0, :300]), want_y, rtol=3e-2,
                               atol=0.15)


@pytest.mark.parametrize("pos", [[0, 5, 0, 9, 3, 0, 0, 7], [0] * 8],
                         ids=["some_live", "none_live"])
def test_state_step_kernel_is_the_xla_form_and_skips_free_rows(pos):
    rng = np.random.default_rng(2)
    B, H, P, N = 8, 64, 64, 128               # two blocks of 2,048 lanes
    S = jnp.asarray(rng.standard_normal((B, N, H * P)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, H, P)), jnp.bfloat16)
    Bm, Cm = (jnp.asarray(rng.standard_normal((B, 1, N)), jnp.bfloat16)
              for _ in range(2))
    dt = jnp.asarray(rng.uniform(0.001, 0.3, (B, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), jnp.float32)
    pos = jnp.asarray(pos)
    # row 4 is live and does not fold: its state already holds the token
    fold = jnp.asarray([0, 1, 0, 1, 0, 0, 0, 1]).astype(bool) & (pos > 0)
    assert ops.state_step_supported(S)
    on_cpu = jax.jit(lambda *a: ops.ssd_state_step(*a))
    in_kernel = jax.jit(lambda *a: ops.ssd_state_step(*a, interpret=True))
    S1, y1 = on_cpu(S, x, dt, A, Bm, Cm, fold, pos)
    S2, y2 = in_kernel(S, x, dt, A, Bm, Cm, fold, pos)
    live = np.asarray(pos) > 0
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S1), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(y2)[live], np.asarray(y1)[live],
                               rtol=1e-5, atol=1e-4)
    if live.any():
        assert not np.asarray(y1)[~live].any()
        # against the recurrence's one step, row 1
        f = lambda a: np.asarray(a, np.float64)            # noqa: E731
        St = f(S[1]).reshape(N, H, P).transpose(1, 2, 0)
        St = np.exp(f(dt[1]) * f(A))[:, None, None] * St + (
            f(dt[1])[:, None] * f(x[1]))[:, :, None] * f(Bm[1, 0])[None, None]
        np.testing.assert_allclose(
            np.asarray(y2[1]), np.einsum("hpn,n->hp", St, f(Cm[1, 0])),
            rtol=1e-4, atol=1e-4)
    # a free row's state is as it was, bit for bit; so is that of a live
    # row that does not fold
    still = ~np.asarray(fold)
    assert np.array_equal(np.asarray(S2)[still], np.asarray(S)[still])
    assert np.array_equal(np.asarray(S1)[still], np.asarray(S)[still])


def test_the_convolutions_ring_is_the_last_four_inputs_by_position():
    rng = np.random.default_rng(5)
    B, L, C, K = 2, 24, 12, 4
    xbc = jnp.asarray(rng.standard_normal((B, L, C)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, C)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((C,)), jnp.float32)
    n = np.asarray([17, 2])
    act, ring = ops.ssd_conv(xbc, w, b, jnp.asarray(n))
    padded = np.concatenate([np.zeros((B, K - 1, C)), np.asarray(xbc)], 1)
    pre = np.asarray(b) + sum(np.asarray(w)[j] * padded[:, j:j + L]
                              for j in range(K))
    np.testing.assert_allclose(np.asarray(act), pre / (1 + np.exp(-pre)),
                               rtol=1e-5, atol=1e-5)
    for r in range(B):
        for q in range(n[r] - K, n[r]):
            want = np.asarray(xbc)[r, q] if q >= 0 else np.zeros(C)
            np.testing.assert_array_equal(np.asarray(ring)[r, q % K], want)
    # decode from the ring: each step is the convolution of the whole
    # sequence at that position, and a token written twice changes nothing
    for t in range(3):
        pos = jnp.asarray(n + t)
        new = jnp.stack([xbc[r, n[r] + t] for r in range(B)])
        ring, out = ops.ssd_conv_step(ring, new, w, b, pos)
        again, out2 = ops.ssd_conv_step(ring, new, w, b, pos)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(ring))
        np.testing.assert_array_equal(np.asarray(out2), np.asarray(out))
        for r in range(B):
            np.testing.assert_allclose(np.asarray(out)[r],
                                       np.asarray(act)[r, n[r] + t],
                                       rtol=1e-5, atol=1e-5)


# -- the expert layer ---------------------------------------------------------

def test_the_router_is_a_sort_based_top_k_with_ties_to_the_lower_index():
    rng = np.random.default_rng(7)
    N, D, E, k = 64, 16, 12, 5
    xs = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    w = np.asarray(rng.standard_normal((D, E)), np.float32)
    w[:, 7] = w[:, 2]                  # experts 2 and 7 tie at every token
    w[:, 9] = w[:, 4]
    ids, weights = M.route(xs, jnp.asarray(w), k)
    # the router's own float32 logits (a float64 product sums the tied
    # columns in another order and unties them)
    logits = np.asarray(jnp.einsum(
        "nd,de->ne", xs, jnp.asarray(w),
        precision=jax.lax.Precision.HIGHEST), np.float64)
    assert (logits[:, 7] == logits[:, 2]).all()
    order = np.argsort(-logits, axis=1, kind="stable")[:, :k]
    np.testing.assert_array_equal(np.asarray(ids), order)
    top = np.take_along_axis(logits, order, 1)
    want = np.exp(top - top.max(1, keepdims=True))
    np.testing.assert_allclose(np.asarray(weights),
                               want / want.sum(1, keepdims=True),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(1), 1.0, rtol=1e-6)
    # wherever a tied pair straddles nothing, the lower id comes first
    got = np.asarray(ids)
    for lo, hi in ((2, 7), (4, 9)):
        both = (got == lo).any(1) & (got == hi).any(1)
        assert both.any()
        assert (np.argmax(got[both] == lo, 1)
                < np.argmax(got[both] == hi, 1)).all()
        assert not ((got == hi).any(1) & ~(got == lo).any(1)).any()


def test_the_two_shares_add_up_to_the_uncut_layer(ref, src):
    """One layer's experts divided between the two chips that share it
    (0-3 and 4-7 of 8 here), each through the PROGRAM's layer with its
    own share, against the reference's layer with all 8: the two parts,
    the shared expert counted once, are the whole."""
    whole = dict(src, num_local_experts=8,
                 experts_held=list(range(8)))
    sizes = ref.sizes(whole)
    params = jax.jit(lambda k: ref.make_params(k, sizes))(
        jax.random.key(9))["layer_0"]["moe"]
    u = jnp.asarray(np.random.default_rng(9).standard_normal((2, 40, 32)),
                    jnp.float32)
    parts = []
    for held in ((0, 1, 2, 3), (4, 5, 6, 7)):
        cfg = M.config_from_source(
            dict(src, num_local_experts=4, experts_held=list(held)),
            compute_dtype=jnp.float32)
        mine = dict(params, **{
            name: {"kernel": params[name]["kernel"][jnp.asarray(held)]}
            for name in ("experts_gate", "experts_up", "experts_down")})
        parts.append(np.asarray(M.MoeLayer(cfg).apply({"params": mine}, u)))
        # a share alone is the reference given the same share
        want = np.asarray(ref.expert_layer(
            u.reshape(80, 32), params, sizes, "f32", held=held))
        np.testing.assert_allclose(parts[-1].reshape(80, 32), want,
                                   rtol=0, atol=2e-6)
    flat = u.reshape(80, 32)
    shared = np.asarray(ref._gated(
        flat, params["shared_gate"]["kernel"], params["shared_up"]["kernel"],
        params["shared_down"]["kernel"], "f32"))
    uncut = np.asarray(ref.expert_layer(flat, params, sizes, "f32"))
    got = (parts[0] + parts[1]).reshape(80, 32) - shared
    np.testing.assert_allclose(got, uncut, rtol=0, atol=3e-6)
    assert np.abs(uncut - shared).max() > 1e-3     # the routed part counts


# -- buckets, and steps computed again ---------------------------------------

def test_one_prompt_in_two_buckets_leaves_one_state_and_one_ring(src,
                                                                 weights):
    _, params = weights
    model = _model(src)
    toks = _tokens(301, seed=8)
    a, ca = _through_the_cache(model, params, toks, 300, 320)
    b, cb = _through_the_cache(model, params, toks, 300, 384)
    np.testing.assert_allclose(b, a, rtol=0, atol=TOL_F32)
    for x, y in zip(_mamba_leaves(ca, "state"), _mamba_leaves(cb, "state")):
        np.testing.assert_allclose(y, x, rtol=1e-5, atol=1e-6)
    # (the ring holds the input projection's rows: another bucket sums them
    # in another order)
    for x, y in zip(_mamba_leaves(ca, "conv"), _mamba_leaves(cb, "conv")):
        np.testing.assert_allclose(y, x, rtol=1e-5, atol=1e-6)
    assert len(_mamba_leaves(ca, "state")) == 3
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
    """The slot's states, rings and the states' stamp, after draining."""
    eng.drain()
    cache = jax.device_get(eng.cache)
    return ([x[slot] for x in _mamba_leaves(cache, "state")],
            [x[slot] for x in _mamba_leaves(cache, "conv")],
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
            # the step in flight has folded its token into the states and
            # written its row of the ring: dropped here, the next launch
            # computes it again
            assert jumpy._ahead is not None
            jumpy.drain()
    assert got[id(jumpy)] == got[id(calm)]
    assert jumpy.ahead_rows_dropped >= 5
    a, b = _state_of(calm, 0), _state_of(jumpy, 0)
    assert a[2] == b[2] == 300 + 9 + 1    # one step ahead, folded once
    for x, y in zip(a[0] + a[1], b[0] + b[1]):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(_logits_now(model, params, calm, 0),
                                  _logits_now(model, params, jumpy, 0))
    # the engine's counters tell the two apart: the rows that only read
    calm_stats, jumpy_stats = calm.model_stats(), jumpy.model_stats()
    assert calm_stats["state_rows_reread"] == 0
    assert jumpy_stats["state_rows_reread"] > 0
    assert jumpy_stats["state_rows_stepped"] == \
        jumpy_stats["state_rows_folded"] + jumpy_stats["state_rows_reread"]


def test_a_row_that_changes_hands_under_a_step_in_flight(served):
    """Slot 1's owner leaves while a step computed for it is in flight
    (it folded that owner's token into slot 1's states); the next owner's
    insert replaces states, rings and stamp together, and its stream and
    state are those of an engine where nothing was in flight."""
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
    assert a[2] == b[2]
    for x, y in zip(a[0], b[0]):
        np.testing.assert_allclose(y, x, rtol=1e-6, atol=1e-6)
    for x, y in zip(a[1], b[1]):
        np.testing.assert_array_equal(y, x)


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


def test_the_counters_are_the_counts_made_by_hand(served):
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
    assert stats["state_rows_stepped"] == live * model.cfg.n_mamba == \
        stats["state_rows_folded"]
    assert stats["state_rows_reread"] == 0
    assert stats["state_bytes_per_slot"] == 3 * 16 * 64 * 4
    assert stats["conv_bytes_per_slot"] == 3 * 4 * (64 + 32) * 4   # f32
    by_kind = stats["cache_bytes_per_slot_by_kind"]
    assert set(by_kind) == {"kv", "state", "conv", "state_pos"}
    assert by_kind["state"] == stats["state_bytes_per_slot"]
    assert by_kind["conv"] == stats["conv_bytes_per_slot"]
    assert by_kind["kv"] == MAX_LEN * 2 * 2 * 8 * 4       # float32 here
    # every live row routes 3 pairs a layer; this chip holds 4 of 8 experts
    assert stats["moe_layers"] == 4
    assert 0 < stats["moe_held_pairs"] < live * 3 * 4
    assert sum(stats["moe_held_pairs_by_expert"]) == stats["moe_held_pairs"]
    assert 0 < stats["moe_experts_hit"] <= 7 * 4 * 4
    # slot 0 at depths 300..304 then 305..306 beside slot 2 at 290..291
    assert stats["attend_keys"] == sum(range(301, 308)) + 291 + 292
    # ... over the attention layers, and what their attends' blocks cover
    # (ops.hybrid_attention.gqa_attend_visits: a live row's blocks to its
    # depth, nothing of the free slot), as exaone_moe counts them
    layers = sum(kind == "attention" for kind in model.cfg.layers)
    assert stats["select_keys_kept"] == layers * stats["attend_keys"]
    bt = H.gqa_attend_block(MAX_LEN)
    assert stats["attend_positions_visited"] == layers * sum(
        (p // bt + 1) * bt
        for p in list(range(300, 307)) + [290, 291])


# -- the attention layer's decode attend ---------------------------------------

@pytest.mark.parametrize("groups, queries", [
    (8, 4), (2, 16), (8, 8), (2, 3)],
    ids=["granite_4_a_group", "nemotron_16_a_group", "kexaone_8_a_group",
         "odd_3_a_group"])
def test_the_depth_bounded_attend_is_the_slot_blind_one_at_any_group_size(
        monkeypatch, groups, queries):
    """``gqa_decode_attend`` (the kernel, in the interpreter) against
    ``dense_decode_attend`` over the whole leaf, at the group sizes of the
    three models whose attention layer runs it: a group of fewer than 8
    queries is padded to a sublane tile inside the kernel and the padding
    dropped. Rows free (first, between, last), one deep, around a block's
    edge and at the end."""
    monkeypatch.setattr(H, "GQA_BLOCK_T", 128)
    k = jax.random.PRNGKey(groups * 100 + queries)
    T, d = 512, 128
    pos = jnp.asarray([0, 1, 127, 0, 128, 129, T - 1, 0])
    B = pos.shape[0]
    q = jax.random.normal(k, (B, groups, queries, d), jnp.bfloat16)
    kv = jax.random.normal(jax.random.fold_in(k, 1),
                           (B, T, 2 * groups * d), jnp.bfloat16)
    assert H.gqa_attend_supported(q, kv)
    got = H.gqa_decode_attend(q, kv, pos, 0.09, interpret=True)
    want = H.dense_decode_attend(q, kv, pos, T, 0.09)
    live = np.asarray(pos) > 0
    assert got.shape == want.shape == (B, groups, queries, d)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-2)
    assert not np.asarray(got)[~live].any()


def test_the_attention_mixer_has_no_flag_for_its_attend():
    """PR 47's ``depth_bounded`` is gone: the mixer's decode attend
    depends on the backend, the shape and the cache kind alone."""
    assert not hasattr(M.AttentionMixer, "depth_bounded")
    assert {f for f in M.AttentionMixer.__dataclass_fields__
            if f not in ("parent", "name")} == {
        "cfg", "qk_norm_eps", "rope_theta", "window"}


# -- the configuration and what config.py refuses ----------------------------

def test_the_share_is_published_layers_0_to_9_with_experts_0_to_35():
    with open(CONFIG) as f:
        src = json.load(f)
    cfg = M.config_from_source(src)
    assert len(src["layer_types"]) == 40
    assert cfg.layers == tuple(src["layer_types"][:10]) == (
        "mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (cfg.router_experts, cfg.experts_held) == (72, tuple(range(36)))
    assert (cfg.num_experts_per_tok, cfg.intermediate_size,
            cfg.shared_intermediate_size) == (10, 768, 1536)
    assert (cfg.head_dim, cfg.attention_multiplier) == (128, 1 / 128)
    assert (cfg.mamba_inner, cfg.conv_width) == (8192, 8448)
    assert cfg.state_bytes_per_slot == 9 * 4_194_304
    assert cfg.conv_bytes_per_slot == 9 * 4 * 8448 * 2
    with pytest.raises(ValueError, match="layer_types"):
        M.config_from_source(dict(src, first_layer_held=35))
    with pytest.raises(ValueError, match="position_embedding_type"):
        M.config_from_source(dict(src, position_embedding_type="rope"))
    with pytest.raises(ValueError, match="experts_held"):
        M.config_from_source(dict(src, experts_held=list(range(35))))
    # groups of B and C are served since PR 43 (ops/state_space.py); what
    # is refused is a count that does not divide the heads
    assert M.config_from_source(dict(src, mamba_n_groups=8)).conv_width \
        == 8192 + 2 * 8 * 128
    with pytest.raises(ValueError, match="mamba_n_groups"):
        M.config_from_source(dict(src, mamba_n_groups=3))


def _cfg(**kw):
    from tensorflow_distributed_tpu.config import TrainConfig
    cfg = TrainConfig(model="granitemoehybrid", mode="serve",
                      model_config=CONFIG)
    for k, v in kw.items():
        obj, *rest = k.split("__")
        if rest:
            setattr(getattr(cfg, obj), rest[0], v)
        else:
            setattr(cfg, obj, v)
    return cfg


@pytest.mark.parametrize("kw,message", [
    ({"mode": "train"}, "the granitemoehybrid family has no training path"),
    ({"model_config": ""}, "takes its sizes from --model-config"),
    ({"model_size": "tiny"}, "no --model-size preset"),
    ({"serve__paged": True}, "no paging over a state or a ring"),
    ({"serve__spec_tokens": 2}, "cannot roll a state back"),
    ({"serve__mesh_model": 2}, "--serve.mesh-model"),
    ({"kv_cache_quant": "int8"}, "int8 KV cache"),
], ids=["train", "no_config", "preset", "paged", "spec", "mesh_model",
        "int8"])
def test_config_refuses_by_name(kw, message):
    from tensorflow_distributed_tpu.config import SOURCE_CONFIG_FAMILIES
    with pytest.raises(ValueError, match=message) as err:
        _cfg(**kw).validate()
    family, untrained, cache = SOURCE_CONFIG_FAMILIES["granitemoehybrid"]
    assert family in str(err.value) or str(err.value) == cache


def test_config_takes_the_family_and_the_registry_builds_it():
    from tensorflow_distributed_tpu.config import SOURCE_CONFIG_MODELS
    from tensorflow_distributed_tpu.models import (
        INFERENCE_ONLY_MODELS, MODEL_NAMES, build_model)
    _cfg().validate()
    assert "granitemoehybrid" in SOURCE_CONFIG_MODELS
    assert "granitemoehybrid" in MODEL_NAMES
    assert "granitemoehybrid" in INFERENCE_ONLY_MODELS
    model = build_model("granitemoehybrid",
                        source=CONFIG + "#rehearsal.sizes", max_len=64)
    assert isinstance(model, M.GraniteMoeHybridLM)
    assert model.cfg.max_len == 64
    with pytest.raises(ValueError, match="no --model-size preset"):
        build_model("granitemoehybrid", size="tiny")


def test_cli_serves_the_family(tmp_path):
    from tensorflow_distributed_tpu import cli
    jsonl = tmp_path / "m.jsonl"
    rc = cli.main([
        "--mode", "serve", "--model", "granitemoehybrid", "--model-config",
        CONFIG + "#rehearsal.sizes", "--compute-dtype", "float32",
        "--seq-len", "64",
        "--serve.num-requests", "5", "--serve.num-slots", "2",
        "--serve.max-new-tokens", "6", "--serve.prompt-len-min", "9",
        "--serve.prompt-len-max", "20", "--observe.metrics-jsonl",
        str(jsonl)])
    assert rc == 0
    recs = [json.loads(x) for x in jsonl.read_text().splitlines()]
    summary = [r for r in recs if r.get("event") == "serve_summary"][-1]
    assert summary["requests"] == 5
    assert set(summary["cache_bytes_per_slot_by_kind"]) == {
        "kv", "state", "conv", "state_pos"}
    assert summary["state_rows_stepped"] == 3 * summary["decode_live_rows"]
    assert summary["moe_layers"] == 4 and summary["moe_held_pairs"] > 0
    # the held experts' plan of the decode step and of every bucket
    plan = summary["moe_plan"]
    assert set(plan) > {"decode"} and all(
        set(p) == {"form", "block_rows", "expected_trips", "max_trips",
                   "tiles_in", "tiles_out", "combine_tile"}
        for p in plan.values())
    assert plan["decode"]["form"] == "one_hot"
    # slots x picked = 6 pairs, in one whole row tile of the grouped matmul
    assert plan["decode"]["block_rows"] == 128
    (start,) = [r for r in recs if r.get("event") == "start"]
    assert (start["model"], start["task"]) == ("granitemoehybrid", "serve")
