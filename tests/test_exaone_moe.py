"""The ``exaone_moe`` family (models/exaone_moe.py; the attention mixer it
shares with models/granitemoehybrid.py, the router and experts it shares
with models/glm_moe_dsa.py; ``prefill_attend`` under a window;
``gqa_dense_attend``) against its plain reference
(perfbench/models/exaone_moe.py), at the benchmark configuration's
REHEARSAL sizes on the CPU, seeded weights.

What is held: prefill then decode through the rings and the full layer's
rows, past several wraps of a 16-row ring, gives the reference's full
forward pass (logits; float32 compute at a tolerance that bfloat16 fails,
bfloat16 at one that fp8 fails), from a prompt shorter than the window,
one exactly a window long and one of several windows; each mechanism
broken in the program shows against the reference; a step the engine
drops and computes again, and a slot freed and used again, leave logits
and cache as an undisturbed run does; the eight chips' shares of a routed
layer (the shared expert counted once) add up to the uncut reference's
layer; ``prefill_attend(window=w)`` is the same as kernel (interpret
mode), XLA loop and masked softmax, and ``window`` 0 is bit for bit what
it gave; ``gqa_dense_attend`` (interpret mode) is ``dense_decode_attend``
and a plain softmax with free slots and rows one past a block's edge, and
its visit count is a walk of the grid's own index map; the ``moe_plan`` of
the benchmark configuration is pinned; ``config.py`` refuses what is not
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

from tensorflow_distributed_tpu.models import exaone_moe as M
from tensorflow_distributed_tpu.models import glm_moe_dsa as G
from tensorflow_distributed_tpu.models.generate import (
    decode_token, prefill_cache)
from tensorflow_distributed_tpu.ops import flash_attention as F
from tensorflow_distributed_tpu.ops import hybrid_attention as H
from tensorflow_distributed_tpu.ops import latent_attention as L

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "perfbench", "configs",
                      "k-exaone-236b-serve.json")
MAX_LEN = 192
W = 16                                   # the rehearsal's sliding_window
# float32 compute against the float32 reference: both sum the same
# products in float32 and differ by the order of the sums (measured 1.2e-6
# on logits of deviation 0.6). bfloat16 operands read 1e-2.
TOL_F32 = 2e-5
# bfloat16 operands, float32 accumulation, against the float32 reference,
# the MEDIAN over positions of a position's largest logit error (a near
# tie of the 3rd and 4th router score turns under bfloat16 rows and moves
# one position by an expert's weight: the largest error is no statistic of
# the precision). Measured 0.006-0.009; the reference at fp8 reads 0.1.
TOL_BF16 = 3e-2


def _reference():
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    try:
        from harness.loader import load_model
        return load_model("exaone_moe")
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
        jax.random.key(47))


def _model(src, dtype=jnp.float32):
    return M.ExaoneMoeLM(M.config_from_source(src, compute_dtype=dtype))


def _tokens(n, seed=0, rows=1):
    return np.random.default_rng(seed).integers(
        0, 96, size=(rows, n)).astype(np.int32)


def _through_the_cache(model, params, toks, prompt, bucket):
    """Logits of ``toks`` [B, n] from a prefill of the first ``prompt``
    tokens padded to ``bucket`` and one decode step a further token:
    [B, n - prompt + 1, V] for positions prompt - 1 .. n - 1."""
    B, n = toks.shape
    padded = np.full((B, bucket), 7, np.int32)     # padding is not zeros
    padded[:, :prompt] = toks[:, :prompt]
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
    # bfloat16 but the router's correction bias, which is seeded non-zero
    assert {k for k, (_, d) in mine.items() if d != jnp.bfloat16} == {
        f"['layer_{i}']['moe']['router_bias']" for i in (1, 2, 3, 4)}
    assert float(jnp.abs(params["layer_1"]["moe"]["router_bias"]).max()) > 0
    assert ref.param_count(sizes) == sum(
        int(np.prod(s)) for s, _ in mine.values())
    # every mechanism at the rehearsal's size: L L L G L with layer 0's
    # feed-forward dense, a window of 16, QK-norm, 4 of 16 experts held
    # with 3 picked, a sliced vocabulary, an untied head
    cfg = model.cfg
    assert cfg.layers == sizes["layers"] == (
        ("sliding_attention", "dense"), ("sliding_attention", "sparse"),
        ("sliding_attention", "sparse"), ("full_attention", "sparse"),
        ("sliding_attention", "sparse"))
    assert (cfg.sliding_window, cfg.rope_theta) == (W, 1e6)
    assert (cfg.router_experts, cfg.experts_held, cfg.num_experts_per_tok
            ) == (16, (0, 1, 2, 3), 3)
    assert "q_norm" in params["layer_3"]["mixer"]
    assert "mlp" in params["layer_0"] and "moe" not in params["layer_0"]
    assert "lm_head" in params and params["tok_emb"].shape == (96, 32)


@pytest.mark.parametrize("prompt,new,bucket,dtype,tol", [
    (70, 60, 128, jnp.float32, TOL_F32),
    (W, 40, 64, jnp.float32, TOL_F32),
    (W - 5, 40, 64, jnp.float32, TOL_F32),
    (W + 1, 20, 64, jnp.float32, TOL_F32),
    (70, 60, 128, jnp.bfloat16, TOL_BF16)],
    ids=["f32_several_windows", "f32_exactly_a_window",
         "f32_shorter_than_the_window", "f32_one_past_the_window", "bf16"])
def test_prefill_then_decode_is_the_references_forward_pass(
        ref, src, weights, prompt, new, bucket, dtype, tol):
    """Decoding ``new`` tokens turns the 16-row ring two to four times."""
    sizes, params = weights
    toks = _tokens(prompt + new, seed=prompt, rows=2)
    got, cache = _through_the_cache(_model(src, dtype), params, toks, prompt,
                                    bucket)
    want = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes))
    err = np.abs(got - want[:, prompt - 1:]).max(-1)      # a position
    if dtype == jnp.bfloat16:
        assert np.median(err) < tol and err.max() < 0.5, (np.median(err),
                                                          err.max())
        # what the tolerance tells apart: fp8 operands fail it
        low = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes,
                                       "fp8"))
        assert np.median(np.abs(low - want).max(-1)) > 2 * tol
    else:
        assert err.max() < tol, err.max()
    # two kinds of K and V leaf in one model
    kinds = {name: set(layer["mixer"]) for name, layer in cache.items()}
    assert kinds == {"layer_0": {"kv_ring"}, "layer_1": {"kv_ring"},
                     "layer_2": {"kv_ring"}, "layer_3": {"kv"},
                     "layer_4": {"kv_ring"}}
    assert cache["layer_0"]["mixer"]["kv_ring"].shape == (2, W, 2 * 2 * 8)
    assert cache["layer_3"]["mixer"]["kv"].shape == (2, MAX_LEN, 2 * 2 * 8)


def test_the_forward_pass_without_a_cache_agrees_too(ref, src, weights):
    sizes, params = weights
    toks = _tokens(128, seed=3)
    got = np.asarray(_model(src).apply({"params": params},
                                       jnp.asarray(toks)))
    want = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes))
    assert np.abs(got - want).max() < TOL_F32


@pytest.mark.parametrize("control", ["window_ignored", "rope_everywhere",
                                     "no_routed", "fp8"])
def test_the_reference_with_a_mechanism_changed_is_another_model(
        ref, src, weights, control):
    """What the benchmark's controls rest on: the reference with the
    window ignored, the rotation on the full layer too, the routed part
    left out or fp8 operands differs from the sound one by far more than
    the program does."""
    sizes, params = weights
    toks = jnp.asarray(_tokens(96, seed=5))
    want = np.asarray(ref.logits_fn(params, toks, sizes))
    kw = {"precision": "fp8"} if control == "fp8" else {"break_": control}
    got = np.asarray(ref.logits_fn(params, toks, sizes, **kw))
    # past the window (before it a band is the triangle)
    assert np.abs(got - want)[:, 2 * W:].max() > 0.05


@pytest.mark.parametrize("prompt,how", [(70, "ring_one_off"),
                                        (W - 5, "ring_read_unmasked"),
                                        (70, "routed_part_left_out")])
def test_a_mechanism_broken_in_the_program_shows(ref, src, weights, prompt,
                                                 how):
    """The breaks perfbench/tools/kexaone_controls.py makes underneath the
    program, at the model's own level: a ring written one slot off, a ring
    read unmasked before it has wrapped (only a prompt shorter than the
    window can show it: the rows past its depth hold the bucket's
    padding), the routed part left out."""
    sizes, params = weights
    sys.path.insert(0, os.path.join(ROOT, "perfbench", "tools"))
    try:
        import kexaone_controls
    finally:
        sys.path.pop(0)
    toks = _tokens(prompt + 24, seed=prompt + 1, rows=2)
    want = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes))
    with kexaone_controls.broken(how, W):
        got, _ = _through_the_cache(_model(src), params, toks, prompt, 96)
    err = np.abs(got - want[:, prompt - 1:]).max(-1)[:, 1:]    # decoded
    assert err.max() > 0.05, err.max()


# -- the shares of a routed layer ---------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer(ref, src):
    """One layer's experts divided between the eight chips that share it
    (2 of 16 each here), each through the PROGRAM's layer with its own
    share, against the reference's layer with all 16: the eight routed
    parts with the shared expert counted once are the whole."""
    whole = dict(src, num_experts=16, experts_held=list(range(16)))
    sizes = ref.sizes(whole)
    params = jax.jit(lambda k: ref.make_params(k, sizes))(
        jax.random.key(9))["layer_1"]["moe"]
    u = jnp.asarray(np.random.default_rng(9).standard_normal((2, 40, 32)),
                    jnp.float32)
    flat = u.reshape(80, 32)
    parts = []
    for lo in range(0, 16, 2):
        held = (lo, lo + 1)
        cfg = M.config_from_source(
            dict(src, num_experts=2, experts_held=list(held)),
            compute_dtype=jnp.float32)
        mine = dict(params, **{
            name: {"kernel": params[name]["kernel"][jnp.asarray(held)]}
            for name in ("experts_gate", "experts_up", "experts_down")})
        parts.append(np.asarray(G.SparseMoe(cfg).apply(
            {"params": mine}, u)).reshape(80, 32))
        # a share alone is the reference given the same share
        want = np.asarray(ref.expert_layer(flat, params, sizes, "f32",
                                           held=held))
        np.testing.assert_allclose(parts[-1], want, rtol=0, atol=2e-5)
    shared = np.asarray(ref.expert_layer(flat, params, sizes, "f32",
                                         routed=False))
    uncut = np.asarray(ref.expert_layer(flat, params, sizes, "f32"))
    np.testing.assert_allclose(sum(parts) - 7 * shared, uncut, rtol=0,
                               atol=5e-5)
    assert np.abs(uncut - shared).max() > 0.05    # the routed part counts
    # the bias picks and does not weigh: without it another routing
    no_bias = dict(params, router_bias=0.0 * params["router_bias"])
    other = np.asarray(ref.expert_layer(flat, no_bias, sizes, "f32"))
    assert np.abs(other - uncut).max() > 0.01


# -- the prefill attend under a window ----------------------------------------

def _masked_softmax(q, k, v, scale, window):
    n = q.shape[1]
    s = jnp.einsum("hqd,hkd->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    keep = F.window_keep(jnp.arange(n)[:, None], jnp.arange(n)[None, :],
                         window)
    return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(
        jnp.where(keep, s, -jnp.inf), -1), v,
        precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("n,window,block", [
    (1024, 128, None), (1024, 16, None), (2048, 128, 256), (2048, 128, 128),
    (2048, 300, 512), (3072, 128, None)],
    ids=["one_step_128", "one_step_16", "banded_grid_256", "banded_grid_128",
         "window_no_multiple", "plans_blocks_band_steps"])
def test_prefill_attend_under_a_window_kernel_loop_and_masked_softmax(
        n, window, block):
    """``block`` None: the blocks the plan gives (at 3,072 a key axis of
    two steps where the context has three blocks); else the forward
    kernel under blocks of its own, where the band's steps are fewer
    still against the context's."""
    k = jax.random.PRNGKey(n + window)
    q, kk, v = (jax.random.normal(jax.random.fold_in(k, i), (2, n, 128),
                                  jnp.float32) for i in range(3))
    want = _masked_softmax(q, kk, v, 0.09, window)
    loop = L.prefill_attend_xla(q, kk, v, None, 0.09, window)
    np.testing.assert_allclose(loop, want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(L.prefill_attend(q, kk, v, None, 0.09,
                                                window), loop, atol=0)
    bf = [x.astype(jnp.bfloat16) for x in (q, kk, v)]
    plan = L.prefill_attend_plan(n, 128, 128, jnp.bfloat16, window)
    if block is None:
        kernel = L.prefill_attend_kernel(*bf, None, 0.09, interpret=True,
                                         window=window)
    else:
        plan = F.flash_plan(n, n, 128, jnp.bfloat16, causal=True,
                            window=window, Dv=128, block_q=block,
                            block_k=block)
        kernel = F._fwd(*bf, causal=True, plan=plan, interpret=True,
                        window=window, scale=0.09, stats=False)[0]
    np.testing.assert_allclose(
        kernel.astype(jnp.float32),
        L.prefill_attend_xla(*bf, None, 0.09, window).astype(jnp.float32),
        atol=2e-2, rtol=0)
    np.testing.assert_allclose(kernel.astype(jnp.float32), want, atol=3e-2,
                               rtol=0)
    # the key axis is a band's steps, and never more tiles than the
    # triangle's
    lo, hi = F._offsets(True, window, walk_keys=True)
    n_k = n // plan.block_k
    steps = F._band_steps(plan.block_q, n // plan.block_q, plan.block_k,
                          n_k, lo, hi)
    assert steps == min(n_k, -(-(window - 1) // plan.block_k) + 1)
    causal = L.prefill_attend_plan(n, 128, 128, jnp.bfloat16)
    area = lambda p: p.tiles_computed * p.tile_q * p.tile_k   # noqa: E731
    assert area(plan) <= area(causal)
    if block is None:
        assert (plan.block_q, plan.block_k) == (causal.block_q,
                                                causal.block_k)


def test_window_and_selection_in_one_call_is_refused():
    q = jnp.zeros((1, 256, 128))
    with pytest.raises(ValueError, match="two masks"):
        L.prefill_attend(q, q, q, jnp.ones((256, 256), bool), 0.1, 16)


# What every user of the fused attend gets with ``window`` 0, as the parent
# gave it (block_q, block_k, tile_q, tile_k, tiles_total, tiles_computed,
# tiles_masked): the buckets of the five configurations that call it.
WINDOW_0_PLANS = {
    (256, 128, 128): (256, 256, 256, 256, 1, 1, 1),
    (512, 128, 128): (512, 512, 256, 256, 4, 3, 2),
    (1024, 128, 128): (1024, 1024, 256, 256, 16, 10, 4),
    (3072, 128, 128): (1024, 1024, 1024, 1024, 9, 6, 3),
    (4096, 128, 128): (1024, 1024, 1024, 1024, 16, 10, 4),
    (12288, 128, 128): (1024, 1024, 1024, 1024, 144, 78, 12),
    (3072, 256, 256): (1024, 1024, 1024, 1024, 9, 6, 3),
    (14336, 256, 256): (1024, 1024, 1024, 1024, 196, 105, 14),
    (8192, 192, 128): (1024, 1024, 1024, 1024, 64, 36, 8),
}


@pytest.mark.parametrize("shape", sorted(WINDOW_0_PLANS))
def test_window_0_plans_are_the_parents(shape):
    n, dq, dv = shape
    plan = L.prefill_attend_plan(n, dq, dv, jnp.bfloat16)
    assert tuple(plan) == WINDOW_0_PLANS[shape]
    assert plan == L.prefill_attend_plan(n, dq, dv, jnp.bfloat16, 0)
    assert plan == F.flash_plan(n, n, dq, jnp.bfloat16, causal=True, Dv=dv)


def test_window_0_is_bit_for_bit_the_causal_attend():
    k = jax.random.PRNGKey(2)
    q, kk, v = (jax.random.normal(jax.random.fold_in(k, i), (2, 2048, 128),
                                  jnp.bfloat16) for i in range(3))
    np.testing.assert_array_equal(
        L.prefill_attend_xla(q, kk, v, None, 0.09),
        L.prefill_attend_xla(q, kk, v, None, 0.09, 0))
    a = L.prefill_attend_kernel(q, kk, v, None, 0.09, interpret=True)
    b = L.prefill_attend_kernel(q, kk, v, None, 0.09, interpret=True,
                                window=0)
    np.testing.assert_array_equal(a, b)
    # a window as long as the context is the triangle (every block takes
    # the mask, so the sums round as another program's)
    np.testing.assert_allclose(
        L.prefill_attend_xla(q, kk, v, None, 0.09, 2048).astype(jnp.float32),
        L.prefill_attend_xla(q, kk, v, None, 0.09).astype(jnp.float32),
        atol=2e-3)


def test_the_bands_plan_at_the_longest_bucket():
    """At the 12,288 bucket the causal plan computes 78 tiles of 1,024 x
    1,024; under the window of 128 the same blocks compute 23 (the
    diagonal block and the one before: 16 windows of keys a query) in a
    grid whose key axis is 2 steps long, not 12."""
    causal = L.prefill_attend_plan(12288, 128, 128, jnp.bfloat16)
    band = L.prefill_attend_plan(12288, 128, 128, jnp.bfloat16, 128)
    assert causal.tiles_computed == 78
    assert (band.block_q, band.block_k) == (causal.block_q, causal.block_k)
    assert band.tiles_computed == 2 * 12 - 1
    lo, hi = F._offsets(True, 128, walk_keys=True)
    assert F._band_steps(1024, 12, 1024, 12, lo, hi) == 2
    describe = L.prefill_attend_describe(12288, 128, 128, jnp.bfloat16, 128)
    assert describe["window"] == 128
    assert describe["keys_per_query"] == describe["tiles_computed"] \
        * describe["block_q"] * describe["block_k"] / 12288


# -- the full layers' decode attend -------------------------------------------

def _plain_gqa(q, kv, pos, scale):
    B, Gk, h, d = q.shape
    out = np.zeros(q.shape, np.float32)
    q, kv = np.asarray(q, np.float32), np.asarray(kv, np.float32)
    for b, p in enumerate(np.asarray(pos)):
        if p == 0:
            continue                       # a free slot gives zeros
        for g in range(Gk):
            kk = kv[b, :p + 1, g * d:(g + 1) * d]
            vv = kv[b, :p + 1, (Gk + g) * d:(Gk + g + 1) * d]
            s = scale * q[b, g] @ kk.T
            w = np.exp(s - s.max(-1, keepdims=True))
            out[b, g] = (w / w.sum(-1, keepdims=True)) @ vv
    return out


def test_gqa_dense_attend_kernel_xla_form_and_plain_softmax(monkeypatch):
    """Rows at depth 0 (free slots, first, in the middle and last), at
    depth 1, just below, at and just past a block's edge, and at T - 1."""
    monkeypatch.setattr(H, "GQA_BLOCK_T", 128)
    k = jax.random.PRNGKey(0)
    T, Gk, h, d = 512, 2, 8, 128
    pos = jnp.asarray([0, 1, 127, 0, 128, 129, T - 1, 0])
    B = pos.shape[0]
    q = jax.random.normal(k, (B, Gk, h, d), jnp.bfloat16)
    kv = jax.random.normal(jax.random.fold_in(k, 1), (B, T, 2 * Gk * d),
                           jnp.bfloat16)
    want = _plain_gqa(q, kv, pos, 0.09)
    assert H.gqa_attend_supported(q, kv)
    kernel = H.gqa_decode_attend(q, kv, pos, 0.09, interpret=True)
    xla = H.gqa_decode_attend(q, kv, pos, 0.09)
    live = np.asarray(pos) > 0
    np.testing.assert_allclose(kernel, want, atol=1e-2)
    np.testing.assert_allclose(xla, want, atol=1e-2)
    np.testing.assert_allclose(
        np.asarray(H.dense_decode_attend(q, kv, pos, T, 0.09))[live],
        np.asarray(xla)[live], atol=0)
    assert not np.asarray(kernel)[~live].any()
    assert not np.asarray(xla)[~live].any()
    f32 = H.gqa_decode_attend(q.astype(jnp.float32),
                              kv.astype(jnp.float32), pos, 0.09)
    np.testing.assert_allclose(f32, want, atol=2e-5)


@pytest.mark.parametrize("pos", [
    [0, 1, 127, 0, 128, 129, 511, 0], [0, 0, 0, 5], [300, 0, 0, 7],
    [0, 0, 0, 0], [511, 511]])
def test_gqa_visits_are_what_the_kernels_grid_visits(monkeypatch, pos):
    """Walk the kernel's grid on the host with its own schedule and its
    own predicate: the blocks it computes on, and the blocks its index
    map makes it FETCH (a step whose block index equals the step before
    moves nothing), both cover ``gqa_attend_visits`` positions, none of
    them in a free slot or past a row's depth."""
    monkeypatch.setattr(H, "GQA_BLOCK_T", 128)
    T = 512
    bt = H.gqa_attend_block(T)
    p = jnp.asarray(pos, jnp.int32)
    row, lo, hi = (np.asarray(a) for a in L.dense_attend_schedule(p, bt))
    computed, fetched, held = 0, 0, None
    for b in range(len(pos)):
        for j in range(T // bt):
            block = (int(row[b]), int(np.clip(j, lo[b], hi[b])))
            if block != held:
                fetched += 1
                held = block
                assert pos[block[0]] > 0 or not any(pos)
                assert block[1] * bt <= pos[block[0]]
            if pos[b] > 0 and j * bt <= pos[b]:
                computed += 1
                assert block == (b, j)       # it computes on its own block
    visits = int(H.gqa_attend_visits(p, T))
    assert computed * bt == visits
    assert fetched * bt == (visits if any(pos) else bt)
    assert visits == sum((q // bt + 1) * bt for q in pos if q > 0)


def test_ring_rows_holds_the_last_window_and_nothing_of_the_padding():
    rows = jnp.arange(2 * 40 * 3, dtype=jnp.float32).reshape(2, 40, 3) + 1
    ring = np.asarray(H.ring_rows(rows, jnp.asarray([40, 5]), W))
    for b, n in enumerate((40, 5)):
        for r in range(W):
            at = [p for p in range(max(n - W, 0), n) if p % W == r]
            want = np.asarray(rows[b, at[0]]) if at else np.zeros(3)
            np.testing.assert_array_equal(ring[b, r], want)
    np.testing.assert_array_equal(
        H.ring_rows(rows, None, W), H.ring_rows(rows, jnp.asarray(40), W))


# -- steps computed again, slots used again ----------------------------------

@pytest.fixture(scope="module")
def served(src, weights):
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    _, params = weights
    model = _model(src)

    def engine(slots=2):
        return SlotDecodeEngine(model, params, slots, buckets=(64, 128))
    return model, params, engine


def _cache_of(eng, slot):
    eng.drain()
    cache = jax.device_get(eng.cache)
    return [np.asarray(leaf)[slot] for leaf in
            jax.tree_util.tree_leaves(cache)]


def _logits_now(model, params, eng, slot):
    last, _ = decode_token(model, params, eng.cache, jnp.asarray(eng.tok),
                           jnp.asarray(eng.pos))
    return np.asarray(last)[slot]


def test_a_step_dropped_and_computed_again_writes_the_same_rows(served):
    model, params, engine = served
    prompt = _tokens(70, seed=9)[0]
    calm, jumpy = engine(), engine()
    for eng in (calm, jumpy):
        eng.prefill(prompt, 0)
    got = {id(calm): [], id(jumpy): []}
    for i in range(2 * W + 3):            # two turns of the ring
        for eng in (calm, jumpy):
            nxt = eng.step()
            assert eng.step_valid[0]
            got[id(eng)].append(int(nxt[0]))
        if i % 2 == 0:
            assert jumpy._ahead is not None
            jumpy.drain()
    assert got[id(jumpy)] == got[id(calm)]
    assert jumpy.ahead_rows_dropped >= W
    for x, y in zip(_cache_of(calm, 0), _cache_of(jumpy, 0)):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(_logits_now(model, params, calm, 0),
                                  _logits_now(model, params, jumpy, 0))


def test_a_slot_freed_and_used_again_reads_nothing_of_its_last_tenant(
        served):
    """The next tenant's prompt is SHORTER than the window: its rings hold
    rows of the last tenant past its depth until its own steps write them,
    and no step reads them."""
    model, params, engine = served
    first, second, other = (_tokens(n, seed=s)[0] for n, s in (
        (90, 10), (W - 6, 11), (75, 12)))
    busy, calm = engine(), engine()
    busy.prefill(other, 0)
    busy.prefill(first, 1)
    for _ in range(3):
        busy.step()
    assert busy._ahead is not None and busy._ahead.rows[1]
    busy.free(1)                          # the step in flight ran for it
    busy.prefill(second, 1)
    calm.prefill(second, 1)
    got, want = [], []
    for _ in range(2 * W):
        nxt = busy.step()
        if busy.step_valid[1]:
            got.append(int(nxt[1]))
    for _ in range(len(got)):
        nxt = calm.step()
        assert calm.step_valid[1]
        want.append(int(nxt[1]))
    assert got == want and len(got) >= 2 * W - 1


def test_the_scheduler_serves_one_shot_greedy_tokens(served):
    from tensorflow_distributed_tpu.models.generate import generate
    from tensorflow_distributed_tpu.serve.scheduler import (
        Request, Scheduler)
    model, params, engine = served
    reqs = [Request(rid=i, prompt=_tokens(n, seed=20 + i)[0],
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(70, 20), (9, 24), (W, 18),
                                        (100, 5)])]
    eng = engine()
    done = Scheduler(eng).run(reqs)
    assert eng._ahead is None and eng.steps_ahead > 0
    for r in reqs:
        want = np.asarray(generate(model, params,
                                   jnp.asarray(r.prompt[None, :]),
                                   r.max_new_tokens))[0]
        got = next(c for c in done if c.rid == r.rid)
        assert [int(t) for t in got.tokens] == [int(t) for t in want], r.rid


def test_the_counters_are_the_counts_made_by_hand(served, monkeypatch):
    monkeypatch.setattr(H, "GQA_BLOCK_T", 64)
    model, params, engine = served
    eng = engine(slots=3)
    eng.prefill(_tokens(70, seed=30)[0], 0)
    for _ in range(4):                    # one live row
        eng.step()
    eng.prefill(_tokens(9, seed=31)[0], 2)
    for _ in range(3):                    # the first of these was launched
        eng.step()                        # before the second admission
    stats = eng.model_stats()
    # steps RETURNED: 4 + 1 with slot 0 alone, 2 with slots 0 and 2
    live = 5 * 1 + 2 * 2
    assert stats["decode_live_rows"] == live
    by_kind = stats["cache_bytes_per_slot_by_kind"]
    assert by_kind == {"kv": MAX_LEN * 2 * 2 * 8 * 4,       # float32 here
                       "kv_ring": 4 * W * 2 * 2 * 8 * 4}
    # slot 0 at depths 70..76, slot 2 at 9..10: causal positions a layer
    depths = list(range(71, 78)) + [10, 11]
    assert stats["full_attend_keys"] == sum(depths)
    assert stats["select_keys_available"] == 5 * sum(depths)
    assert stats["select_keys_kept"] == sum(depths) + 4 * sum(
        min(p, W) for p in depths)
    assert stats["index_keep_share"] == round(
        stats["select_keys_kept"] / stats["select_keys_available"], 6)
    # the kernel's blocks of 64 to each live row's depth; the rings'
    # slot-blind attend every slot's whole ring, every step
    steps = 7
    assert stats["attend_positions_visited"] == sum(
        (p - 1) // 64 * 64 + 64 for p in depths) + 4 * 3 * W * steps
    assert stats["moe_layers"] == 4
    assert stats["moe_pairs_routed"] == live * 3 * 4
    assert 0 < stats["moe_held_pairs"] < stats["moe_pairs_routed"]
    assert sum(stats["moe_held_pairs_by_expert"]) == stats["moe_held_pairs"]
    plan = stats["moe_plan"]["decode"]
    assert (plan["form"], plan["block_rows"]) == ("one_hot", 128)


# -- the configuration and what config.py refuses ----------------------------

# ``moe_plan`` at the benchmark configuration's shapes (16 of 128 gated
# experts [6144, 2048], 8 picked, 32 slots), pinned beside the ones
# tests/test_glm_moe_dsa.py holds: (form, rows of a block, expected trips,
# lanes of the result the combine holds).
KEXAONE_PLAN = {
    "decode": ("one_hot", 256, 1, 2048), "256": ("one_hot", 2048, 1, 2048),
    "512": ("gather", 1024, 1, 2048), "1024": ("gather", 2048, 1, 2048),
    "2048": ("gather", 3840, 1, 2048), "3072": ("gather", 5632, 1, 2048),
    "4096": ("gather", 7424, 1, 2048), "6144": ("gather", 8192, 1, 1024),
    "8192": ("gather", 8192, 1, 1024), "12288": ("gather", 8192, 2, 512)}


def test_the_plan_of_the_benchmark_configuration():
    from tensorflow_distributed_tpu.models import build_model
    with open(CONFIG) as f:
        src = json.load(f)
    model = build_model(src["model"], source=CONFIG)
    buckets = [int(b) for b in src["serve"]["buckets"].split(",")]
    slots = src["serve"]["num_slots"]
    got = model.moe_plan(slots, buckets)
    assert set(got) == set(KEXAONE_PLAN)
    for name, p in got.items():
        assert (p["form"], p["block_rows"], p["expected_trips"],
                p["combine_tile"]) == KEXAONE_PLAN[name], (name, p)
        tokens = slots if name == "decode" else int(name)
        # 2 to 768 rows an expert: 128-row tiles below 64 rows an expert
        tm = 256 if tokens * 8 / 8 / 16 >= 64 else 128
        assert (p["tiles_in"], p["tiles_out"]) == (
            [tm, 1536, 1024], [tm, 1024, 1024]), (name, p)
        assert p["block_rows"] % tm == 0
        assert p["max_trips"] * p["block_rows"] >= tokens * 8    # dropless


def test_the_share_is_published_layers_0_to_4_with_experts_0_to_15():
    with open(CONFIG) as f:
        src = json.load(f)
    cfg = M.config_from_source(src)
    assert len(src["layer_types"]) == len(src["mlp_layer_types"]) == 48
    assert src["layer_types"][:5] == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    assert [ff for _, ff in cfg.layers] == ["dense"] + ["sparse"] * 4
    assert (cfg.count("sliding_attention"), cfg.count("full_attention"),
            cfg.count("sparse"), cfg.count("dense")) == (4, 1, 4, 1)
    assert (cfg.router_experts, cfg.experts_held) == (128, tuple(range(16)))
    assert (cfg.num_experts_per_tok, cfg.moe_intermediate_size,
            cfg.intermediate_size, cfg.hidden_size) == (8, 2048, 18432, 6144)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.attention_multiplier, cfg.sliding_window, cfg.rope_theta
            ) == (64, 8, 128, 128 ** -0.5, 128, 1e6)
    assert (cfg.routed_scaling_factor, cfg.norm_topk_prob, cfg.n_group,
            cfg.vocab_size, cfg.max_len) == (2.5, True, 1, 19200, 16384)
    with pytest.raises(ValueError, match="layer_types"):
        M.config_from_source(dict(src, first_layer_held=45))
    with pytest.raises(ValueError, match="mlp_layer_types"):
        M.config_from_source(dict(src, mlp_layer_types=["moe"] * 48))
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        M.config_from_source(dict(src, first_layer_held=1,
                                  first_k_dense_replace=2))
    with pytest.raises(ValueError, match="experts_held"):
        M.config_from_source(dict(src, experts_held=list(range(15))))
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        M.config_from_source(dict(src, num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        M.config_from_source(dict(src, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="scoring_func"):
        M.config_from_source(dict(src, scoring_func="softmax"))
    with pytest.raises(ValueError, match="rope_type"):
        M.config_from_source(dict(src, rope_parameters={
            "rope_theta": 1e6, "rope_type": "yarn"}))
    with pytest.raises(ValueError, match="group limit"):
        M.config_from_source(dict(src, n_group=8, topk_group=4))


def _cfg(**kw):
    from tensorflow_distributed_tpu.config import TrainConfig
    cfg = TrainConfig(model="exaone_moe", mode="serve", model_config=CONFIG)
    for k, v in kw.items():
        obj, *rest = k.split("__")
        if rest:
            setattr(getattr(cfg, obj), rest[0], v)
        else:
            setattr(cfg, obj, v)
    return cfg


@pytest.mark.parametrize("kw,message", [
    ({"mode": "train"}, "the exaone_moe family has no training path"),
    ({"model_config": ""}, "takes its sizes from --model-config"),
    ({"model_size": "tiny"}, "no --model-size preset"),
    ({"serve__paged": True}, "no paging over a ring"),
    ({"serve__spec_tokens": 2}, "cannot take them back out of a ring"),
    ({"serve__mesh_model": 2}, "no exchange of routed pairs"),
    ({"kv_cache_quant": "int8"}, "int8 KV cache"),
], ids=["train", "no_config", "preset", "paged", "spec", "mesh_model",
        "int8"])
def test_config_refuses_by_name(kw, message):
    from tensorflow_distributed_tpu.config import SOURCE_CONFIG_FAMILIES
    with pytest.raises(ValueError, match=message) as err:
        _cfg(**kw).validate()
    family, untrained, cache = SOURCE_CONFIG_FAMILIES["exaone_moe"]
    assert family in str(err.value) or str(err.value) == cache


def test_config_takes_the_family_and_the_registry_builds_it():
    from tensorflow_distributed_tpu.config import (
        SOURCE_CONFIG_FAMILIES, SOURCE_CONFIG_MODELS)
    from tensorflow_distributed_tpu.models import (
        INFERENCE_ONLY_MODELS, MODEL_NAMES, build_model)
    _cfg().validate()
    assert len(SOURCE_CONFIG_FAMILIES) == 7
    assert "exaone_moe" in SOURCE_CONFIG_MODELS
    assert "exaone_moe" in MODEL_NAMES
    assert "exaone_moe" in INFERENCE_ONLY_MODELS
    model = build_model("exaone_moe", source=CONFIG + "#rehearsal.sizes",
                        max_len=64)
    assert isinstance(model, M.ExaoneMoeLM)
    assert model.cfg.max_len == 64
    with pytest.raises(ValueError, match="no --model-size preset"):
        build_model("exaone_moe", size="tiny")


def test_cli_serves_the_family(tmp_path):
    from tensorflow_distributed_tpu import cli
    jsonl = tmp_path / "m.jsonl"
    rc = cli.main([
        "--mode", "serve", "--model", "exaone_moe", "--model-config",
        CONFIG + "#rehearsal.sizes", "--compute-dtype", "float32",
        "--seq-len", "64",
        "--serve.num-requests", "5", "--serve.num-slots", "2",
        "--serve.max-new-tokens", "20", "--serve.prompt-len-min", "9",
        "--serve.prompt-len-max", "30", "--observe.metrics-jsonl",
        str(jsonl)])
    assert rc == 0
    recs = [json.loads(x) for x in jsonl.read_text().splitlines()]
    summary = [r for r in recs if r.get("event") == "serve_summary"][-1]
    assert summary["requests"] == 5
    assert summary["cache_bytes_per_slot_by_kind"] == {
        "kv": 64 * 32 * 4, "kv_ring": 4 * W * 32 * 4}
    assert summary["moe_layers"] == 4 and summary["moe_held_pairs"] > 0
    assert summary["moe_pairs_routed"] == \
        summary["decode_live_rows"] * 3 * 4
    assert summary["select_keys_kept"] < summary["select_keys_available"]
    assert summary["full_attend_keys"] * 5 == \
        summary["select_keys_available"]
    assert summary["attend_positions_visited"] > 0
    plan = summary["moe_plan"]
    assert set(plan) > {"decode"} and plan["decode"]["form"] == "one_hot"
    (start,) = [r for r in recs if r.get("event") == "start"]
    assert (start["model"], start["task"]) == ("exaone_moe", "serve")
    attend = start["prefill_attend_plan"]
    assert set(next(iter(attend.values()))) == {"full", "window"}
    assert all(p["window"]["window"] == W for p in attend.values())
