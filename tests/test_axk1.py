"""The latent-attention family's third layer kind (PR 33): a layer with
NO indexer, dense attention under YaRN, and group-limited routing, as the
source ``model_type: axk1`` (A.X-K1, DeepSeek-V3's keys) asks for them,
against the plain reference of ``perfbench/models/axk1.py`` (float32,
``highest``, no code of the package, none of the GLM reference's).

The program runs with ``compute_dtype=float32`` here, on the same
bfloat16-valued weights, so both sides do the same arithmetic in another
order: the tolerance 2e-5 on logits of magnitude ~0.4 is summation order,
nothing else (they read 1e-7 apart). A mechanism left out (the YaRN ramp,
the softmax's mscale factor, the group mask) moves a logit by 3e-4 to
1e-2 even at these widths, where N(0, 0.02) weights make every softmax
nearly flat: ten tolerances at the least, which a parametrised test shows
for each.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERFBENCH = os.path.join(ROOT, "perfbench")
if PERFBENCH not in sys.path:
    sys.path.insert(0, PERFBENCH)

from harness.loader import load_model  # noqa: E402

from tensorflow_distributed_tpu.models import build_model  # noqa: E402
from tensorflow_distributed_tpu.models import glm_moe_dsa as G  # noqa: E402
from tensorflow_distributed_tpu.ops import latent_attention as L  # noqa: E402
from tensorflow_distributed_tpu.serve.engine import (  # noqa: E402
    SlotDecodeEngine)

TOL = 2e-5
REF = load_model("axk1", runner_kind="serve")
GLM_REF = load_model("glm_moe_dsa", runner_kind="serve")

# Every mechanism at toy widths, under the source's key names: 16 experts
# in 4 groups of which 2 are kept, 4 held across a group's edge, YaRN with
# a factor above 1 whose ramp ends inside the rope width, one dense and
# four expert layers, and NO indexer key at all.
TINY_SOURCE = dict(
    vocab_size=96, hidden_size=32, num_attention_heads=4, q_lora_rank=16,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    intermediate_size=64, moe_intermediate_size=16, n_routed_experts=4,
    n_routed_experts_published=16, experts_held=[2, 3, 4, 5],
    n_shared_experts=1, num_experts_per_tok=4, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=8, beta_fast=32, beta_slow=1,
                      mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=8),
    max_position_embeddings=64, num_hidden_layers=5,
    first_k_dense_replace=1, moe_layer_freq=1)
PUBLISHED = os.path.join(PERFBENCH, "configs", "ax-k1-serve.json")


def build(seed=3, **over):
    src = dict(TINY_SOURCE)
    src.update(over)
    sizes = REF.sizes(src)
    cfg = G.config_from_source(src, compute_dtype=jnp.float32)
    params = jax.jit(lambda k: REF.make_params(k, sizes))(
        jax.random.PRNGKey(seed))
    return G.GlmMoeDsaLM(cfg), params, sizes


@pytest.fixture(scope="module")
def tiny():
    model, params, sizes = build()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                              sizes["vocab_size"])
    return model, params, sizes, toks, REF.logits_fn(params, toks, sizes)


# -- the layer list and the family's names -----------------------------------

def test_a_source_without_indexer_keys_gives_dense_layers():
    cfg = G.config_from_source(dict(TINY_SOURCE))
    assert [(s.mlp, s.indexer) for s in cfg.layers] == [
        ("dense", "none")] + [("sparse", "none")] * 4
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        0, 0, 0)
    # first_layer_held keeps its meaning: published layers 3-4 of a model
    # with two leading dense layers and an expert layer every second one
    cfg = G.config_from_source(dict(
        TINY_SOURCE, first_k_dense_replace=2, moe_layer_freq=2,
        first_layer_held=1, num_hidden_layers=4))
    assert [s.mlp for s in cfg.layers] == ["dense", "sparse", "dense",
                                           "sparse"]
    with pytest.raises(ValueError, match="n_group"):
        G.config_from_source(dict(TINY_SOURCE, n_group=3))
    with pytest.raises(ValueError, match="n_group"):
        G.config_from_source(dict(TINY_SOURCE, topk_group=1,
                                  num_experts_per_tok=5))
    with pytest.raises(ValueError, match="yarn"):
        G.config_from_source(dict(TINY_SOURCE, rope_scaling=dict(
            type="linear", factor=2)))


def test_the_published_configuration_through_either_name():
    with open(PUBLISHED) as f:
        src = json.load(f)
    cfg = G.config_from_source(src)
    assert [(s.mlp, s.indexer) for s in cfg.layers] == [
        ("dense", "none")] + [("sparse", "none")] * 4
    assert (cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.latent_dim, cfg.latent_row, cfg.qk_head_dim,
            cfg.v_head_dim, cfg.intermediate_size,
            cfg.moe_intermediate_size) == (
        7168, 1536, 512, 576, 640, 192, 128, 18432, 2048)
    assert (cfg.router_experts, cfg.n_group, cfg.topk_group,
            cfg.num_experts_per_tok, cfg.experts_held) == (
        192, 8, 4, 8, tuple(range(12)))
    assert cfg.rope_scaling == G.RopeScaling(32.0, 4096, 32.0, 1.0, 1.0, 1.0)
    a = build_model("axk1", source=PUBLISHED)
    b = build_model("glm_moe_dsa", source=PUBLISHED)
    assert type(a) is type(b) is G.GlmMoeDsaLM and a.cfg == b.cfg


def test_a_source_with_indexer_types_still_builds_glms_tree():
    """GLM's rehearsal sizes through the family as it is now: parameters
    and cache leaf for leaf what GLM's own benchmark file makes (names,
    shapes, dtypes), indexer leaves on the ``full`` layers only."""
    with open(os.path.join(PERFBENCH, "configs",
                           "glm-5.2-serve.json")) as f:
        src = json.load(f)["rehearsal"]["sizes"]
    model = G.GlmMoeDsaLM(G.config_from_source(src))
    assert model.cfg.rope_scaling is None and model.cfg.n_group == 1
    at = jnp.zeros((2, 1), jnp.int32)
    mine = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    theirs = jax.eval_shape(
        lambda k: GLM_REF.make_params(k, GLM_REF.sizes(src)),
        jax.random.PRNGKey(0))
    flat = lambda t: {jax.tree_util.keystr(p): (x.shape, x.dtype)  # noqa: E731
                      for p, x in jax.tree_util.tree_leaves_with_path(t)}
    assert flat(mine) == flat(theirs)
    cache = jax.eval_shape(lambda p: model.apply(
        {"params": p}, at, decode=True, positions=at,
        mutable=["cache"])[1]["cache"], mine)
    kinds = [sorted(str(p[-1].key) for p, _ in
                    jax.tree_util.tree_leaves_with_path(cache[f"layer_{i}"]))
             for i in range(5)]
    assert kinds == [["index_keys", "latent"], ["latent"], ["latent"],
                     ["latent"], ["index_keys", "latent"]]


# -- YaRN --------------------------------------------------------------------

def test_yarn_numbers_of_the_published_configuration():
    """Hand-computed from the equations for factor 32 over 4,096 original
    positions, theta 10000, d 64: cd(32) = 64 ln(4096 / 64 pi) / (2 ln
    1e4) = 10.47, cd(1) = 64 ln(4096 / 2 pi) / (2 ln 1e4) = 22.51."""
    with open(PUBLISHED) as f:
        cfg = G.config_from_source(json.load(f))
    assert G.yarn_correction_range(cfg.rope_scaling, 64, 10000.0) == (10, 23)
    f = np.asarray(G.rope_frequencies(cfg, 64), np.float64)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert f.shape == (32,)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)   # ramp 0
    np.testing.assert_allclose(f[23:], plain[23:] / 32, rtol=1e-6)  # ramp 1
    assert f[0] == 1.0
    np.testing.assert_allclose(f[31], 10000.0 ** (-62 / 64) / 32, rtol=1e-6)
    # half way up the ramp, pair 16: ramp 6/13
    np.testing.assert_allclose(
        f[16], plain[16] * (1 - 6 / 13) + plain[16] / 32 * (6 / 13),
        rtol=1e-6)
    assert G.yarn_mscale(32, 1) == pytest.approx(1 + 0.1 * np.log(32))
    assert G.yarn_mscale(32, 1) ** 2 == pytest.approx(1.8133, abs=5e-5)
    assert G.rope_magnitude(cfg) == 1.0
    assert G.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 1.81326,
                                                 rel=1e-5)
    # the reference computes the same numbers on its own
    sizes = REF.sizes(json.load(open(PUBLISHED)))
    assert REF.yarn_range(sizes, 64) == (10, 23)
    np.testing.assert_allclose(REF.rope_frequencies(sizes, 64), f, rtol=1e-6)
    assert REF.softmax_scale(sizes) == pytest.approx(G.softmax_scale(cfg))


def test_without_rope_scaling_the_frequencies_are_bit_for_bit_the_old():
    cfg = G.config_from_source(dict(TINY_SOURCE, rope_scaling=None))
    d = 8
    old = 10000.0 ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    np.testing.assert_array_equal(G.rope_frequencies(cfg, d), old)
    assert G.softmax_scale(cfg) == 16 ** -0.5
    assert G.rope_magnitude(cfg) == 1.0


# -- the router --------------------------------------------------------------

def _route_as_it_was(xs, w_g, bias, cfg):
    """``route()`` of the parent commit, statement for statement."""
    logits = jnp.einsum("nd,de->ne", xs.astype(jnp.float32),
                        w_g.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(s + bias[None, :], cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w * cfg.routed_scaling_factor


def _router_inputs(cfg, n=200):
    k = jax.random.PRNGKey(0)
    xs = jax.random.normal(k, (n, cfg.hidden_size))
    w_g = jax.random.normal(jax.random.fold_in(k, 1),
                            (cfg.hidden_size, cfg.router_experts)) * 0.3
    bias = 0.2 * jax.random.normal(jax.random.fold_in(k, 2),
                                   (cfg.router_experts,))
    return xs, w_g, bias


def test_one_group_routes_bit_for_bit_as_before():
    cfg = G.config_from_source(dict(TINY_SOURCE, n_group=1, topk_group=1))
    xs, w_g, bias = _router_inputs(cfg)
    got, want = G.route(xs, w_g, bias, cfg), _route_as_it_was(xs, w_g, bias,
                                                              cfg)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


@pytest.mark.parametrize("groups, kept, k", [(4, 2, 4), (8, 4, 8), (4, 1, 3)])
def test_group_limited_routing_against_a_plain_loop(groups, kept, k):
    experts = 16 if groups == 4 else 192
    cfg = G.config_from_source(dict(
        TINY_SOURCE, n_routed_experts_published=experts, n_group=groups,
        topk_group=kept, num_experts_per_tok=k))
    xs, w_g, bias = _router_inputs(cfg)
    ids, w = (np.asarray(a) for a in G.route(xs, w_g, bias, cfg))
    s = np.asarray(jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", xs, w_g, precision=jax.lax.Precision.HIGHEST)))
    per = experts // groups
    left_out = 0
    for n in range(xs.shape[0]):
        choice = s[n] + np.asarray(bias)
        score = [np.sort(choice[g * per:(g + 1) * per])[-2:].sum()
                 for g in range(groups)]
        best = sorted(range(groups), key=lambda g: -score[g])[:kept]
        allowed = [e for g in best for e in range(g * per, (g + 1) * per)]
        want = sorted(allowed, key=lambda e: -choice[e])[:k]
        assert sorted(ids[n]) == sorted(want), n
        # the groups mattered: an expert outside them would have been picked
        left_out += set(np.argsort(-choice)[:k]) != set(want)
        picked = s[n][ids[n]]                     # weighed by s, not s + b
        np.testing.assert_allclose(
            w[n], picked / picked.sum() * cfg.routed_scaling_factor,
            rtol=1e-5)
    assert left_out > 0.2 * xs.shape[0]
    # the reference's router, written on its own, picks the same
    sizes = REF.sizes(dict(
        TINY_SOURCE, n_routed_experts_published=experts, n_group=groups,
        topk_group=kept, num_experts_per_tok=k))
    r_ids, r_w = REF.router(xs, w_g, bias, sizes, "f32")
    np.testing.assert_array_equal(np.sort(r_ids, 1), np.sort(ids, 1))
    np.testing.assert_allclose(np.sort(r_w, 1), np.sort(w, 1), rtol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold four experts each of a 16-expert layer in 4 groups
    (each share across a group's edge). Each computes its held experts'
    part plus the shared expert (which every chip computes alike). The
    routed parts summed, the shared expert counted once, are the uncut
    reference's layer."""
    src = dict(TINY_SOURCE, n_routed_experts=16,
               experts_held=list(range(16)))
    sizes = REF.sizes(src)
    params = jax.jit(lambda k: REF.make_params(k, sizes))(
        jax.random.PRNGKey(11))
    p = params["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))
    whole = jax.vmap(lambda a: REF.moe_layer(a, p, sizes))(x)
    shared = jax.vmap(lambda a: REF.moe_layer(a, p, sizes)
                      - REF.moe_layer(a, p, sizes, shared=False))(x)
    shares = [[2, 3, 4, 5], [6, 7, 8, 9], [10, 11, 12, 13], [14, 15, 0, 1]]
    total = jnp.zeros_like(whole)
    for held in shares:
        cfg = G.config_from_source(dict(TINY_SOURCE, experts_held=held),
                                   compute_dtype=jnp.float32)
        mine = dict(p)
        for name in ("experts_gate", "experts_up", "experts_down"):
            mine[name] = {"kernel": p[name]["kernel"][jnp.asarray(held)]}
        total = total + G.SparseMoe(cfg).apply({"params": mine}, x)
    np.testing.assert_allclose(total - 3.0 * shared, whole, atol=TOL,
                               rtol=0)
    assert float(jnp.max(jnp.abs(total / 4 - whole))) > 1e-3


# -- the model against the reference -----------------------------------------

def test_full_forward_matches_the_reference(tiny):
    model, params, _, toks, want = tiny
    got = model.apply({"params": params}, toks)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_prefill_then_twelve_absorbed_decode_steps_match_the_full_pass(tiny):
    """Prefill expands keys and values and attends causally with no mask
    array; a decode step absorbs W_kvb and attends the row's whole cache
    row in place: both against the one plain (expanded, uncached)
    forward pass of the reference."""
    model, params, _, toks, want = tiny
    P = 13
    logits, state = model.apply(
        {"params": params}, toks[:1, :P], decode=True,
        positions=jnp.arange(P)[None], mutable=["cache"])
    np.testing.assert_allclose(logits, want[:1, :P], atol=TOL, rtol=0)
    cache = state["cache"]
    assert {str(p[-1].key) for p, _ in
            jax.tree_util.tree_leaves_with_path(cache)} == {"latent"}
    for t in range(P, P + 12):
        step, state = model.apply(
            {"params": params, "cache": cache}, toks[:1, t:t + 1],
            decode=True, positions=jnp.asarray([[t]]), mutable=["cache"])
        cache = state["cache"]
        np.testing.assert_allclose(step[:, 0], want[:1, t], atol=TOL,
                                   rtol=0)


@pytest.mark.parametrize("left_out", ["yarn_ramp", "mscale_factor",
                                      "group_mask"])
def test_a_mechanism_left_out_of_the_program_fails_the_comparison(
        tiny, monkeypatch, left_out):
    model, params, _, toks, want = tiny
    cfg = model.cfg
    if left_out == "yarn_ramp":
        monkeypatch.setattr(G, "rope_frequencies", lambda c, d: (
            c.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)))
    elif left_out == "mscale_factor":
        monkeypatch.setattr(G, "softmax_scale",
                            lambda c: c.qk_head_dim ** -0.5)
    else:
        model = G.GlmMoeDsaLM(dataclasses.replace(cfg, n_group=1,
                                                  topk_group=1))
    got = model.apply({"params": params}, toks)
    assert float(jnp.max(jnp.abs(got - want))) > 10 * TOL


def test_prefill_attend_without_a_mask_is_causal_attention(monkeypatch):
    """Several query and key blocks, the block sizes unequal: the blocks
    below the diagonal take no mask, the ones on it compare positions;
    equal to the same function under an explicit causal ``keep``."""
    monkeypatch.setattr(L, "ATTEND_BLOCK_Q", 16)
    monkeypatch.setattr(L, "ATTEND_BLOCK_K", 8)
    k = jax.random.PRNGKey(0)
    H, n, dq, dv = 2, 48, 8, 4
    q, kk = (jax.random.normal(jax.random.fold_in(k, i), (H, n, dq))
             for i in (0, 1))
    v = jax.random.normal(jax.random.fold_in(k, 2), (H, n, dv))
    got = L.prefill_attend(q, kk, v, None, 0.3)
    s = jnp.einsum("hqd,hkd->hqk", q, kk,
                   precision=jax.lax.Precision.HIGHEST) * 0.3
    causal = jnp.tril(jnp.ones((n, n), bool))
    want = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(
        jnp.where(causal, s, -jnp.inf), -1), v,
        precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(got, L.prefill_attend(q, kk, v, causal, 0.3),
                               atol=2e-6, rtol=0)


# -- the dense decode attend -------------------------------------------------

def _plain_attend(qa, qr, cache, pos, scale, rank):
    out = np.zeros(qa.shape, np.float32)
    qa, qr, cache = (np.asarray(a, np.float32) for a in (qa, qr, cache))
    for b, p in enumerate(np.asarray(pos)):
        if p == 0:
            continue                       # a free slot gives zeros
        c, kr = cache[b, :p + 1, :rank], cache[b, :p + 1,
                                               rank:rank + qr.shape[-1]]
        s = scale * (qa[b] @ c.T + qr[b] @ kr.T)
        w = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (w / w.sum(-1, keepdims=True)) @ c
    return out


def test_dense_attend_xla_form_and_kernel_against_a_plain_softmax(
        monkeypatch):
    """Rows at depth 0 (free slots, first, in the middle and last), at
    depth 1, just below, at and just past a block's edge, and at T - 1."""
    monkeypatch.setattr(L, "DENSE_BLOCK_T", 128)
    k = jax.random.PRNGKey(0)
    T, H, r, dr, C = 512, 8, 128, 64, 256
    pos = jnp.asarray([0, 1, 127, 0, 128, 129, T - 1, 0])
    B = pos.shape[0]
    qa = jax.random.normal(k, (B, H, r), jnp.bfloat16)
    qr = jax.random.normal(jax.random.fold_in(k, 1), (B, H, dr),
                           jnp.bfloat16)
    cache = jax.random.normal(jax.random.fold_in(k, 2), (B, T, C),
                              jnp.bfloat16)
    want = _plain_attend(qa, qr, cache, pos, 0.1, r)
    xla = L.decode_attend_dense(qa, qr, cache, pos, 0.1, r, dr)
    assert L.dense_attend_supported(qa, cache)
    kernel = L.dense_attend_kernel(qa, qr, cache, pos, 0.1, r,
                                   interpret=True)
    # bfloat16 probabilities against float32 ones, outputs of size ~1
    np.testing.assert_allclose(xla, want, atol=1e-2)
    np.testing.assert_allclose(kernel, want, atol=1e-2)
    assert not np.asarray(kernel)[np.asarray(pos) == 0].any()
    # float32 operands: the XLA form to summation order
    f32 = L._dense_attend_xla(*(a.astype(jnp.float32)
                                for a in (qa, qr, cache)), pos, 0.1, r)
    np.testing.assert_allclose(f32, want, atol=2e-5)


@pytest.mark.parametrize("pos", [
    [0, 1, 127, 0, 128, 129, 511, 0], [0, 0, 0, 5], [300, 0, 0, 7],
    [0, 0, 0, 0], [511, 511]])
def test_positions_visited_is_what_the_kernels_grid_visits(monkeypatch,
                                                           pos):
    """Walk the kernel's grid on the host with its own schedule and its
    own predicate: the blocks it computes on, and the blocks its index
    map makes it FETCH (a step whose block index equals the step before
    moves nothing), both cover ``dense_attend_visits`` positions, none
    of them in a free slot or past a row's depth."""
    monkeypatch.setattr(L, "DENSE_BLOCK_T", 128)
    T = 512
    bt = L.dense_attend_block(T)
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
    visits = int(L.dense_attend_visits(p, T))
    assert computed * bt == visits
    assert fetched * bt == (visits if any(pos) else bt)
    assert visits == sum((q // bt + 1) * bt for q in pos if q > 0)


# -- through the slot engine -------------------------------------------------

def test_engine_serves_the_dense_family_with_a_one_kind_cache(tiny):
    model, params, sizes, toks, _ = tiny
    eng = SlotDecodeEngine(model, params, 3, buckets=(16, 32))
    prompts = {0: np.asarray(toks[0, :19]), 2: np.asarray(toks[1, :11])}
    served = {s: [eng.prefill(p, s)] for s, p in prompts.items()}
    for _ in range(9):
        nxt = eng.step()
        for s in prompts:
            served[s].append(int(nxt[s]))
    for s, p in prompts.items():
        seq = np.concatenate([p, served[s]])[None]
        seq = jnp.asarray(np.pad(seq, ((0, 0), (0, 40 - seq.shape[1]))))
        gap, _ = REF.served_token_gaps(params, seq, "f32")
        got = np.asarray(gap[0, len(p) - 1:len(p) - 1 + len(served[s])])
        assert got.max() <= 1e-4, got
    stats = eng.model_stats()
    assert stats["cache_bytes_per_slot_by_kind"] == {
        "latent": 5 * 64 * 128 * 4}
    depths = sum(range(20, 29)) + sum(range(12, 21))
    assert stats["select_keys_available"] == depths
    assert stats["select_keys_kept"] == depths
    assert stats["index_keep_share"] == 1.0
    assert stats["decode_live_rows"] == 2 * 9
    # max_len 64 is one block: each live row's block, never the free slot's
    assert stats["attend_positions_visited"] == 2 * 9 * 64
    assert stats["moe_layers"] == 4
    assert sum(stats["moe_held_pairs_by_expert"]) == stats["moe_held_pairs"]


def test_cli_serves_under_the_second_name_and_refuses_alike(tmp_path):
    from tensorflow_distributed_tpu import cli
    from tensorflow_distributed_tpu.config import parse_args
    jsonl = tmp_path / "m.jsonl"
    src = tmp_path / "src.json"
    src.write_text(json.dumps({"nested": {"sizes": TINY_SOURCE}}))
    rc = cli.main([
        "--mode", "serve", "--model", "axk1", "--model-config",
        f"{src}#nested.sizes", "--compute-dtype", "float32",
        "--serve.num-requests", "5", "--serve.num-slots", "2",
        "--serve.max-new-tokens", "6", "--serve.prompt-len-min", "9",
        "--serve.prompt-len-max", "20", "--observe.metrics-jsonl",
        str(jsonl)])
    assert rc == 0
    recs = [json.loads(x) for x in jsonl.read_text().splitlines()]
    summary = [r for r in recs if r.get("event") == "serve_summary"][-1]
    assert summary["requests"] == 5
    assert summary["select_keys_kept"] == summary["select_keys_available"]
    assert summary["attend_positions_visited"] >= summary[
        "select_keys_available"]
    assert set(summary["cache_bytes_per_slot_by_kind"]) == {"latent"}
    # the run's start record: which form of the expanded attend each
    # prefill bucket's program traced (off the TPU the XLA loop)
    (start,) = [r for r in recs if r.get("event") == "start"]
    assert (start["model"], start["task"]) == ("axk1", "serve")
    plan = start["prefill_attend_plan"]
    assert plan and all(
        p["form"] == "xla" and 0 < p["tiles_computed"] <= p["tiles_total"]
        and int(b) % p["block_q"] == 0 for b, p in plan.items())
    for name in ("axk1", "glm_moe_dsa"):
        ok = ["--mode", "serve", "--model", name, "--model-config",
              str(src)]
        parse_args(ok)
        for bad in (["--mode", "train"], ["--serve.paged", "true"],
                    ["--serve.spec-tokens", "2"],
                    ["--serve.mesh-model", "2"],
                    ["--serve.kv-dtype", "int8"], ["--model-size", "tiny"]):
            with pytest.raises(ValueError):
                parse_args(ok + bad)
        with pytest.raises(ValueError, match="model-config"):
            parse_args(ok[:4])
