"""The glm_moe_dsa family (latent attention, learned sparse selection
with shared indices, sigmoid-routed dropless experts held as a share)
against its plain reference, at a small size on the CPU with seeded
weights.

The reference is the benchmark's (``perfbench/models/glm_moe_dsa.py``:
float32, ``highest``, no code of the package). The program runs with
``compute_dtype=float32`` here, on the same bfloat16-valued weights, so
both sides do the same arithmetic in another order: the tolerance 2e-5 on
logits of magnitude ~0.5 is summation order, nothing else. A discrete
choice (a selected key, a routed expert) that differed would move a
logit by 1e-2 or more.
"""

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

from tensorflow_distributed_tpu.models import glm_moe_dsa as G  # noqa: E402
from tensorflow_distributed_tpu.ops import latent_attention as L  # noqa: E402
from tensorflow_distributed_tpu.serve.engine import (  # noqa: E402
    SlotDecodeEngine)

TOL = 2e-5
REF = load_model("glm_moe_dsa", runner_kind="serve")

# Every mechanism at toy widths, under the source's key names: 16 experts
# of which 4 are held, prompts longer than index_topk select.
TINY_SOURCE = dict(
    vocab_size=96, hidden_size=32, num_attention_heads=4, q_lora_rank=16,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    index_n_heads=2, index_head_dim=16, index_topk=8,
    intermediate_size=64, moe_intermediate_size=16, n_routed_experts=4,
    n_routed_experts_published=16, experts_held=[1, 5, 6, 12],
    n_shared_experts=1, num_experts_per_tok=4, routed_scaling_factor=2.5,
    norm_topk_prob=True, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 10000.0},
    max_position_embeddings=64, num_hidden_layers=4,
    first_k_dense_replace=1,
    mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
    indexer_types=["full", "shared", "full", "shared"])


def build(seed=3, **over):
    src = dict(TINY_SOURCE)
    src.update(over)
    sizes = REF.sizes(src)
    model = G.GlmMoeDsaLM(G.config_from_source(
        src, compute_dtype=jnp.float32))
    params = jax.jit(lambda k: REF.make_params(k, sizes))(
        jax.random.PRNGKey(seed))
    return model, params, sizes


@pytest.fixture(scope="module")
def tiny():
    model, params, sizes = build()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 28), 0,
                              sizes["vocab_size"])
    return model, params, sizes, toks, REF.logits_fn(params, toks, sizes)


def test_layer_list_comes_from_the_sources_keys():
    cfg = G.config_from_source(dict(TINY_SOURCE))
    assert [(s.mlp, s.indexer) for s in cfg.layers] == [
        ("dense", "full"), ("sparse", "shared"), ("sparse", "full"),
        ("sparse", "shared")]
    src = dict(TINY_SOURCE, first_layer_held=1, num_hidden_layers=2,
               first_k_dense_replace=0)
    with pytest.raises(ValueError, match="first layer held"):
        G.config_from_source(src)        # starts on a 'shared' layer
    with pytest.raises(ValueError, match="experts_held"):
        G.config_from_source(dict(TINY_SOURCE, experts_held=[1, 5, 6, 99]))
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        G.config_from_source(dict(TINY_SOURCE, first_k_dense_replace=2))


def test_published_layers_2_to_6_of_the_benchmarks_configuration():
    import json
    with open(os.path.join(PERFBENCH, "configs", "glm-5.2-serve.json")) as f:
        cfg = G.config_from_source(json.load(f))
    assert [(s.indexer, s.mlp) for s in cfg.layers] == [
        ("full", "dense"), ("shared", "sparse"), ("shared", "sparse"),
        ("shared", "sparse"), ("full", "sparse")]
    assert (cfg.router_experts, len(cfg.experts_held),
            cfg.num_experts_per_tok) == (256, 8, 8)
    assert (cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.latent_dim, cfg.qk_head_dim, cfg.v_head_dim) == (
        6144, 2048, 512, 576, 256, 256)


def test_parameters_are_bfloat16_and_the_benchmarks_tree(tiny):
    model, params, _, _, _ = tiny
    init = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    mine = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x
            in jax.tree_util.tree_leaves_with_path(init)}
    theirs = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x
              in jax.tree_util.tree_leaves_with_path(params)}
    assert mine == theirs
    f32 = [k for k, (_, dt) in mine.items() if dt != jnp.bfloat16]
    assert f32 and all(k.endswith("['router_bias']") for k in f32)


def test_a_shared_layer_holds_no_indexer_and_reuses_the_selection(tiny):
    model, params, _, toks, _ = tiny
    assert "indexer" in params["layer_0"]["attn"]
    assert "indexer" not in params["layer_1"]["attn"]
    _, state = model.apply(
        {"params": params}, toks[:1], decode=True,
        positions=jnp.arange(toks.shape[1])[None], mutable=["cache"],
        capture_intermediates=lambda m, _: isinstance(m, G.LatentAttention))
    cache = state["cache"]
    assert set(cache["layer_0"]["attn"]) == {"latent", "indexer"}
    assert set(cache["layer_1"]["attn"]) == {"latent"}
    sel = [state["intermediates"][f"layer_{i}"]["attn"]["__call__"][0][1]
           for i in range(4)]
    assert sel[0].dtype == jnp.bool_ and sel[0].shape == (1, 28, 28)
    np.testing.assert_array_equal(sel[1], sel[0])
    np.testing.assert_array_equal(sel[3], sel[2])
    assert (np.asarray(sel[2]) != np.asarray(sel[0])).any()
    # index_topk is 8: row t keeps min(t + 1, 8) keys, all of them causal
    np.testing.assert_array_equal(np.asarray(sel[0][0]).sum(-1),
                                  np.minimum(np.arange(28) + 1, 8))
    assert not np.triu(np.asarray(sel[0][0]), 1).any()


def test_full_forward_matches_the_reference(tiny):
    model, params, _, toks, want = tiny
    got = model.apply({"params": params}, toks)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_prefill_then_absorbed_decode_match_the_references_full_forward(
        tiny):
    """Prefill expands keys and values, a decode step absorbs W_kvb and
    attends over the gathered selected rows of the latent cache: both
    against the one plain (expanded, uncached) forward pass."""
    model, params, _, toks, want = tiny
    P, T = 13, toks.shape[1]
    logits, state = model.apply(
        {"params": params}, toks[:1, :P], decode=True,
        positions=jnp.arange(P)[None], mutable=["cache"])
    np.testing.assert_allclose(logits, want[:1, :P], atol=TOL, rtol=0)
    only_last, _ = model.apply(
        {"params": params}, toks[:1, :P], decode=True,
        positions=jnp.arange(P)[None], mutable=["cache"],
        logits_at=jnp.asarray([P - 3]))
    np.testing.assert_allclose(only_last[:, 0], want[:1, P - 3], atol=TOL,
                               rtol=0)
    cache = state["cache"]
    for t in range(P, T):
        step, state = model.apply(
            {"params": params, "cache": cache}, toks[:1, t:t + 1],
            decode=True, positions=jnp.asarray([[t]]), mutable=["cache"])
        cache = state["cache"]
        np.testing.assert_allclose(step[:, 0], want[:1, t], atol=TOL,
                                   rtol=0)


def test_below_index_topk_the_sparse_result_is_dense_mla():
    """With index_topk at or above the context every causal key is kept:
    the indexer's weights cannot matter."""
    model, params, sizes = build(index_topk=64)
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 20), 0,
                              sizes["vocab_size"])
    base = model.apply({"params": params}, toks)
    scrambled = jax.tree_util.tree_map_with_path(
        lambda p, x: -3.0 * x if "indexer" in jax.tree_util.keystr(p)
        else x, params)
    np.testing.assert_array_equal(
        model.apply({"params": scrambled}, toks), base)
    np.testing.assert_allclose(base, REF.logits_fn(params, toks, sizes),
                               atol=TOL, rtol=0)
    # ... and with the selection active they do matter
    model8, params8, _ = build()
    scr8 = jax.tree_util.tree_map_with_path(
        lambda p, x: -3.0 * x if "indexer" in jax.tree_util.keystr(p)
        else x, params8)
    assert float(jnp.max(jnp.abs(
        model8.apply({"params": scr8}, toks)
        - model8.apply({"params": params8}, toks)))) > 1e-3


def test_router_picks_by_s_plus_b_and_weighs_by_s():
    cfg = G.config_from_source(dict(TINY_SOURCE))
    k = jax.random.PRNGKey(0)
    xs = jax.random.normal(k, (64, cfg.hidden_size))
    w_g = jax.random.normal(jax.random.fold_in(k, 1),
                            (cfg.hidden_size, cfg.router_experts)) * 0.3
    bias = jnp.zeros((cfg.router_experts,)).at[3].set(10.0).at[7].set(-10.0)
    ids, w = G.route(xs, w_g, bias, cfg)
    s = np.asarray(jax.nn.sigmoid(xs @ w_g))
    ids = np.asarray(ids)
    assert (ids == 3).any(axis=1).all()        # the bias picks expert 3
    assert not (ids == 7).any()                # ... and never expert 7
    picked = np.take_along_axis(s, ids, axis=1)
    want = picked / picked.sum(1, keepdims=True) * cfg.routed_scaling_factor
    np.testing.assert_allclose(w, want, rtol=1e-5)   # s alone, no bias
    np.testing.assert_allclose(np.asarray(w).sum(1),
                               cfg.routed_scaling_factor, rtol=1e-5)
    # the rest of each row is the top of s + b among the others
    order = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :4]
    assert (np.sort(order, 1) == np.sort(ids, 1)).all()


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold four experts each of a 16-expert layer. Each
    computes its held experts' part plus the shared expert (which every
    chip computes alike). The routed parts summed, the shared expert
    counted once, are the uncut reference's layer."""
    src = dict(TINY_SOURCE, n_routed_experts=16,
               experts_held=list(range(16)))
    sizes = REF.sizes(src)
    params = jax.jit(lambda k: REF.make_params(k, sizes))(
        jax.random.PRNGKey(11))
    p = params["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))
    whole = jax.vmap(lambda a: REF.moe_layer(a, p, sizes))(x)
    shared = jax.vmap(lambda a: REF._swiglu(
        a, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
        p["shared_down"]["kernel"], "f32"))(x)
    shares = [[0, 5, 9, 14], [1, 4, 10, 15], [2, 7, 8, 13], [3, 6, 11, 12]]
    total = jnp.zeros_like(whole)
    for held in shares:
        cfg = G.config_from_source(
            dict(TINY_SOURCE, experts_held=held),
            compute_dtype=jnp.float32)
        mine = dict(p)
        for name in ("experts_gate", "experts_up", "experts_down"):
            mine[name] = {"kernel": p[name]["kernel"][jnp.asarray(held)]}
        total = total + G.SparseMoe(cfg).apply({"params": mine}, x)
    np.testing.assert_allclose(total - 3.0 * shared, whole, atol=TOL,
                               rtol=0)
    # one share alone is NOT the layer: absent experts add nothing here
    assert float(jnp.max(jnp.abs(total / 4 - whole))) > 1e-3


@pytest.mark.parametrize("tokens", [32, 600])
def test_held_experts_is_dropless_whatever_the_routing(tokens):
    """Every pair on a held expert is computed: even when ALL pairs land
    here (the block loop then takes several trips) the result equals the
    loop over experts."""
    k = jax.random.PRNGKey(tokens)
    D, F, E, K = 16, 8, 4, 4
    xs = jax.random.normal(k, (tokens, D))
    gate, up = (jax.random.normal(jax.random.fold_in(k, i), (E, D, F)) * 0.2
                for i in (1, 2))
    down = jax.random.normal(jax.random.fold_in(k, 3), (E, F, D)) * 0.2
    weights = jax.random.uniform(jax.random.fold_in(k, 4), (tokens, K))
    for local in (
            jnp.tile(jnp.arange(K)[None], (tokens, 1)),      # all held
            jax.random.randint(jax.random.fold_in(k, 5), (tokens, K), -1,
                               E),                           # some absent
            jnp.full((tokens, K), -1)):                      # none held
        local = local.astype(jnp.int32)
        got = L.held_experts(xs, local, weights, gate, up, down,
                             jnp.float32, 0.25)
        want = jnp.zeros((tokens, D))
        for e in range(E):
            w_e = jnp.sum(jnp.where(local == e, weights, 0.0), -1)
            h = jax.nn.silu(xs @ gate[e]) * (xs @ up[e])
            want = want + w_e[:, None] * (h @ down[e])
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def _pairs_one_by_one(xs, local, weights, gate, up, down):
    """The plain form of ``held_experts``: every held (token, expert)
    pair on its own, in numpy float32."""
    xs, local, weights, gate, up, down = (
        np.asarray(a) for a in (xs, local, weights, gate, up, down))
    want = np.zeros(xs.shape, np.float32)
    for n, j in zip(*np.nonzero(local >= 0)):
        e = local[n, j]
        g, u = xs[n] @ gate[e], xs[n] @ up[e]
        want[n] += weights[n, j] * ((g / (1 + np.exp(-g)) * u) @ down[e])
    return want


# name: tokens, k, held, routed, D, F, routing ("random", "all", "none")
HELD_CASES = {
    "many_small_experts_half_landing": (300, 6, 12, 24, 16, 8, "random"),
    "few_wide_experts_quarter_landing": (300, 4, 4, 16, 16, 64, "random"),
    "every_pair_lands_here": (600, 4, 4, 16, 16, 8, "all"),
    "no_pair_lands_here": (300, 4, 4, 16, 16, 8, "none"),
    "one_hot_at_the_limit": (L.ONE_HOT_TOKENS, 6, 12, 24, 16, 8, "random"),
    "gathers_just_past_the_limit": (L.ONE_HOT_TOKENS + 1, 6, 12, 24, 16, 8,
                                    "random"),
    "a_decode_step": (16, 6, 12, 24, 16, 8, "random"),
}


@pytest.mark.parametrize("case", sorted(HELD_CASES))
def test_held_experts_under_its_plan_is_the_pairs_one_by_one(case):
    """Whatever blocks and tiles the plan makes of the shapes and the
    share it is given (many small experts, few wide ones, either side of
    the one-hot limit), and whatever the routing then sends here (the
    share, everything: the loop's further trips, nothing), the result is
    the held pairs computed one by one."""
    tokens, K, held, routed, D, F, routing = HELD_CASES[case]
    k = jax.random.PRNGKey(len(case))
    xs = jax.random.normal(k, (tokens, D))
    gate, up = (jax.random.normal(jax.random.fold_in(k, i),
                                  (held, D, F)) * 0.2 for i in (1, 2))
    down = jax.random.normal(jax.random.fold_in(k, 3), (held, F, D)) * 0.2
    weights = jax.random.uniform(jax.random.fold_in(k, 4), (tokens, K))
    if routing == "random":
        _, ids = jax.lax.top_k(jax.random.normal(
            jax.random.fold_in(k, 5), (tokens, routed)), K)
        local = jnp.where(ids < held, ids, -1)
    else:
        local = jnp.tile(jnp.arange(K)[None], (tokens, 1)) \
            if routing == "all" else jnp.full((tokens, K), -1)
    local = local.astype(jnp.int32)
    plan = L.moe_plan(tokens, K, held, D, F, held / routed)
    assert plan.one_hot == (tokens <= L.ONE_HOT_TOKENS)
    assert plan.combine_tile == D           # no lane tile: the one tile
    if routing == "all":
        assert plan.max_trips > plan.expected_trips == 1     # the loop runs on
    got = L.held_experts(xs, local, weights, gate, up, down, jnp.float32,
                         held / routed)
    np.testing.assert_allclose(
        got, _pairs_one_by_one(xs, local, weights, gate, up, down),
        atol=2e-5, rtol=0)


# The combine of the gathered branch (PR 44), at the routed
# configurations' (k, held, routed, D, F) cut to test sizes (the picked
# count and the share stand; D keeps whole lane tiles, since the kernel
# ``moe_combine_held`` runs here in interpret mode beside the
# scatter-add): name -> (k, held, routed, D, F, lanes the combine holds).
COMBINE_CONFIGS = {
    "granite": (10, 12, 24, 256, 16, 256),
    "axk1": (8, 8, 128, 256, 32, 128),          # two D tiles
    "glm": (8, 8, 256, 384, 32, 128),           # three
}
COMBINE_ROUTINGS = ("at_the_share", "none_held", "every_pair_held",
                    "all_on_one_expert", "one_token_holds_all_its_picks",
                    "held_to_a_block_edge", "held_to_a_row_tile_edge")
COMBINE_TOKENS = 288


def combine_routing(name, key, tokens, k, held, routed, plan):
    """``local [tokens, k]`` of one of :data:`COMBINE_ROUTINGS`."""
    pair = jnp.arange(tokens * k).reshape(tokens, k)
    spread = (pair // k + pair % k) % held      # a token's experts differ
    if name == "at_the_share":
        _, ids = jax.lax.top_k(jax.random.normal(key, (tokens, routed)), k)
        local = jnp.where(ids < held, ids, -1)
    elif name == "none_held":
        local = jnp.full((tokens, k), -1)
    elif name == "every_pair_held":             # all ``max_trips`` blocks
        local = spread
    elif name == "all_on_one_expert":           # one group over the blocks
        local = jnp.zeros((tokens, k))
    elif name == "one_token_holds_all_its_picks":
        local = jnp.where(pair // k == 7, spread, -1)
    else:                   # the held pairs end exactly on an edge
        edge = plan.block_rows if "block" in name else plan.tiles_in[0]
        assert 0 < edge < tokens * k
        local = jnp.where(pair < edge, spread, -1)
    return local.astype(jnp.int32)


def held_case(key, tokens, k, held, routed, D, F, routing, td,
              monkeypatch, gated=True):
    """Seeded operands of ``held_experts`` for one (configuration,
    routing), the plan's combine tile pinned to ``td`` lanes."""
    monkeypatch.setattr(L, "COMBINE_TILE", tokens * td)
    plan = L.moe_plan(tokens, k, held, D, F, held / routed)
    assert not plan.one_hot and plan.combine_tile == td
    xs = jax.random.normal(key, (tokens, D))
    gate, up = (jax.random.normal(jax.random.fold_in(key, i),
                                  (held, D, F)) * 0.2 for i in (1, 2))
    down = jax.random.normal(jax.random.fold_in(key, 3), (held, F, D)) * 0.2
    weights = jax.random.uniform(jax.random.fold_in(key, 4), (tokens, k))
    local = combine_routing(routing, jax.random.fold_in(key, 5), tokens, k,
                            held, routed, plan)
    return plan, xs, local, weights, gate if gated else None, up, down


@pytest.mark.parametrize("routing", COMBINE_ROUTINGS)
@pytest.mark.parametrize("config", sorted(COMBINE_CONFIGS))
def test_the_combine_walks_the_held_rows_whatever_the_routing(
        config, routing, monkeypatch):
    """The gathered branch under both forms of its combine, the kernel
    (interpret mode) and the scatter-add, is the held pairs computed one
    by one: nothing dropped when every pair lands here, nothing added
    when none does, the last row before a block's or a row tile's edge
    counted and the first after it not; and a second call gives the
    same bits."""
    k, held, routed, D, F, td = COMBINE_CONFIGS[config]
    plan, xs, local, weights, gate, up, down = held_case(
        jax.random.PRNGKey(len(config + routing)), COMBINE_TOKENS, k, held,
        routed, D, F, routing, td, monkeypatch)
    n_held = int(jnp.sum(local >= 0))
    if routing in ("every_pair_held", "all_on_one_expert"):
        assert n_held == COMBINE_TOKENS * k > plan.block_rows
        assert plan.max_trips > plan.expected_trips
    if routing == "held_to_a_block_edge":
        assert n_held == plan.block_rows
    if routing == "held_to_a_row_tile_edge":
        assert n_held == plan.tiles_in[0] < plan.block_rows
    want = _pairs_one_by_one(xs, local, weights, gate, up, down)
    assert (np.abs(want).max() > 0.1) == (n_held > 0)
    tol = 3e-6 * max(10.0, np.abs(want).max())      # float32 sums of 100s
    for kernel in (False, True):
        got = L.held_experts(xs, local, weights, gate, up, down,
                             jnp.float32, held / routed, kernel=kernel)
        np.testing.assert_allclose(got, want, atol=tol, rtol=0,
                                   err_msg=f"kernel={kernel}")
        again = L.held_experts(xs, local, weights, gate, up, down,
                               jnp.float32, held / routed, kernel=kernel)
        assert np.array_equal(np.asarray(got), np.asarray(again)), kernel


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["scatter_add", "moe_combine_held"])
def test_a_poisoned_token_of_the_gathered_branch_stays_alone(kernel,
                                                             monkeypatch):
    """A non-finite token's rows are added to its own row of the result
    and to no other: every other token comes out with the bits it has
    without the poison."""
    k, held, routed, D, F, td = COMBINE_CONFIGS["axk1"]
    _, xs, local, weights, gate, up, down = held_case(
        jax.random.PRNGKey(9), COMBINE_TOKENS, k, held, routed, D, F,
        "at_the_share", td, monkeypatch)
    local = local.at[5].set(jnp.arange(k))       # the row has held pairs
    clean = L.held_experts(xs, local, weights, gate, up, down, jnp.float32,
                           held / routed, kernel=kernel)
    got = L.held_experts(xs.at[5].set(jnp.nan), local, weights, gate, up,
                         down, jnp.float32, held / routed, kernel=kernel)
    others = np.arange(COMBINE_TOKENS) != 5
    assert not np.isfinite(np.asarray(got[5])).any()
    assert np.array_equal(np.asarray(got)[others], np.asarray(clean)[others])


@pytest.mark.parametrize("n_rows", [0, 1, 127, 128, 129, 384])
def test_the_combine_kernel_reads_no_row_past_the_held_ones(n_rows):
    """``moe_combine_held`` against the rows one by one with everything
    from ``n_rows`` on NaN (what the grouped matmul may leave there):
    a tile edge, one either side of it, nothing, the whole block."""
    N, D, M, tr, td = 40, 256, 384, 128, 128
    key = jax.random.PRNGKey(n_rows)
    y = jax.random.normal(key, (N, D))
    out = jax.random.normal(jax.random.fold_in(key, 1), (M, D))
    out = jnp.where(jnp.arange(M)[:, None] < n_rows, out, jnp.nan)
    tok = jax.random.randint(jax.random.fold_in(key, 2), (M,), 0, N)
    w = jax.random.uniform(jax.random.fold_in(key, 3), (M,))
    want = np.array(y)
    for r in range(n_rows):
        want[int(tok[r])] += np.float32(w[r]) * np.asarray(out[r])
    for kernel in (False, True):
        got = L.combine_held(y, out, tok, w, jnp.int32(n_rows), tr, td,
                             kernel)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=0,
                                   err_msg=f"kernel={kernel}")


@pytest.mark.parametrize("tokens", [16, 300])
def test_rows_the_grouped_matmul_leaves_unwritten_reach_no_token(
        tokens, monkeypatch):
    """megablox writes only the rows of its groups: what lies past them
    in a block is uninitialised memory on the chip. Here those rows are
    made NaN, on both branches: no token's result holds one."""
    K, held, routed, D, F = 4, 6, 12, 16, 8
    plain = L.grouped_matmul

    def leaves_rows_unwritten(lhs, rhs, sizes, tiles, kernel):
        out = plain(lhs, rhs, sizes, tiles, kernel)
        written = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
        return jnp.where(written[:, None], out, jnp.nan)

    monkeypatch.setattr(L, "grouped_matmul", leaves_rows_unwritten)
    k = jax.random.PRNGKey(tokens)
    xs = jax.random.normal(k, (tokens, D))
    gate, up = (jax.random.normal(jax.random.fold_in(k, i),
                                  (held, D, F)) * 0.2 for i in (1, 2))
    down = jax.random.normal(jax.random.fold_in(k, 3), (held, F, D)) * 0.2
    weights = jax.random.uniform(jax.random.fold_in(k, 4), (tokens, K))
    _, ids = jax.lax.top_k(jax.random.normal(
        jax.random.fold_in(k, 5), (tokens, routed)), K)
    local = jnp.where(ids < held, ids, -1).astype(jnp.int32)
    got = L.held_experts(xs, local, weights, gate, up, down, jnp.float32,
                         held / routed)
    np.testing.assert_allclose(
        got, _pairs_one_by_one(xs, local, weights, gate, up, down),
        atol=2e-5, rtol=0)


def test_a_poisoned_row_of_the_one_hot_moves_stays_alone():
    """The one-hot moves are matmuls over all rows: a non-finite row (a
    poisoned slot, whose router weights are non-finite too) comes out
    non-finite and leaves every other row what it is without it."""
    tokens, K, held, D, F = 16, 4, 6, 16, 8
    k = jax.random.PRNGKey(3)
    xs = jax.random.normal(k, (tokens, D))
    gate, up = (jax.random.normal(jax.random.fold_in(k, i),
                                  (held, D, F)) * 0.2 for i in (1, 2))
    down = jax.random.normal(jax.random.fold_in(k, 3), (held, F, D)) * 0.2
    weights = jax.random.uniform(jax.random.fold_in(k, 4), (tokens, K))
    local = jax.random.randint(jax.random.fold_in(k, 5), (tokens, K), -1,
                               held).astype(jnp.int32)
    local = local.at[5].set(jnp.arange(K))       # the row has held pairs
    clean = L.held_experts(xs, local, weights, gate, up, down, jnp.float32,
                           0.5)
    got = L.held_experts(xs.at[5].set(jnp.nan), local,
                         weights.at[5].set(jnp.nan), gate, up, down,
                         jnp.float32, 0.5)
    others = jnp.arange(tokens) != 5
    assert not bool(jnp.isfinite(got[5]).any())
    np.testing.assert_allclose(got[others], clean[others], atol=2e-6, rtol=0)


# What PERF.md section 3 states for the three routed configurations (the
# sweeps of PR 42 on the chip): per configuration the decode step's tiles
# (gate and up, down) and the prefills', and by bucket (rows of a block,
# expected trips, lanes of the result the combine holds).
PLANS = {
    "granite-4.0-h-small-serve": dict(
        decode=([128, 2048, 768], [128, 768, 2048]),
        prefill=([256, 2048, 768], [256, 768, 2048]),
        one_hot={"256": 2560},      # the shortest bucket: 128-row tiles
        buckets={"512": (3584, 1, 2048), "768": (5120, 1, 2048),
                 "1024": (6912, 1, 2048), "1536": (8192, 1, 2048),
                 "2048": (8192, 2, 2048), "3072": (8192, 2, 2048)}),
    "glm-5.2-serve": dict(
        decode=([128, 1536, 1024], [128, 1024, 1024]),
        prefill=([256, 1536, 1024], [256, 1024, 1024]), one_hot={},
        buckets={"3072": (1536, 1, 2048), "5120": (2560, 1, 1536),
                 "14336": (7168, 1, 512)}),
    "ax-k1-serve": dict(
        decode=([128, 1024, 1024], [128, 1024, 1024]),
        prefill=([256, 1024, 1024], [256, 1024, 1024]), one_hot={},
        buckets={"2048": (2048, 1, 1792), "4096": (4096, 1, 1792),
                 "8192": (7936, 1, 1024)}),
}


@pytest.mark.parametrize("name", sorted(PLANS))
def test_the_plan_of_a_benchmark_configuration_is_what_perf_md_states(name):
    """``moe_plan`` is a pure function of shapes: the three routed
    configurations under ``perfbench/configs/`` (read here, not edited)
    give the blocks and tiles ``PERF.md`` states, every tile divides its
    expert, and the decode step and every bucket are one expected trip
    but the two longest of granite's (capped by ``MAX_BLOCK_ROWS``)."""
    import json

    from tensorflow_distributed_tpu.models import build_model

    path = os.path.join(PERFBENCH, "configs", name + ".json")
    with open(path) as f:
        src = json.load(f)
    model = build_model(src["model"], source=path)
    buckets = [int(b) for b in src["serve"]["buckets"].split(",")]
    got = model.moe_plan(src["serve"]["num_slots"], buckets)
    want = PLANS[name]
    assert set(got) == {"decode"} | {str(b) for b in buckets}
    cfg = model.cfg
    assert got["decode"]["form"] == "one_hot"
    assert got["decode"]["block_rows"] == (
        src["serve"]["num_slots"] * cfg.num_experts_per_tok)
    assert (got["decode"]["tiles_in"],
            got["decode"]["tiles_out"]) == want["decode"]
    for b, rows in want["one_hot"].items():
        assert (got[b]["form"], got[b]["block_rows"], got[b]["tiles_in"],
                got[b]["tiles_out"]) == ("one_hot", rows) + want["decode"]
    for b, (rows, trips, lanes) in want["buckets"].items():
        p = got[b]
        assert (p["form"], p["block_rows"], p["expected_trips"],
                p["combine_tile"]) == ("gather", rows, trips, lanes), b
    D = cfg.hidden_size
    F = getattr(cfg, "moe_intermediate_size", 0) or cfg.intermediate_size
    for b in buckets:
        p = got[str(b)]
        if p["form"] == "gather":
            assert (p["tiles_in"], p["tiles_out"]) == want["prefill"], b
            assert p["block_rows"] <= L.MAX_BLOCK_ROWS
            assert p["max_trips"] * p["block_rows"] >= (
                b * cfg.num_experts_per_tok)                 # dropless
        (tm, tk, tn), (_, tk2, tn2) = p["tiles_in"], p["tiles_out"]
        assert p["block_rows"] % tm == 0 and D % tk == 0 and D % tn2 == 0
        assert F % tn == 0 and F % tk2 == 0     # no masked part tile
        assert tk * tn <= L.WEIGHT_BLOCK >= tk2 * tn2
        # the combine's [tokens, lanes] tile of the result: whole lane
        # tiles that divide D, 2,048 lanes and 32 MiB of float32 at most
        assert D % p["combine_tile"] == 0 == p["combine_tile"] % 128
        assert p["combine_tile"] <= L.COMBINE_LANES
        assert b * p["combine_tile"] <= max(L.COMBINE_TILE, 128 * b)


# The fourth routed configuration (PR 43): 128 of 512 ungated experts [1024,
# 2688] in a LATENT (the plan's D is the latent's 1,024, not the hidden
# size), 22 picked a token. Read, not tuned: what ``moe_plan`` gives at
# these shapes is recorded in PERF.md section 5 with what it costs.
NEMOTRON_PLAN = {
    "decode": ("one_hot", 2816, 1), "256": ("one_hot", 5632, 1),
    "512": ("gather", 4608, 1), "768": ("gather", 6784, 1),
    "1024": ("gather", 8192, 1), "1536": ("gather", 8192, 2),
    "2048": ("gather", 8192, 2), "3072": ("gather", 8192, 3),
    "4096": ("gather", 8192, 3)}


def test_the_plan_of_the_latent_configuration_is_what_perf_md_states():
    import json

    from tensorflow_distributed_tpu.models import build_model

    path = os.path.join(PERFBENCH, "configs", "nemotron-3-super-serve.json")
    with open(path) as f:
        src = json.load(f)
    model = build_model(src["model"], source=path)
    buckets = [int(b) for b in src["serve"]["buckets"].split(",")]
    slots = src["serve"]["num_slots"]
    got = model.moe_plan(slots, buckets)
    assert slots == 128 and set(got) == set(NEMOTRON_PLAN)
    for name, p in got.items():
        assert (p["form"], p["block_rows"], p["expected_trips"]
                ) == NEMOTRON_PLAN[name], name
        assert p["combine_tile"] == 1024, name      # the whole latent
        # 5.5 to 44 rows an expert: 128-row tiles; from the 1,536 bucket
        # on 66 and more: 256. K and N tiles divide the latent and 21 x 128
        tm = 256 if name != "decode" and int(name) >= 1536 else 128
        assert (p["tiles_in"], p["tiles_out"]) == (
            [tm, 1024, 896], [tm, 896, 1024]), name
        assert p["block_rows"] % tm == 0
        tokens = slots if name == "decode" else int(name)
        assert p["max_trips"] * p["block_rows"] >= tokens * 22   # dropless
    # a decode step of 128 rows: 2,816 pair rows for 704 held
    assert got["decode"]["block_rows"] == 128 * 22 == 22 * 128


@pytest.mark.parametrize("k", [4, 8, 10, 22])
def test_the_one_hot_block_of_any_slot_count_is_whole_row_tiles(k):
    """megablox takes whole row tiles: 96 slots x 22 picked are 16.5 tiles
    of 128, and ``grouped_matmul`` used to leave it for XLA's ragged dot
    without a word, on the TPU too. The one-hot block is rounded up (the
    rows past the pairs belong to no group); the three pinned plans'
    ``N k`` were whole tiles already and stay (the test above)."""
    for tokens in range(1, L.ONE_HOT_TOKENS + 1):
        plan = L.moe_plan(tokens, k, 128, 1024, 2688, 0.25)
        tm = plan.tiles_in[0]
        assert plan.one_hot and plan.max_trips == plan.expected_trips == 1
        assert plan.block_rows % tm == 0
        assert 0 <= plan.block_rows - tokens * k < tm
    assert L.moe_plan(96, 22, 128, 1024, 2688, 0.25).block_rows == 2176
    assert L.moe_plan(64, 10, 36, 4096, 768, 0.5).block_rows == 640


def test_the_sliced_head_is_the_rows_of_the_whole_head():
    """A sliced vocabulary is a smaller vocabulary: the logits of the
    slice are the whole model's logits at those rows."""
    model, params, sizes = build()
    toks = jax.random.randint(jax.random.PRNGKey(7), (1, 12), 0, 48)
    whole = model.apply({"params": params}, toks)
    cut = G.GlmMoeDsaLM(G.config_from_source(
        dict(TINY_SOURCE, vocab_size=48), compute_dtype=jnp.float32))
    sliced = dict(params, tok_emb=params["tok_emb"][:48],
                  lm_head={"kernel": params["lm_head"]["kernel"][:, :48]})
    np.testing.assert_array_equal(
        cut.apply({"params": sliced}, toks), whole[..., :48])


def test_exact_threshold_selection_is_top_k_with_its_ties():
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (40, 2, 16))
    keys = jax.random.normal(jax.random.fold_in(k, 1), (40, 16))
    # two heads with mixed-sign weights: many scores are exactly 0 (ties)
    w = jax.random.normal(jax.random.fold_in(k, 2), (40, 2))
    got = L.prefill_selection(q, keys, w, 8)
    want = REF.selection(q, keys, w, 8, "f32")
    np.testing.assert_array_equal(got, want)
    x = jnp.asarray([[3., -1., 0., 2., -jnp.inf, 5., -0.5, 1.]])
    kth = L.kth_largest_key(L._sort_key(x), 3)
    assert kth == L._sort_key(jnp.asarray([2.0]))


def test_pallas_decode_kernels_in_interpret_mode(monkeypatch):
    k = jax.random.PRNGKey(0)
    B, T, nh, dh = 3, 256, 8, 128
    q = jax.random.normal(k, (B, nh, dh), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(k, 1), (B, nh))
    keys = jax.random.normal(jax.random.fold_in(k, 2), (B, T, dh),
                             jnp.bfloat16)
    pos = jnp.asarray([5, 130, 255])
    monkeypatch.setattr(L, "INDEX_BLOCK_T", 128)
    assert L.index_scores_supported(q, keys)
    got = L.index_scores_kernel(q, w, keys, pos, interpret=True)
    s = jnp.einsum("bhd,btd->bht", q, keys,
                   preferred_element_type=jnp.float32)
    want = jnp.where(jnp.arange(T)[None] <= pos[:, None],
                     jnp.einsum("bht,bh->bt", jax.nn.relu(s), w), -jnp.inf)
    np.testing.assert_array_equal(np.isfinite(got), np.isfinite(want))
    np.testing.assert_allclose(np.where(np.isfinite(want), got, 0),
                               np.where(np.isfinite(want), want, 0),
                               atol=1e-4)
    H, r, dr, K = 8, 128, 64, 128
    qa = jax.random.normal(k, (B, H, r), jnp.bfloat16)
    qr = jax.random.normal(k, (B, H, dr), jnp.bfloat16)
    rows = jax.random.normal(jax.random.fold_in(k, 3), (B, K, r + dr),
                             jnp.bfloat16)
    valid = jnp.arange(K)[None] < jnp.asarray([K, 50, 1])[:, None]
    assert L.latent_attend_supported(qa, rows)
    got = L.latent_attend_kernel(qa, qr, rows, valid, 0.1, r,
                                 interpret=True)
    want = L._latent_attend_xla(qa, qr, rows, valid, 0.1, r)
    # bfloat16 probabilities on both sides, summed in another order
    np.testing.assert_allclose(got, want, atol=1e-2)


# -- the gather of the selected rows visits live slots only (PR 34) ----------

def _gather_case(depths, K=8, T=32, C=24, H=4, rank=16, seed=0):
    k = jax.random.PRNGKey(seed)
    B = len(depths)
    pos = jnp.asarray(depths, jnp.int32)
    cache = jax.random.normal(k, (B, T, C), jnp.float32) + 3.0   # no 0 row
    idx = jnp.stack([jax.random.permutation(jax.random.fold_in(k, b), T)[:K]
                     for b in range(B)]).astype(jnp.int32)
    valid = idx <= pos[:, None]
    qa = jax.random.normal(jax.random.fold_in(k, 100), (B, H, rank))
    qr = jax.random.normal(jax.random.fold_in(k, 101), (B, H, C - rank))
    return cache, idx, valid, pos, qa, qr, rank


@pytest.mark.parametrize("depths", [
    (0, 17, 0, 0, 31, 5), (9, 17, 3, 1, 31, 5), (0, 0, 0, 0, 0, 0)],
    ids=["live_and_free", "every_slot_live", "no_slot_live"])
def test_decode_attend_gathers_the_rows_of_live_slots_only(depths):
    """A live slot's rows, and with them its attend, are what the gather
    of every slot's rows gave, bit for bit; a free slot (depth 0) gets
    zeros and a finite result; the count is K a live slot."""
    cache, idx, valid, pos, qa, qr, rank = _gather_case(depths)
    live = np.asarray(pos) > 0
    K = idx.shape[1]
    every = jnp.take_along_axis(cache, idx[:, :, None], axis=1)
    rows = jax.jit(L.gather_live_rows)(cache, idx, pos)
    np.testing.assert_array_equal(rows[live], every[live])
    assert not np.asarray(rows[~live]).any()
    # what the loop moved IS what the program counts
    moved = int(np.asarray(rows).any(axis=-1).sum())
    assert moved == int(L.live_rows_gathered(pos, K)) == live.sum() * K
    order, n = L.live_slots(pos)
    assert int(n) == live.sum()
    assert list(np.asarray(order[:int(n)])) == list(np.flatnonzero(live))
    got = jax.jit(lambda *a: L.decode_attend(*a, 0.3, rank, qr.shape[-1]))(
        qa, qr, cache, idx, valid, pos)
    want = L._latent_attend_xla(qa, qr, every, valid, 0.3, rank)
    np.testing.assert_array_equal(got[live], want[live])
    assert np.isfinite(np.asarray(got)).all()


def test_a_nan_row_fails_a_live_slots_sensor_and_not_a_free_slots(tiny):
    """The slot_nan drill's premise, at the model: a live slot whose cache
    row holds NaN gathers it as before and its logits are non-finite; the
    same row under a FREE slot (depth 0) is not gathered and its logits are
    finite (the attend of every slot's rows multiplied a zero probability
    by it)."""
    model, params, _, toks, _ = tiny
    eng = _engine(model, params)
    prompts = {0: np.asarray(toks[0, :19]), 2: np.asarray(toks[1, :11])}
    first = {s: eng.prefill(p, s) for s, p in prompts.items()}
    cache = jax.tree_util.tree_map(lambda c: c.at[2].set(jnp.nan), eng.cache)
    tok = jnp.asarray([first[0], 0, first[2]], jnp.int32)[:, None]

    def ok(depths):
        logits, _ = model.apply(
            {"params": params, "cache": cache}, tok, decode=True,
            positions=jnp.asarray(depths, jnp.int32)[:, None],
            mutable=["cache"])
        return list(np.asarray(jnp.isfinite(logits).all(axis=(-1, -2))))

    assert ok([19, 0, 11]) == [True, True, False]
    assert ok([19, 0, 0]) == [True, True, True]


def test_rows_gathered_is_live_slots_by_topk_by_selecting_layers(tiny):
    """The counter the gather brings: ``index_topk`` rows a live slot in
    each of the model's four layers with a selection, whatever the slot
    count, named ``select_rows_gathered`` by ``summarize_stats``; with
    every live slot past ``index_topk`` it is the keys kept a layer."""
    model, params, _, toks, _ = tiny
    assert [s.indexer for s in model.cfg.layers] == [
        "full", "shared", "full", "shared"]
    eng = _engine(model, params, slots=5)
    _serve(eng, {1: np.asarray(toks[0, :19]), 3: np.asarray(toks[1, :11])},
           6)
    stats = eng.model_stats()
    assert stats["decode_live_rows"] == 2 * 6
    assert stats["select_rows_gathered"] == 2 * 6 * 8 * 4
    assert stats["select_rows_gathered"] == 4 * stats["select_keys_kept"]
    named = model.summarize_stats(
        {"live_rows": 3, "keys_available": 90, "keys_kept": 24,
         "rows_gathered": 96}, decode_steps=1)
    assert named["select_rows_gathered"] == 96
    # a program that counts none (a model without a selection) names none
    assert "select_rows_gathered" not in model.summarize_stats(
        {"live_rows": 3, "keys_available": 90, "keys_kept": 90},
        decode_steps=1)


# -- through the slot engine -------------------------------------------------

def _engine(model, params, slots=3):
    return SlotDecodeEngine(model, params, slots, buckets=(16, 32))


def _serve(eng, prompts, steps):
    out = {s: [eng.prefill(p, s)] for s, p in prompts.items()}
    for _ in range(steps):
        nxt = eng.step()
        for s in prompts:
            out[s].append(int(nxt[s]))
    return out


def test_engine_tokens_have_no_gap_in_the_reference(tiny):
    """Slots at different depths, prompts padded into buckets, rows
    inserted into the two-kind cache: every served token is the
    reference's own best when the reference is forced along the served
    sequence (a gap of 0 up to summation order)."""
    model, params, sizes, toks, _ = tiny
    eng = _engine(model, params)
    prompts = {0: np.asarray(toks[0, :19]), 2: np.asarray(toks[1, :11])}
    served = _serve(eng, prompts, 9)
    for s, p in prompts.items():
        seq = np.concatenate([p, served[s]])[None]
        seq = jnp.asarray(np.pad(seq, ((0, 0), (0, 40 - seq.shape[1]))))
        gap, top = REF.served_token_gaps(params, seq, "f32")
        got = np.asarray(gap[0, len(p) - 1:len(p) - 1 + len(served[s])])
        assert got.max() <= 1e-4, got
    stats = eng.model_stats()
    # a latent row (16 + 8 numbers) is stored in whole lane tiles
    assert model.cfg.latent_dim == 24 and model.cfg.latent_row == 128
    assert stats["cache_bytes_per_slot_by_kind"] == {
        "latent": 4 * 64 * 128 * 4, "index_keys": 2 * 64 * 16 * 4}
    assert sum(stats["cache_bytes_per_slot_by_kind"].values()) \
        == eng.cache_bytes_per_slot()
    # 2 live slots x 9 steps; depths 19.. and 11.., index_topk 8
    assert stats["select_keys_kept"] == 2 * 9 * 8
    assert stats["select_keys_available"] == sum(
        range(20, 29)) + sum(range(12, 21))
    assert stats["decode_live_rows"] == 2 * 9
    assert stats["moe_layers"] == 3
    assert sum(stats["moe_held_pairs_by_expert"]) == stats["moe_held_pairs"]
    assert 0 < stats["moe_held_pairs"] <= 2 * 9 * 3 * 4
    # a held expert is reached by at least one pair and at most by all of a
    # step's: at most 4 held a layer, never more than the pairs
    assert 0 < stats["moe_experts_hit"] <= min(stats["moe_held_pairs"],
                                               9 * 3 * 4)


def test_free_insert_and_quarantine_on_the_two_kind_cache(tiny):
    model, params, _, toks, _ = tiny
    clean, eng = _engine(model, params), _engine(model, params)
    prompts = {0: np.asarray(toks[0, :14]), 1: np.asarray(toks[1, :20])}
    want = _serve(clean, prompts, 6)
    got = {s: [eng.prefill(p, s)] for s, p in prompts.items()}
    for step in range(6):
        if step == 2:
            eng.poison_slot(1)           # NaN in BOTH kinds of leaf
        nxt = eng.step()
        if step == 2:
            assert eng.take_bad_slots() == [1]
            got[0].append(int(nxt[0]))
            # quarantine: free the slot, re-prefill what it had served
            eng.free(1)
            assert not eng.active[1] and eng.pos[1] == 0
            redo = np.concatenate([prompts[1], got[1]])
            got[1].append(eng.prefill(redo, 1))
            continue
        assert eng.take_bad_slots() == []
        for s in prompts:
            if eng.step_valid[s]:
                got[s].append(int(nxt[s]))
    assert got[0] == want[0]             # the neighbour never noticed
    # the re-prefilled row caught up, less the step that was in flight
    # when it came back (it holds no token of the new owner's)
    assert got[1] == want[1][:-1]
    leaves = jax.tree_util.tree_leaves(eng.cache)
    assert all(bool(jnp.isfinite(c).all()) for c in leaves)
    assert {c.shape[2] for c in leaves} == {128, 16}


def test_cli_serves_the_family_and_rejects_what_it_cannot(tmp_path):
    import json

    from tensorflow_distributed_tpu import cli
    from tensorflow_distributed_tpu.config import parse_args
    jsonl = tmp_path / "m.jsonl"
    src = tmp_path / "src.json"
    src.write_text(json.dumps({"nested": {"sizes": TINY_SOURCE}}))
    rc = cli.main([
        "--mode", "serve", "--model", "glm_moe_dsa", "--model-config",
        f"{src}#nested.sizes", "--compute-dtype", "float32",
        "--serve.num-requests", "5", "--serve.num-slots", "2",
        "--serve.max-new-tokens", "6", "--serve.prompt-len-min", "9",
        "--serve.prompt-len-max", "20", "--observe.metrics-jsonl",
        str(jsonl)])
    assert rc == 0
    recs = [json.loads(x) for x in jsonl.read_text().splitlines()]
    summary = [r for r in recs if r.get("event") == "serve_summary"][-1]
    assert summary["requests"] == 5
    assert 0 < summary["index_keep_share"] < 1
    assert set(summary["cache_bytes_per_slot_by_kind"]) == {
        "latent", "index_keys"}
    # 2 slots x 4 picked = 8 pairs, in one whole row tile
    assert summary["moe_plan"]["decode"]["block_rows"] == 128
    assert summary["moe_plan"]["decode"]["expected_trips"] == 1
    ok = ["--mode", "serve", "--model", "glm_moe_dsa", "--model-config",
          str(src)]
    parse_args(ok)
    for bad in (["--mode", "train"], ["--serve.paged", "true"],
                ["--serve.spec-tokens", "2"], ["--serve.kv-dtype", "int8"],
                ["--model-size", "tiny"]):
        with pytest.raises(ValueError):
            parse_args(ok + bad)
    # no preset: a run that forgets the flag fails, it serves no toy
    with pytest.raises(ValueError, match="model-config"):
        parse_args(ok[:4])
    with pytest.raises(ValueError, match="model-config"):
        G.glm_moe_dsa_lm()
    with pytest.raises(ValueError, match="model_config"):
        parse_args(["--model", "gpt_lm", "--model-config", str(src)])
