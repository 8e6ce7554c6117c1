"""The ``jamba`` family (models/jamba.py; the attention mixer, the
convolution and the state's stamp it shares with models/granitemoehybrid
.py; ``ops/state_space.py::s6_chunk_scan`` and ``s6_state_step``;
``gqa_dense_attend`` at one key-value head under 20 queries) against its
plain reference (perfbench/models/jamba.py), at the benchmark
configuration's REHEARSAL sizes on the CPU, seeded weights.

What is held: prefill then decode through state, ring and cache gives the
reference's full forward pass (logits; float32 compute at a tolerance that
bfloat16 fails, bfloat16 at one that fp8 fails), from a prompt shorter than
the convolution, one that ends mid-chunk and one that fills its bucket;
each mechanism changed in the reference or broken in the program shows; a
step the engine drops and computes again, and a slot freed and used again,
leave tokens, logits and cache as an undisturbed run does; the scan kernel
(interpret mode) is the ``lax.scan`` form and the reference's recurrence
with ``true_len`` inside, at and past a chunk's edge, and its last state is
the state the step continues from; the step kernel (interpret mode) is one
turn of the recurrence and leaves free slots untouched bit for bit;
``gqa_dense_attend`` with 20 queries on one key-value head (interpret mode)
is ``dense_decode_attend`` and a plain softmax; the head is the table;
``config.py`` refuses what is not implemented, by name; the counters are
the counts made by hand; the family runs through ``cli.main``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_distributed_tpu.models import jamba as M
from tensorflow_distributed_tpu.models.generate import (
    decode_token, prefill_cache)
from tensorflow_distributed_tpu.ops import hybrid_attention as H
from tensorflow_distributed_tpu.ops import state_space as S

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "perfbench", "configs", "jamba2-3b-serve.json")
MAX_LEN = 192
# float32 compute against the float32 reference: both sum the same products
# in float32 and differ by the order of the sums (measured 5e-6 on logits of
# deviation 0.9). bfloat16 operands read 3e-2.
TOL_F32 = 5e-5
# bfloat16 operands, float32 accumulation, against the float32 reference,
# the MEDIAN over positions of a position's largest logit error. Measured
# 0.02; the reference at fp8 reads 0.3.
TOL_BF16 = 6e-2


def _reference():
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    try:
        from harness.loader import load_model
        return load_model("jamba")
    finally:
        sys.path.remove(os.path.join(ROOT, "perfbench"))


def _controls():
    spec = importlib.util.spec_from_file_location(
        "jamba_controls",
        os.path.join(ROOT, "perfbench", "tools", "jamba_controls.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


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
        jax.random.key(50))


def _model(src, dtype=jnp.float32):
    return M.JambaLM(M.config_from_source(src, compute_dtype=dtype))


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
    """``make_params`` gives the program's tree leaf for leaf (paths,
    shapes, dtypes): matrices bfloat16; ``A_log``, ``D``, ``dt``'s bias and
    every norm's scale float32; one table and no head."""
    sizes, params = weights
    model = _model(src)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    mine = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in
            jax.tree_util.tree_leaves_with_path(params)}
    theirs = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in
              jax.tree_util.tree_leaves_with_path(shapes)}
    assert mine == theirs
    assert sizes["layers"] == ("mamba", "mamba", "attention", "mamba") * 2
    assert model.cfg.layers == sizes["layers"]
    f32 = {k for k, (_, d) in mine.items() if d == jnp.float32}
    assert all(k.endswith(("['scale']", "['A_log']", "['D']['value']",
                           "['dt_bias']['value']")) for k in f32)
    assert len(f32) == 6 * 6 + 2 * 8 + 1
    assert "['lm_head']['kernel']" not in mine
    a_log = np.asarray(params["layer_0"]["mixer"]["A_log"])
    np.testing.assert_allclose(a_log[:, 0], np.log(np.arange(1, 5)),
                               rtol=1e-6)
    dt0 = np.asarray(jax.nn.softplus(
        params["layer_0"]["mixer"]["dt_bias"]["value"]))
    assert 0.001 <= dt0.min() and dt0.max() <= 0.1 + 1e-6
    assert ref.param_count(sizes) == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


@pytest.mark.parametrize("prompt,new,bucket,dtype,tol", [
    (2, 24, 64, jnp.float32, TOL_F32),        # shorter than the convolution
    (37, 40, 64, jnp.float32, TOL_F32),       # ends inside a chunk
    (64, 12, 64, jnp.float32, TOL_F32),       # fills its bucket
    (37, 24, 128, jnp.bfloat16, TOL_BF16),
], ids=["prompt2", "prompt37", "prompt64", "bf16"])
def test_prefill_then_decode_is_the_references_forward_pass(
        ref, src, weights, prompt, new, bucket, dtype, tol):
    sizes, params = weights
    toks = _tokens(prompt + new, seed=prompt, rows=2)
    want = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes))
    got, cache = _through_the_cache(_model(src, dtype), params, toks,
                                    prompt, bucket)
    err = np.abs(got - want[:, prompt - 1:]).max(axis=(0, 2))
    if dtype == jnp.float32:
        assert err.max() < tol, err.max()
        # float32 compute tells bfloat16 from itself: the same comparison
        # at bfloat16 would fail this tolerance
        assert np.abs(want).max() > 1.0
    else:
        assert np.median(err) < tol, np.median(err)
        low = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes,
                                       "fp8"))
        assert np.median(np.abs(low - want).max(axis=(0, 2))) > 3 * tol
    # the stamp says what the states hold
    assert list(np.asarray(cache["state_pos"])) == [prompt + new] * 2


def test_the_forward_pass_without_a_cache_agrees_too(ref, src, weights):
    sizes, params = weights
    toks = _tokens(48, seed=3, rows=2)
    got = _model(src).apply({"params": params}, jnp.asarray(toks))
    want = ref.logits_fn(params, jnp.asarray(toks), sizes)
    assert float(jnp.max(jnp.abs(got - want))) < TOL_F32


def test_the_head_is_the_table(src, weights):
    """The logits are the final-normed features times the table the
    tokens were embedded with; scaling one row of the table moves that
    token's logit and its embedding alike."""
    _, params = weights
    model = _model(src)
    toks = jnp.asarray(_tokens(12, seed=4))
    base = model.apply({"params": params}, toks)
    emb = params["tok_emb"].astype(jnp.float32)
    bumped = dict(params, tok_emb=emb.at[95].multiply(2.0).astype(
        params["tok_emb"].dtype))
    assert 95 not in np.asarray(toks)
    got = model.apply({"params": bumped}, toks)
    np.testing.assert_allclose(np.asarray(got[..., 95]),
                               2 * np.asarray(base[..., 95]), rtol=2e-2,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(got[..., :95]),
                               np.asarray(base[..., :95]), atol=1e-5)


@pytest.mark.parametrize("control", ["no_bc_norm", "no_attention", "fp8"])
def test_the_reference_with_a_mechanism_changed_is_another_model(
        ref, src, weights, control):
    sizes, params = weights
    assert set(ref.CONTROLS) == {"no_bc_norm", "no_attention"}
    toks = jnp.asarray(_tokens(64, seed=5, rows=2))
    want = ref.logits_fn(params, toks, sizes)
    if control in ref.CONTROLS:
        got = ref.logits_fn(params, toks, sizes, "f32", control)
    else:
        got = ref.logits_fn(params, toks, sizes, control)
    assert float(jnp.median(jnp.max(jnp.abs(got - want), axis=-1))) > 0.1


@pytest.mark.parametrize("how", ["state_zeroed", "state_at_bucket_end",
                                 "ring_one_tap_off"])
def test_a_mechanism_broken_in_the_program_shows(ref, src, weights, how):
    sizes, params = weights
    controls = _controls()
    assert set(controls.BREAKS) == {"state_zeroed", "state_at_bucket_end",
                                    "ring_one_tap_off"}
    toks = _tokens(37 + 16, seed=6, rows=2)
    want = np.asarray(ref.logits_fn(params, jnp.asarray(toks), sizes))
    with controls.broken(how):
        got, _ = _through_the_cache(_model(src), params, toks, 37, 64)
    err = np.abs(got - want[:, 36:]).max(axis=(0, 2))
    # the prefill's own logits are sound under all three (the scan's y,
    # the bucket's convolution); the steps after it are not
    assert err[0] < TOL_F32
    assert np.median(err[1:]) > 1e-2, np.median(err[1:])
    sound, _ = _through_the_cache(_model(src), params, toks, 37, 64)
    assert np.abs(sound - want[:, 36:]).max() < TOL_F32


# -- the two kernels ---------------------------------------------------------

def _scan_inputs(L, C=256, N=8, rows=2, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    return dict(
        x=jax.random.normal(k[0], (rows, L, C)).astype(jnp.bfloat16),
        dt=jax.nn.softplus(jax.random.normal(k[1], (rows, L, C)) - 2.0),
        A=-jnp.exp(0.5 * jax.random.normal(k[2], (N, C))),
        Bm=jax.random.normal(k[3], (rows, L, N)),
        Cm=jax.random.normal(k[4], (rows, L, N)),
        D=jax.random.normal(k[5], (C,)))


@pytest.mark.parametrize("true_len", [(1, 96), (31, 32), (32, 33),
                                      (50, 64), (96, 7)],
                         ids=["first", "edge_before", "edge_at", "inside",
                              "whole"])
def test_the_scan_kernel_is_the_lax_scan_and_the_references_recurrence(
        ref, monkeypatch, true_len):
    """Chunks of 32 positions and blocks of 128 channels over 96 positions
    of 256 channels: ``true_len`` inside, at and one past a chunk's edge,
    at the first position and at the bucket's end."""
    monkeypatch.setattr(S, "S6_CHUNK", 32)
    monkeypatch.setattr(S, "S6_CHANNELS", 128)
    a = _scan_inputs(96)
    n = jnp.asarray(true_len)
    assert S.s6_scan_supported(a["x"], 8)
    y_k, h_k = S.s6_chunk_scan(*a.values(), n, interpret=True)
    y_x, h_x = S.s6_chunk_scan(*a.values(), n)
    for row, t in enumerate(true_len):
        x = a["x"][row, :t].astype(jnp.float32)
        y_r, h_r = ref.selective_scan(x, a["dt"][row, :t], a["A"].T,
                                      a["Bm"][row, :t], a["Cm"][row, :t])
        y_r = y_r + a["D"] * x
        for y, h in ((y_k, h_k), (y_x, h_x)):
            np.testing.assert_allclose(np.asarray(y[row, :t]),
                                       np.asarray(y_r), atol=2e-5)
            np.testing.assert_allclose(np.asarray(h[row]),
                                       np.asarray(h_r.T), atol=2e-5)
    # whole chunks of padding are not computed: the kernel leaves zeros
    for row, t in enumerate(true_len):
        first_free = -(-t // 32) * 32
        assert not np.asarray(y_k[row, first_free:]).any()


def test_the_scans_last_state_is_what_the_step_continues_from(
        ref, monkeypatch):
    """The state the scan leaves at ``true_len`` under a padded bucket,
    stepped once with the next token, is the state of a scan one token
    longer; a row that does not fold only reads; a free row is neither
    read nor written, bit for bit, in both forms."""
    monkeypatch.setattr(S, "S6_CHUNK", 32)
    monkeypatch.setattr(S, "S6_CHANNELS", 128)
    monkeypatch.setattr(S, "S6_STEP_LANES", 128)
    a = _scan_inputs(64, rows=3, seed=1)
    n = jnp.asarray([40, 40, 40])
    _, h = S.s6_chunk_scan(*a.values(), n, interpret=True)
    pos = jnp.asarray([40, 40, 0])                 # row 2 is a free slot
    fold = jnp.asarray([True, False, False])       # row 1 is computed again
    args = (a["x"][:, 40].astype(jnp.float32), a["dt"][:, 40], a["A"],
            a["Bm"][:, 40], a["Cm"][:, 40], a["D"], fold, pos)
    assert S.s6_step_supported(h)
    y_next, h_next = S.s6_chunk_scan(*a.values(), n + 1)
    for interpret in (True, None):
        S1, y1 = S.s6_state_step(h, *args, interpret=interpret)
        np.testing.assert_allclose(np.asarray(S1[0]),
                                   np.asarray(h_next[0]), atol=2e-5)
        np.testing.assert_allclose(np.asarray(y1[0]),
                                   np.asarray(y_next[0, 40]), atol=2e-5)
        # one turn of the reference's recurrence from the same state
        y_r, h_r = ref.selective_scan(
            args[0][0][None], args[1][0][None], a["A"].T,
            args[3][0][None], args[4][0][None], h0=h[0].T)
        np.testing.assert_allclose(np.asarray(S1[0]), np.asarray(h_r.T),
                                   atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(y1[0]), np.asarray(y_r[0] + a["D"] * args[0][0]),
            atol=1e-5)
        np.testing.assert_array_equal(np.asarray(S1[1]), np.asarray(h[1]))
        assert np.abs(np.asarray(y1[1])).max() > 0
        np.testing.assert_array_equal(np.asarray(S1[2]), np.asarray(h[2]))
        assert not np.asarray(y1[2]).any()


def test_what_the_kernels_take():
    x = jnp.zeros((1, 512, 5120), jnp.bfloat16)
    assert S.s6_scan_supported(x, 16)
    assert S._s6_blocks(8192, 5120) == (S.S6_CHUNK, S.S6_CHANNELS)
    assert 5120 % S.S6_CHANNELS == 0 and S.S6_CHANNELS % 128 == 0
    assert S.S6_CHUNK % S.S6_GROUP == 0
    assert S.s6_step_supported(jnp.zeros((4, 16, 5120), jnp.float32))
    # the rehearsal's widths go through the XLA forms
    assert not S.s6_scan_supported(jnp.zeros((1, 512, 96), jnp.bfloat16), 4)
    assert not S.s6_step_supported(jnp.zeros((4, 4, 96), jnp.float32))
    assert not S.s6_step_supported(jnp.zeros((4, 16, 5120), jnp.bfloat16))


def _plain_attend(q, kv, pos, scale):
    B, G, h, d = q.shape
    out = np.zeros((B, G, h, d), np.float32)
    q, kv = np.asarray(q, np.float32), np.asarray(kv, np.float32)
    for b in range(B):
        if pos[b] == 0:
            continue
        for g in range(G):
            k = kv[b, :pos[b] + 1, g * d:(g + 1) * d]
            v = kv[b, :pos[b] + 1, (G + g) * d:(G + g + 1) * d]
            s = q[b, g] @ k.T * scale
            p = np.exp(s - s.max(-1, keepdims=True))
            out[b, g] = (p / p.sum(-1, keepdims=True)) @ v
    return out


def test_gqa_dense_attend_with_twenty_queries_on_one_key_value_head(
        monkeypatch):
    """Multi-query attention as the published model has it: 20 queries on
    one key-value head pad to 24 rows of one product, and the padding is
    dropped; free slots give zeros; rows one past a block's edge."""
    monkeypatch.setattr(H, "GQA_BLOCK_T", 64)
    B, G, h, d, T = 5, 1, 20, 128, 256
    k = jax.random.split(jax.random.key(7), 2)
    q = jax.random.normal(k[0], (B, G, h, d)).astype(jnp.bfloat16)
    kv = jax.random.normal(k[1], (B, T, 2 * G * d)).astype(jnp.bfloat16)
    pos = np.asarray([63, 0, 64, 255, 1])
    assert H.gqa_attend_supported(q, kv)
    scale = d ** -0.5
    got = H.gqa_decode_attend(q, kv, jnp.asarray(pos), scale,
                              interpret=True)
    assert got.shape == (B, G, h, d)
    xla = H.gqa_decode_attend(q, kv, jnp.asarray(pos), scale)
    want = _plain_attend(q, kv, pos, scale)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(xla), atol=2e-2)
    assert not np.asarray(got[1]).any()


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


def test_a_step_dropped_and_computed_again_folds_its_token_once(served):
    model, params, engine = served
    prompt = _tokens(70, seed=9)[0]
    calm, jumpy = engine(), engine()
    for eng in (calm, jumpy):
        eng.prefill(prompt, 0)
    got = {id(calm): [], id(jumpy): []}
    for i in range(21):
        for eng in (calm, jumpy):
            nxt = eng.step()
            assert eng.step_valid[0]
            got[id(eng)].append(int(nxt[0]))
        if i % 2 == 0:
            assert jumpy._ahead is not None
            jumpy.drain()
    assert got[id(jumpy)] == got[id(calm)]
    assert jumpy.ahead_rows_dropped >= 10
    for x, y in zip(_cache_of(calm, 0), _cache_of(jumpy, 0)):
        np.testing.assert_allclose(x, y, atol=1e-6)
    np.testing.assert_allclose(_logits_now(model, params, calm, 0),
                               _logits_now(model, params, jumpy, 0),
                               atol=1e-5)
    stats = jumpy.model_stats()
    assert stats["state_rows_reread"] > 0
    assert stats["state_rows_folded"] + stats["state_rows_reread"] \
        == stats["state_rows_stepped"]


def test_a_slot_freed_and_used_again_reads_nothing_of_its_last_tenant(
        served):
    """The next tenant's prompt is SHORTER than the convolution: its ring
    holds zeros before the sequence, not the last tenant's rows, and its
    states are its own."""
    model, params, engine = served
    first, second, other = (_tokens(n, seed=s)[0] for n, s in (
        (90, 10), (2, 11), (75, 12)))
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
    for _ in range(24):
        nxt = busy.step()
        if busy.step_valid[1]:
            got.append(int(nxt[1]))
    for _ in range(len(got)):
        nxt = calm.step()
        assert calm.step_valid[1]
        want.append(int(nxt[1]))
    assert got == want and len(got) >= 23


def test_the_scheduler_serves_one_shot_greedy_tokens(served):
    from tensorflow_distributed_tpu.models.generate import generate
    from tensorflow_distributed_tpu.serve.scheduler import (
        Request, Scheduler)
    model, params, engine = served
    reqs = [Request(rid=i, prompt=_tokens(n, seed=20 + i)[0],
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(70, 20), (2, 24), (64, 18),
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
    assert stats["state_rows_stepped"] == 6 * live
    assert stats["state_rows_folded"] == 6 * live
    assert stats["state_rows_reread"] == 0
    inner = 96
    assert stats["cache_bytes_per_slot_by_kind"] == {
        "state": 6 * 4 * inner * 4, "conv": 6 * 4 * inner * 4,  # float32 here
        "kv": 2 * MAX_LEN * 2 * 16 * 4, "state_pos": 4}
    assert stats["state_bytes_per_slot"] == 6 * 4 * inner * 4
    assert stats["conv_bytes_per_slot"] == 6 * 4 * inner * 4
    # slot 0 at depths 70..76, slot 2 at 9..10: causal positions a layer
    depths = list(range(71, 78)) + [10, 11]
    assert stats["attend_keys"] == sum(depths)
    assert stats["select_keys_kept"] == stats["full_attend_keys"] \
        == 2 * sum(depths)
    # the kernel's blocks of 64 to each live row's depth, two layers
    assert stats["attend_positions_visited"] == 2 * sum(
        (p - 1) // 64 * 64 + 64 for p in depths)
    # what the prefills' scans were handed: buckets of 128 and 64, of which
    # 70 and 9 positions were prompt, six layers
    assert stats["s6_scan_positions"] == 6 * (128 + 64)
    assert stats["s6_scan_positions_live"] == 6 * (70 + 9)
    assert "moe_plan" not in stats and "moe_layers" not in stats


# -- the configuration and what config.py refuses ----------------------------

def test_the_layer_list_is_the_periods_and_a_routed_jamba_is_refused(src):
    whole = M.load_source(CONFIG)
    cfg = M.config_from_source(whole)
    assert [i for i, k in enumerate(cfg.layers) if k == "attention"] \
        == [7, 21]
    assert (cfg.n_mamba, cfg.n_attention, cfg.mamba_inner, cfg.head_dim) \
        == (26, 2, 5120, 128)
    assert cfg.attention_multiplier == 128 ** -0.5
    assert cfg.state_bytes_per_slot == 26 * 16 * 5120 * 4
    assert cfg.conv_bytes_per_slot == 26 * 4 * 5120 * 2
    with pytest.raises(ValueError, match="num_experts 16.*routed Jamba"):
        M.config_from_source(dict(src, num_experts=16))
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        M.config_from_source(dict(src, tie_word_embeddings=False))
    with pytest.raises(ValueError, match="sliding_window"):
        M.config_from_source(dict(src, sliding_window=4096))
    with pytest.raises(ValueError, match="attn_layer_offset"):
        M.config_from_source(dict(src, attn_layer_offset=4))
    with pytest.raises(ValueError, match="has no training path"):
        _model(src).apply({"params": {}}, jnp.zeros((1, 4), jnp.int32),
                          train=True)


def _cfg(**kw):
    from tensorflow_distributed_tpu.config import TrainConfig
    cfg = TrainConfig(model="jamba", mode="serve", model_config=CONFIG)
    for k, v in kw.items():
        obj, *rest = k.split("__")
        if rest:
            setattr(getattr(cfg, obj), rest[0], v)
        else:
            setattr(cfg, obj, v)
    return cfg


@pytest.mark.parametrize("kw,message", [
    ({"mode": "train"}, "the jamba family has no training path"),
    ({"model_config": ""}, "takes its sizes from --model-config"),
    ({"model_size": "tiny"}, "no --model-size preset"),
    ({"serve__paged": True}, "no paging over a state or a ring"),
    ({"serve__spec_tokens": 2}, "cannot roll a state back"),
    ({"serve__mesh_model": 2}, "served whole on one chip"),
    ({"kv_cache_quant": "int8"}, "int8 KV cache"),
    ({"serve__kv_dtype": "int8"}, "int8 KV cache"),
], ids=["train", "no_config", "preset", "paged", "spec", "mesh_model",
        "int8", "kv_int8"])
def test_config_refuses_by_name(kw, message):
    from tensorflow_distributed_tpu.config import SOURCE_CONFIG_FAMILIES
    with pytest.raises(ValueError, match=message) as err:
        _cfg(**kw).validate()
    family, untrained, cache = SOURCE_CONFIG_FAMILIES["jamba"]
    assert family in str(err.value) or str(err.value) == cache


def test_config_takes_the_family_and_the_registry_builds_it():
    from tensorflow_distributed_tpu.config import (
        SOURCE_CONFIG_FAMILIES, SOURCE_CONFIG_MODELS)
    from tensorflow_distributed_tpu.models import (
        INFERENCE_ONLY_MODELS, MODEL_NAMES, build_model)
    _cfg().validate()
    assert len(SOURCE_CONFIG_FAMILIES) == 7
    assert "jamba" in SOURCE_CONFIG_MODELS
    assert "jamba" in MODEL_NAMES
    assert "jamba" in INFERENCE_ONLY_MODELS
    model = build_model("jamba", source=CONFIG + "#rehearsal.sizes",
                        max_len=64)
    assert isinstance(model, M.JambaLM)
    assert model.cfg.max_len == 64
    with pytest.raises(ValueError, match="no --model-size preset"):
        build_model("jamba", size="tiny")


def test_cli_serves_the_family(tmp_path):
    from tensorflow_distributed_tpu import cli
    jsonl = tmp_path / "m.jsonl"
    rc = cli.main([
        "--mode", "serve", "--model", "jamba", "--model-config",
        CONFIG + "#rehearsal.sizes", "--compute-dtype", "float32",
        "--seq-len", "64",
        "--serve.num-requests", "5", "--serve.num-slots", "2",
        "--serve.max-new-tokens", "20", "--serve.prompt-len-min", "2",
        "--serve.prompt-len-max", "30", "--observe.metrics-jsonl",
        str(jsonl)])
    assert rc == 0
    recs = [json.loads(x) for x in jsonl.read_text().splitlines()]
    summary = [r for r in recs if r.get("event") == "serve_summary"][-1]
    assert summary["requests"] == 5
    assert summary["cache_bytes_per_slot_by_kind"] == {
        "state": 6 * 4 * 96 * 4, "conv": 6 * 4 * 96 * 4,
        "kv": 2 * 64 * 32 * 4, "state_pos": 4}
    assert summary["state_rows_stepped"] == 6 * summary["decode_live_rows"]
    assert summary["s6_scan_positions"] >= summary["s6_scan_positions_live"] \
        > 0
    assert summary["s6_scan_positions"] % 6 == 0
    assert summary["attend_positions_visited"] > 0
    (start,) = [r for r in recs if r.get("event") == "start"]
    assert (start["model"], start["task"]) == ("jamba", "serve")
