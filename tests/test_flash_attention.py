"""Pallas flash attention vs the XLA oracle (interpret mode on CPU).

The kernel is validated the way SURVEY.md §4 prescribes for everything
else: run the real code path on the host platform and compare against
a plain-XLA reference — here ``full_attention``, which is also the
ring-attention building block, so the two attention paths are pinned
to each other.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_distributed_tpu.ops import flash_attention as fa
from tensorflow_distributed_tpu.ops.flash_attention import (
    NEG_INF, attention, flash_attention, flash_plan, supported,
    window_keep)
from tensorflow_distributed_tpu.parallel.ring_attention import full_attention

B, L, H, D = 2, 256, 2, 64


def _qkv(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, L, H, D)), dtype) * 0.5
    return mk(), mk(), mk()


def _causal_mask():
    from tensorflow_distributed_tpu.parallel.ring_attention import causal_bias
    return causal_bias(L, L)


def test_forward_matches_oracle():
    q, k, v = _qkv()
    got = flash_attention(q, k, v, interpret=True)
    want = full_attention(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_forward_causal():
    q, k, v = _qkv(1)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = full_attention(q, k, v, _causal_mask())
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_oracle(causal):
    q, k, v = _qkv(2)
    mask = _causal_mask() if causal else None

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        return jnp.sum(jnp.sin(out))  # non-uniform cotangents

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(full_attention(q, k, v, mask)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-4,
                                   err_msg=f"d{name}")


def test_supported_gate():
    assert supported(256, 256, 64)
    assert supported(200, 256, 64)       # blocks clamp to short seqs
    assert not supported(250, 256, 64)   # ragged: 250 % 8 != 0
    assert supported(768, 256, 64)       # clamps to bq=768 (div by 8)
    assert not supported(1536, 256, 64)  # 1536 not divisible by bq=1024
    assert not supported(256, 256, 300)  # head dim too large


def test_short_seq_clamped_blocks():
    q = jnp.ones((1, 40, 2, 16), jnp.float32) * 0.1
    got = flash_attention(q, q, q, interpret=True)
    np.testing.assert_allclose(got, full_attention(q, q, q),
                               atol=2e-6, rtol=2e-6)


def test_flash_under_shard_map(mesh8):
    """The multi-device TPU path: kernel shard_mapped over the batch
    axis (interpret mode on the 8-device CPU mesh)."""
    from jax.sharding import PartitionSpec as P

    rng = np.random.default_rng(4)
    mk = lambda: jnp.asarray(rng.normal(size=(8, 256, 2, 32)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    spec = P("data", None, None, None)
    got = jax.jit(jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, interpret=True),
        mesh=mesh8, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False))(q, k, v)
    np.testing.assert_allclose(got, full_attention(q, k, v),
                               atol=2e-5, rtol=2e-5)


def test_ragged_seq_raises():
    q = jnp.ones((1, 1500, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, q, q, interpret=True)


def test_dispatcher_falls_back_off_tpu():
    # On CPU the dispatcher must route to the XLA path and still be
    # numerically the oracle (incl. the causal-mask construction).
    q, k, v = _qkv(3)
    np.testing.assert_allclose(attention(q, k, v, causal=True),
                               full_attention(q, k, v, _causal_mask()),
                               atol=1e-6)


def test_causal_multiblock_skip_matches_oracle():
    """Small blocks at L=256 give an 8x8 block grid where the causal
    skip predicate and the DMA re-point index_maps actually fire on the
    28 above-diagonal pairs — an off-by-one in _band or in _walk_map's
    re-point would corrupt exactly this case (on the plan's own one-step
    grid the skip is inside the kernel instead)."""
    rng = np.random.default_rng(7)
    B, L, H, D = 2, 256, 2, 16
    mk = lambda: jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    q, k, v = mk(), mk(), mk()

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32,
                               block_k=64, interpret=True)

    from tensorflow_distributed_tpu.parallel.ring_attention import (
        causal_bias, full_attention)
    oracle = full_attention(q, k, v, causal_bias(L, L))
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(oracle), rtol=2e-5, atol=2e-5)

    # Gradients through all three kernels on the same multi-block grid.
    gf = jax.grad(lambda q, k, v: jnp.sum(flash(q, k, v) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(
        lambda q, k, v: jnp.sum(full_attention(q, k, v,
                                               causal_bias(L, L)) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def _window_bias(L, window):
    from tensorflow_distributed_tpu.parallel.ring_attention import (
        causal_bias)
    rows = np.arange(L)[:, None]
    cols = np.arange(L)[None, :]
    extra = jnp.where(jnp.asarray(cols > rows - window), 0.0,
                      float(NEG_INF))[None]
    return causal_bias(L, L) + extra


@pytest.mark.parametrize("window", [1, 17, 48, 64, 200, 256])
def test_window_multiblock_matches_oracle(window):
    """Sliding-window flash vs the dense masked oracle on an 8x4 block
    grid (bq=32, bk=64): windows smaller than a block, spanning
    several blocks, block-aligned, and >= L (== plain causal) all hit
    the band bounds (_band) and the clamp index maps (_walk_map)
    differently. Forward AND all three gradient kernels."""
    rng = np.random.default_rng(9)
    B, L, H, D = 2, 256, 2, 16
    mk = lambda: jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    q, k, v = mk(), mk(), mk()

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=32, block_k=64, interpret=True)

    oracle_fn = lambda q, k, v: full_attention(  # noqa: E731
        q, k, v, _window_bias(L, window))
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(oracle_fn(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    gf = jax.grad(lambda q, k, v: jnp.sum(flash(q, k, v) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(lambda q, k, v: jnp.sum(oracle_fn(q, k, v) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_window_at_or_past_length_equals_causal():
    q, k, v = _qkv(seed=10)
    plain = flash_attention(q, k, v, causal=True, block_q=64,
                            block_k=64, interpret=True)
    for w in (L, L + 100):
        out = flash_attention(q, k, v, causal=True, window=w,
                              block_q=64, block_k=64, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(plain),
                                   rtol=1e-6, atol=1e-6)


def test_window_requires_causal():
    q, k, v = _qkv(seed=11)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=8, interpret=True)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=-1, interpret=True)
    # The XLA-oracle dispatcher path must not silently drop the window
    # for non-causal configs either.
    with pytest.raises(ValueError, match="causal"):
        attention(q, k, v, causal=False, window=8, allow_flash=False)


def test_window_dispatcher_xla_fallback_matches_flash():
    """attention() with a window on the non-flash path (allow_flash=
    False) must agree with the windowed kernel — the two code paths a
    user can land on depending on backend/shapes."""
    rng = np.random.default_rng(12)
    B, L2, H, D = 2, 128, 2, 16
    mk = lambda: jnp.asarray(rng.normal(size=(B, L2, H, D)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    xla = attention(q, k, v, causal=True, window=24, allow_flash=False)
    fl = flash_attention(q, k, v, causal=True, window=24, block_q=32,
                         block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(fl),
                               rtol=2e-5, atol=2e-5)


def test_causal_multiblock_uneven_blocks():
    """bq != bk with bq > bk and bk > bq both exercise the floor-div
    arithmetic in the skip maps."""
    rng = np.random.default_rng(8)
    B, L, H, D = 1, 128, 2, 8
    mk = lambda: jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    from tensorflow_distributed_tpu.parallel.ring_attention import (
        causal_bias, full_attention)
    oracle = full_attention(q, k, v, causal_bias(L, L))
    for bq, bk in [(16, 64), (64, 16), (32, 32)]:
        out = flash_attention(q, k, v, causal=True, block_q=bq,
                              block_k=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"bq={bq} bk={bk}")


# ---- the plan's own choice of blocks and tiles --------------------------

def _oracle(q, k, v, causal, window):
    if not causal:
        return full_attention(q, k, v)
    return full_attention(q, k, v, fa.window_bias(
        jnp.arange(q.shape[1])[:, None], jnp.arange(k.shape[1])[None, :],
        window))


def _assert_parity(q, k, v, causal, window, **blocks):
    """Forward and all three gradients against the dense oracle."""
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=causal, window=window, interpret=True, **blocks)
    oracle = lambda q, k, v: _oracle(q, k, v, causal, window)  # noqa: E731
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(oracle(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    gf = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))),
                  argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(lambda *a: jnp.sum(jnp.sin(oracle(*a))),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, go, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5, err_msg=f"d{name}")


def _rand_qkv(seed, L, Lk=None, heads=1, D=64):
    rng = np.random.default_rng(seed)
    mk = lambda n: jnp.asarray(  # noqa: E731
        rng.normal(size=(1, n, heads, D)), jnp.float32) * 0.5
    return mk(L), mk(Lk or L), mk(Lk or L)


@pytest.mark.parametrize("L,causal,window", [
    (128, True, 0), (384, True, 0), (1024, True, 0),   # the cell's L last
    (384, False, 0),
    (384, True, 1), (384, True, 17), (384, True, 200), (384, True, 256),
], ids=lambda x: str(x))
def test_plan_choice_matches_oracle(L, causal, window):
    """No block_q / block_k passed: the kernels run on flash_plan's own
    blocks and tiles (one 128 tile; 3 x 3 tiles of 128 with windows
    inside a tile, across two and on a tile edge; 4 x 4 tiles of 256 at
    the train cells' length), loops that end at the diagonal and start
    at the horizon, the mask on edge tiles only."""
    plan = flash_plan(L, L, 64, jnp.float32, causal=causal, window=window)
    assert (plan.block_q, plan.block_k) == (L, L)
    assert plan.tile_q == plan.tile_k == (256 if L == 1024 else 128)
    if causal and L > 128:
        assert plan.tiles_computed < plan.tiles_total
        assert 0 < plan.tiles_masked <= plan.tiles_computed
    _assert_parity(*_rand_qkv(20 + window, L, heads=2 if L < 1024 else 1),
                   causal, window)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 300),
                                           (False, 0)],
                         ids=["causal", "window300", "full"])
def test_long_lk_branch_matches_oracle(causal, window):
    """K and V past the byte budget: the plan falls back to a k-major
    grid axis (two blocks of 512 here) and a tile is a whole block, the
    band walked by the grid. The budget is handed to the plan function;
    the blocks it returns are what flash_attention is pinned to."""
    L = 1024
    budget = fa._vmem_bytes(1024, 512, 64, 4)
    plan = flash_plan(L, L, 64, jnp.float32, causal=causal, window=window,
                      vmem_bytes=budget)
    assert plan[:4] == (1024, 512, 1024, 512)
    whole = flash_plan(L, L, 64, jnp.float32, causal=causal, window=window)
    assert whole[:4] == (1024, 1024, 256, 256)
    _assert_parity(*_rand_qkv(31, L), causal, window,
                   block_q=plan.block_q, block_k=plan.block_k)


def _brute_tiles(L, Lk, tq, tk, causal, window):
    """{(q tile, k tile): all scores kept} over the tiles holding any
    kept score, straight from window_keep."""
    keep = np.ones((L, Lk), bool) if not causal else np.asarray(
        window_keep(np.arange(L)[:, None], np.arange(Lk)[None, :], window))
    tiles = {}
    for i in range(L // tq):
        for j in range(Lk // tk):
            t = keep[i * tq:(i + 1) * tq, j * tk:(j + 1) * tk]
            if t.any():
                tiles[i, j] = bool(t.all())
    return tiles


@pytest.mark.parametrize("L,blocks", [
    (1024, {}), (1024, dict(block_q=128, block_k=128)), (384, {}),
    (200, {}), (2048, {}), (256, dict(block_q=32, block_k=64)),
    (256, dict(block_q=64, block_k=32)), (128, dict(block_q=16, block_k=16)),
], ids=lambda x: str(x).replace(" ", ""))
@pytest.mark.parametrize("window", [None, 0, 1, 17, 48, 64, 200, 256, 5000])
def test_plan_counts_equal_brute_force(L, blocks, window):
    """The plan alone (window None: non-causal). Its counts, and the
    band both walks are built from, against a brute-force pass over
    window_keep: every tile holding a kept score is visited, by the
    k walk of fwd / dq and by the q walk of dkv; a tile visited without
    a mask holds no masked score."""
    causal = window is not None
    window = window or 0
    plan = flash_plan(L, L, 64, causal=causal, window=window, **blocks)
    tq, tk = plan.tile_q, plan.tile_k
    want = _brute_tiles(L, L, tq, tk, causal, window)
    assert plan.tiles_total == (L // tq) * (L // tk)
    assert plan.tiles_computed == len(want)
    assert plan.tiles_masked == sum(not full for full in want.values())

    def visited(walk_keys):
        fixed, walk = (tq, tk) if walk_keys else (tk, tq)
        lo, hi = fa._offsets(causal, window, walk_keys)
        got = {}
        for a in range(L // fixed):
            first, full_lo, full_hi, last = fa._band(
                a * fixed, fixed, walk, lo, hi, 0, L // walk)
            for w in range(first, last):
                got[(a, w) if walk_keys else (w, a)] = full_lo <= w < full_hi
        return got

    assert visited(True) == want
    assert visited(False) == want


def test_plan_counts_at_the_train_cells_length():
    """ISSUE 31's figures: 10 of 16 tiles and 4 of those masked with
    256-tiles, 36 of 64 and 8 with 128-tiles."""
    p = flash_plan(1024, 1024, 64, causal=True)
    assert (p.tile_q, p.tiles_total, p.tiles_computed, p.tiles_masked) == (
        256, 16, 10, 4)
    assert p.describe()["computed_share"] == 10 / 16
    assert p.describe()["masked_share"] == 4 / 10
    p = flash_plan(1024, 1024, 64, causal=True, block_q=128, block_k=128)
    assert (p.tile_q, p.tiles_total, p.tiles_computed, p.tiles_masked) == (
        128, 64, 36, 8)


def test_band_k_major_blocks_tile_the_whole_walk():
    """A walk split over k-major blocks visits, block by block in local
    tile indices, exactly the tiles the whole walk visits."""
    L, tq, tk, per_block = 1024, 128, 64, 4
    for window in (0, 100, 300):
        lo, hi = fa._offsets(True, window, True)
        for a in range(L // tq):
            whole = fa._band(a * tq, tq, tk, lo, hi, 0, L // tk)
            got_all, got_full = [], []
            for blk in range(L // (tk * per_block)):
                w0 = blk * tk * per_block
                first, full_lo, full_hi, last = fa._band(
                    a * tq, tq, tk, lo, hi, w0, per_block)
                got_all += [blk * per_block + t for t in range(first, last)]
                got_full += [blk * per_block + t
                             for t in range(full_lo, full_hi)]
            assert got_all == list(range(whole[0], whole[3]))
            assert got_full == list(range(whole[1], whole[2]))


def test_start_record_carries_the_flash_plan(tmp_path, monkeypatch):
    """The train loop writes the plan, with both shares, on the start
    record it writes once a run, with the compile records off as the
    benchmark's cells run; absent where the step does not reach the
    kernel (here: off the TPU without the interpreter forced)."""
    import json

    from tensorflow_distributed_tpu.config import (
        MeshConfig, ObserveConfig, TrainConfig)
    from tensorflow_distributed_tpu.train.loop import train

    def start_record(name):
        jsonl = str(tmp_path / f"{name}.jsonl")
        train(TrainConfig(
            model="gpt_lm", model_size="tiny", dataset="synthetic",
            seq_len=32, batch_size=16, train_steps=1, eval_every=0,
            log_every=1, compute_dtype="float32", dropout_rate=0.0,
            mesh=MeshConfig(data=8),
            observe=ObserveConfig(metrics_jsonl=jsonl, programs=False)))
        records = [json.loads(line) for line in open(jsonl)]
        assert not [r for r in records if r["event"] == "compile"]
        return next(r for r in records if r["event"] == "start")

    assert "flash_plan" not in start_record("xla")
    monkeypatch.setenv("TFD_FLASH_INTERPRET", "1")
    got = start_record("flash")["flash_plan"]
    want = flash_plan(32, 32, 16, jnp.float32, causal=True).describe()
    assert got == want
    assert got["tile_q"] == got["tile_k"] == 32
    assert got["computed_share"] == 1.0 and got["masked_share"] == 1.0


# ---- partial-softmax variant (the ring's building block) ---------------

def _partial_oracle(q, k, v, causal):
    from tensorflow_distributed_tpu.parallel.ring_attention import (
        _block_attend, causal_bias)
    bias = causal_bias(q.shape[1], k.shape[1]) if causal else None
    return _block_attend(q, k, v, bias)


@pytest.mark.parametrize("causal", [False, True])
def test_partial_matches_einsum_oracle(causal):
    """flash_attention_partial == the einsum streaming-softmax partials
    (m, l, unnormalized o) that the zigzag ring merges."""
    from tensorflow_distributed_tpu.ops.flash_attention import (
        flash_attention_partial)

    q, k, v = _qkv(11)
    gm, gl, go = flash_attention_partial(q, k, v, causal=causal,
                                         interpret=True)
    wm, wl, wo = _partial_oracle(q, k, v, causal)
    # m may differ by the oracle's fully-masked-row clamp only when a
    # row is fully masked — never the case here (diagonal visible).
    np.testing.assert_allclose(np.asarray(gm), np.asarray(wm), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gl), np.asarray(wl),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(go), np.asarray(wo),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_partial_grads_match_einsum_oracle(causal):
    """Gradients THROUGH a ring-style merge+normalize consumer: the
    custom VJP (m as stop-grad stabilizer) must match AD through the
    einsum partials exactly where it matters — after the invariant
    merge/finish, not on the raw partials."""
    from tensorflow_distributed_tpu.ops.flash_attention import (
        flash_attention_partial)

    q, k, v = _qkv(12)
    q2, k2, v2 = _qkv(13)

    def consumer(attend):
        def f(q, k, v):
            m1, l1, o1 = attend(q, k, v)
            m2, l2, o2 = _partial_oracle(q2, k2, v2, False)
            from tensorflow_distributed_tpu.parallel.ring_attention \
                import _merge
            m, l, o = _merge(m1, l1, o1, m2, l2, o2)
            out = o / l.transpose(0, 2, 1)[..., None]
            return jnp.sum(out * out)
        return f

    flash = consumer(lambda q, k, v: flash_attention_partial(
        q, k, v, causal=causal, interpret=True))
    oracle = consumer(lambda q, k, v: _partial_oracle(q, k, v, causal))
    gf = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)
