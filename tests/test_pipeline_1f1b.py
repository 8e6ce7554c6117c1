"""1F1B pipeline schedule: parity, memory bound, dropout, composition.

The correctness bar: 1F1B is a SCHEDULE change, not a math change —
its step must reproduce the GPipe step (same state, same batch) to
float tolerance, while compiling to materially less temp memory at
large microbatch counts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
from tensorflow_distributed_tpu.data.lm import synthetic_clm
from tensorflow_distributed_tpu.models.pipelined import pipelined_lm
from tensorflow_distributed_tpu.parallel.mesh import make_mesh
from tensorflow_distributed_tpu.parallel.pipeline import bubble_fraction
from tensorflow_distributed_tpu.parallel.sharding import shard_batch
from tensorflow_distributed_tpu.train.pipeline_step import (
    make_1f1b_train_step)
from tensorflow_distributed_tpu.train.state import create_train_state
from tensorflow_distributed_tpu.train.step import make_train_step
from tensorflow_distributed_tpu.train.tasks import (
    mlm_batch_shardings, mlm_loss)


def _setup(mesh, microbatches=8, batch=16, dropout=0.0, **kw):
    kw.setdefault("n_layers", 4)
    kw.setdefault("max_len", 16)
    model = pipelined_lm(mesh, num_microbatches=microbatches,
                         dropout_rate=dropout,
                         compute_dtype=jnp.float32, **kw)
    state = create_train_state(model, optax.adam(1e-2),
                               np.zeros((2, 16), np.int32), mesh)
    ds = synthetic_clm(n=max(2 * batch, 32), seq_len=16, vocab_size=64)
    b = shard_batch(mesh, ds.batch(np.arange(batch)), seq_axis=1)
    return model, state, b


def test_1f1b_matches_gpipe(devices8):
    """Same state, same batch: 1F1B step == GPipe step (loss, metrics,
    updated params) to float tolerance."""
    mesh = make_mesh(MeshConfig(data=2, pipe=4), devices8)
    model, state, batch = _setup(mesh)
    step_g = make_train_step(mesh, loss=mlm_loss,
                             batch_shardings=mlm_batch_shardings(mesh),
                             donate=False, grad_norm_metric=True)
    step_f = make_1f1b_train_step(model, mesh, donate=False,
                                  grad_norm_metric=True)
    st_g, met_g = step_g(state, batch)
    st_f, met_f = step_f(state, batch)
    np.testing.assert_allclose(float(met_f["loss"]),
                               float(met_g["loss"]), rtol=1e-5)
    # The hand-scheduled backward produces the SAME gradients — pinned
    # here via the global grad norm both schedules now report.
    np.testing.assert_allclose(float(met_f["grad_norm"]),
                               float(met_g["grad_norm"]), rtol=1e-4)
    np.testing.assert_allclose(float(met_f["accuracy"]),
                               float(met_g["accuracy"]), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-6, rtol=1e-4),
        st_g.params, st_f.params)


@pytest.mark.parametrize("mutable,served", [
    ("health", True), (["health"], True), (["cache"], False),
    (["health", "batch_stats"], False)])
def test_apply_opens_health_empty_and_rejects_unknown(devices8, mutable,
                                                      served):
    """train.step.apply_model opens "health" on every training pass:
    the pipelined model takes it and sows nothing (the logits are the
    plain apply's), and still refuses a collection it cannot serve."""
    mesh = make_mesh(MeshConfig(data=2, pipe=4), devices8)
    model, state, batch = _setup(mesh)
    variables = {"params": state.params}
    if not served:
        with pytest.raises(ValueError, match="only; got"):
            model.apply(variables, batch["tokens"], train=True,
                        mutable=mutable)
        return
    logits, mut = jax.jit(lambda v, t: model.apply(
        v, t, train=True, mutable=mutable))(variables, batch["tokens"])
    assert not mut
    plain = jax.jit(lambda v, t: model.apply(v, t, train=True))(
        variables, batch["tokens"])
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(plain))


@pytest.mark.parametrize("tie", [False, True])
def test_1f1b_fused_ce_matches_dense_head(devices8, tie):
    """ce_chunk > 0 swaps the last stage's dense head+loss for the
    chunked custom-VJP op INSIDE the scheduled head vjp — a loss-
    formulation change, not a math change: same batch + state must
    reproduce the dense 1F1B step (loss, accuracy, updated params),
    tied and untied heads both."""
    mesh = make_mesh(MeshConfig(data=2, pipe=4), devices8)
    model, state, batch = _setup(mesh, tie_embeddings=tie,
                                 pos_emb="rope" if tie else "learned")
    dense_step = make_1f1b_train_step(model, mesh, donate=False)
    fused_step = make_1f1b_train_step(model, mesh, donate=False,
                                      ce_chunk=24)
    st_d, met_d = dense_step(state, batch)
    st_f, met_f = fused_step(state, batch)
    np.testing.assert_allclose(float(met_f["loss"]),
                               float(met_d["loss"]), rtol=1e-6)
    np.testing.assert_allclose(float(met_f["accuracy"]),
                               float(met_d["accuracy"]), rtol=1e-6)
    # Not bitwise: the fused op's streaming logsumexp reduces in a
    # different order than the dense one; Adam amplifies the last-ulp
    # grad differences on near-zero-grad params.
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5, rtol=1e-3),
        st_d.params, st_f.params)


def test_gpipe_fused_ce_matches_dense_head(devices8):
    """The GPipe path reaches the fused loss through
    PipelinedLM.apply(features_only=True) — make_mlm_loss(ce_chunk)
    must reproduce the dense mlm_loss trajectory."""
    from tensorflow_distributed_tpu.train.tasks import make_mlm_loss

    mesh = make_mesh(MeshConfig(data=2, pipe=4), devices8)
    model, state, batch = _setup(mesh)
    dense = make_train_step(mesh, loss=mlm_loss, donate=False,
                            batch_shardings=mlm_batch_shardings(mesh))
    fused = make_train_step(mesh, loss=make_mlm_loss(ce_chunk=24),
                            donate=False,
                            batch_shardings=mlm_batch_shardings(mesh))
    st_d, met_d = dense(state, batch)
    st_f, met_f = fused(state, batch)
    np.testing.assert_allclose(float(met_f["loss"]),
                               float(met_d["loss"]), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5, rtol=1e-3),
        st_d.params, st_f.params)


def test_variant_residual_mask_splits_weights_from_activations():
    """The stash backward's hoist: residual leaves that are a pure
    function of params (weight matrices, their compute-dtype casts)
    must be flagged invariant — verified BEHAVIORALLY: leaves the mask
    calls invariant are bit-identical across different (x, m), leaves
    it calls variant include everything that moves. Dropout-mask
    residuals depend on the microbatch index through the key fold and
    must stay variant even though they don't depend on x."""
    from tensorflow_distributed_tpu.parallel.pipeline import (
        variant_residual_mask)

    base_key = jax.random.PRNGKey(7)
    params = {"w": jnp.linspace(0, 1, 64).reshape(8, 8)
              .astype(jnp.float32), "b": jnp.ones((8,), jnp.float32)}

    def stage(p, x, m):
        h = x @ p["w"].astype(jnp.bfloat16).astype(jnp.float32) + p["b"]
        keep = jax.random.bernoulli(
            jax.random.fold_in(base_key, m), 0.8, h.shape)
        return jnp.tanh(h) * keep

    def res_fn(p, x, m):
        _, vjp = jax.vjp(lambda pp, xx: stage(pp, xx, m), p, x)
        return jax.tree_util.tree_leaves(vjp)

    x1 = jnp.ones((4, 8), jnp.float32)
    x2 = 2.0 * x1
    mask = variant_residual_mask(res_fn, params, x1)
    ra = res_fn(params, x1, jnp.int32(0))
    rb = res_fn(params, x2, jnp.int32(1))
    assert len(mask) == len(ra)
    hoisted = [i for i, v in enumerate(mask) if not v]
    assert hoisted, "no leaf hoisted — the weight cast should be"
    for i in hoisted:
        np.testing.assert_array_equal(np.asarray(ra[i]),
                                      np.asarray(rb[i]))
    # Something must still be stashed (activations, dropout masks).
    assert any(mask)
    # The dropout mask moved with m at fixed x — the mask may not
    # call every moving leaf invariant.
    rc = res_fn(params, x1, jnp.int32(1))
    moved = [i for i in range(len(ra))
             if np.asarray(ra[i]).shape == np.asarray(rc[i]).shape
             and not np.array_equal(np.asarray(ra[i]),
                                    np.asarray(rc[i]))]
    assert all(mask[i] for i in moved)


def test_1f1b_stash_backward_matches_recompute(devices8):
    """backward="stash" (residual ring buffers, no forward recompute)
    is a memory/compute trade, not a math change: same batch + state
    must give the same loss and updated params as the default
    recompute backward, including with dropout active (the stashed
    residuals carry the forward-tick masks). Round 4 read recompute
    as the WINNER on one v5e (the stash's HBM traffic costs more than
    re-running the stage forward on an underutilized MXU; not
    re-measured, PERF.md "Before the benchmark"), so stash stays
    opt-in."""
    mesh = make_mesh(MeshConfig(data=2, pipe=4), devices8)
    # remat=True inside the stage: the vjp residual set shrinks to the
    # checkpoint-saved subset — the documented mitigation for stash's
    # memory cost — and must compose transparently (jax.vjp of a
    # rematted stage_fn just yields the smaller residual pytree).
    model, state, batch = _setup(mesh, dropout=0.2, remat=True)
    steps = {
        mode: make_1f1b_train_step(model, mesh, donate=False,
                                   backward=mode)
        for mode in ("recompute", "stash")}
    st_r, met_r = steps["recompute"](state, batch)
    st_s, met_s = steps["stash"](state, batch)
    assert float(met_r["loss"]) == pytest.approx(float(met_s["loss"]),
                                                 rel=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-6, rtol=1e-4),
        st_r.params, st_s.params)


@pytest.mark.slow
def test_1f1b_temp_memory_bounded(devices8):
    """The point of 1F1B: compiled temp memory stays O(S) while GPipe's
    grows O(M). At M=16 the gap must be at least 3x (measured ~16x at
    M=32 on this backend)."""
    mesh = make_mesh(MeshConfig(data=1, pipe=2), devices8[:2])
    M = 16
    model = pipelined_lm(mesh, num_microbatches=M, n_layers=4,
                         max_len=64, d_model=64, d_ff=128,
                         dropout_rate=0.0, compute_dtype=jnp.float32)
    state = create_train_state(model, optax.adam(1e-2),
                               np.zeros((2, 64), np.int32), mesh)
    ds = synthetic_clm(n=32, seq_len=64, vocab_size=64)
    batch = shard_batch(mesh, ds.batch(np.arange(32)), seq_axis=1)
    step_g = make_train_step(mesh, loss=mlm_loss,
                             batch_shardings=mlm_batch_shardings(mesh),
                             donate=False)
    step_f = make_1f1b_train_step(model, mesh, donate=False)
    t_g = step_g.lower(state, batch).compile().memory_analysis()
    t_f = step_f.lower(state, batch).compile().memory_analysis()
    ratio = t_g.temp_size_in_bytes / t_f.temp_size_in_bytes
    assert ratio > 3.0, (
        f"1f1b should need far less temp memory: gpipe "
        f"{t_g.temp_size_in_bytes/1e6:.1f}MB vs 1f1b "
        f"{t_f.temp_size_in_bytes/1e6:.1f}MB ({ratio:.2f}x)")


@pytest.mark.slow
def test_1f1b_dropout_deterministic_and_active(devices8):
    """With dropout: the step is deterministic (same state+batch twice
    -> same result) and the masks are real (loss differs from the
    dropout-free model with identical params)."""
    mesh = make_mesh(MeshConfig(data=2, pipe=2), devices8[:4])
    model_d, state, batch = _setup(mesh, microbatches=4, dropout=0.3)
    step = make_1f1b_train_step(model_d, mesh, donate=False)
    _, met1 = step(state, batch)
    _, met2 = step(state, batch)
    assert float(met1["loss"]) == float(met2["loss"])

    model_n = pipelined_lm(mesh, num_microbatches=4, n_layers=4,
                           max_len=16, dropout_rate=0.0,
                           compute_dtype=jnp.float32)
    step_n = make_1f1b_train_step(model_n, mesh, donate=False)
    _, met_n = step_n(state, batch)
    assert float(met1["loss"]) != float(met_n["loss"])


def test_1f1b_composes_with_tp(devices8):
    """PP x TP x DP under 1F1B: mesh (data=2, pipe=2, model=2) produces
    the same step as (data=4, pipe=2) — TP is a layout, not math."""
    mesh_tp = make_mesh(MeshConfig(data=2, pipe=2, model=2), devices8)
    mesh_dp = make_mesh(MeshConfig(data=4, pipe=2), devices8)
    losses = []
    for mesh in (mesh_tp, mesh_dp):
        model, state, batch = _setup(mesh, microbatches=4)
        step = make_1f1b_train_step(model, mesh, donate=False)
        _, met = step(state, batch)
        losses.append(float(met["loss"]))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)


def _layer_major(blocks, V):
    """Stage-stacked block leaves -> layer-major [n_layers, ...] so
    plain ([S, lps]) and interleaved ([S, V, lps], virtual stage
    j = v*S + s) layouts compare directly."""
    def one(p):
        if V == 1:
            return p.reshape(p.shape[0] * p.shape[1], *p.shape[2:])
        q = jnp.swapaxes(p, 0, 1)  # [V, S, lps, ...]; [v, s] = j=v*S+s
        return q.reshape(q.shape[0] * q.shape[1] * q.shape[2],
                         *q.shape[3:])
    return jax.tree_util.tree_map(one, blocks)


def test_interleaved_1f1b_matches_plain(devices8):
    """Interleaved virtual stages (round-4 review item 4): the [S, V, lps]
    regrouping is a LAYOUT, not a math change. With the same per-layer
    weights (same init keys — regrouping happens after the per-layer
    vmap), the V=2 single-scan interleaved schedule must reproduce the
    plain 1F1B step: loss, accuracy, grad norm, and updated params
    (compared layer-major)."""
    mesh = make_mesh(MeshConfig(data=2, pipe=2), devices8[:4])
    kw = dict(n_layers=4, max_len=16, dropout_rate=0.0,
              compute_dtype=jnp.float32, use_flash=False)
    m_p = pipelined_lm(mesh, num_microbatches=8, **kw)
    m_i = pipelined_lm(mesh, num_microbatches=8, virtual_stages=2, **kw)
    sample = np.zeros((2, 16), np.int32)
    s_p = create_train_state(m_p, optax.adam(1e-2), sample, mesh)
    s_i = create_train_state(m_i, optax.adam(1e-2), sample, mesh)
    # Identical underlying layer weights despite different stackings.
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        _layer_major(s_p.params["blocks"], 1),
        _layer_major(s_i.params["blocks"], 2))

    ds = synthetic_clm(n=32, seq_len=16, vocab_size=64)
    batch = shard_batch(mesh, ds.batch(np.arange(16)), seq_axis=1)

    # Forward parity too (the GPipe/eval path chains V pipeline
    # passes over the chunk groups).
    lp = jax.jit(lambda v, t: m_p.apply(v, t))(
        {"params": s_p.params}, batch["tokens"])
    li = jax.jit(lambda v, t: m_i.apply(v, t))(
        {"params": s_i.params}, batch["tokens"])
    np.testing.assert_allclose(np.asarray(li), np.asarray(lp),
                               atol=2e-5, rtol=2e-4)

    step_p = make_1f1b_train_step(m_p, mesh, donate=False,
                                  grad_norm_metric=True)
    step_i = make_1f1b_train_step(m_i, mesh, donate=False,
                                  grad_norm_metric=True)
    st_p, met_p = step_p(s_p, batch)
    st_i, met_i = step_i(s_i, batch)
    np.testing.assert_allclose(float(met_i["loss"]),
                               float(met_p["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(met_i["accuracy"]),
                               float(met_p["accuracy"]), rtol=1e-6)
    np.testing.assert_allclose(float(met_i["grad_norm"]),
                               float(met_p["grad_norm"]), rtol=1e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-6, rtol=1e-4),
        _layer_major(st_p.params["blocks"], 1),
        _layer_major(st_i.params["blocks"], 2))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-6, rtol=1e-4),
        st_p.params["shell"], st_i.params["shell"])


@pytest.mark.slow
def test_interleaved_ring_matches_plain(devices8):
    """The full composition stack: interleaved virtual stages x ring
    attention (pipe=2 x seq=2 x V=2) — the interleaved schedule's
    where-masked bubble mode (seq collectives can't live under
    cond-skipped branches) must reproduce plain 1F1B on the same
    mesh."""
    mesh = make_mesh(MeshConfig(pipe=2, seq=2), devices8[:4])
    kw = dict(n_layers=4, max_len=16, dropout_rate=0.0,
              compute_dtype=jnp.float32, use_flash=False,
              pos_emb="rope")
    m_p = pipelined_lm(mesh, num_microbatches=4, **kw)
    m_i = pipelined_lm(mesh, num_microbatches=4, virtual_stages=2, **kw)
    sample = np.zeros((2, 16), np.int32)
    s_p = create_train_state(m_p, optax.adam(1e-2), sample, mesh)
    s_i = create_train_state(m_i, optax.adam(1e-2), sample, mesh)
    ds = synthetic_clm(n=32, seq_len=16, vocab_size=64)
    batch = shard_batch(mesh, ds.batch(np.arange(16)), seq_axis=1)
    step_p = make_1f1b_train_step(m_p, mesh, donate=False)
    step_i = make_1f1b_train_step(m_i, mesh, donate=False)
    _, met_p = step_p(s_p, batch)
    _, met_i = step_i(s_i, batch)
    np.testing.assert_allclose(float(met_i["loss"]),
                               float(met_p["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(met_i["accuracy"]),
                               float(met_p["accuracy"]), rtol=1e-6)


def test_interleaved_cli_end_to_end(devices8):
    """--pipeline-virtual-stages 2 trains through the full loop."""
    from tensorflow_distributed_tpu.train.loop import train

    cfg = TrainConfig(model="pipelined_lm", model_size="tiny",
                      dataset="synthetic", batch_size=16, train_steps=3,
                      eval_every=0, log_every=0, eval_batch_size=16,
                      compute_dtype="float32", pipeline_schedule="1f1b",
                      pipeline_virtual_stages=2,
                      pipeline_microbatches=4,
                      mesh=MeshConfig(data=4, pipe=2))
    cfg.validate()
    result = train(cfg)
    assert np.isfinite(result.final_metrics["loss"])


def test_interleaved_config_walls():
    """virtual stages: rejected off-family, with stash backward, and
    with too few microbatches."""
    with pytest.raises(ValueError, match="pipelined_lm"):
        TrainConfig(model="gpt_lm",
                    pipeline_virtual_stages=2).validate()
    with pytest.raises(ValueError, match="recompute"):
        TrainConfig(model="pipelined_lm", pipeline_schedule="1f1b",
                    pipeline_virtual_stages=2,
                    pipeline_backward="stash",
                    mesh=MeshConfig(pipe=2)).validate()
    with pytest.raises(ValueError, match="virtual"):
        TrainConfig(model="pipelined_lm", pipeline_schedule="1f1b",
                    pipeline_virtual_stages=4,
                    pipeline_microbatches=4, batch_size=32,
                    mesh=MeshConfig(pipe=2)).validate()


@pytest.mark.slow
def test_1f1b_trains_end_to_end(devices8):
    """The full loop with pipeline_schedule=1f1b learns the synthetic
    progression well above chance (the GPipe twin of this test is
    test_pipeline.py::test_pipelined_lm_trains)."""
    from tensorflow_distributed_tpu.train.loop import train

    cfg = TrainConfig(model="pipelined_lm", model_size="tiny",
                      dataset="synthetic", batch_size=32, train_steps=40,
                      eval_every=0, log_every=0, eval_batch_size=32,
                      compute_dtype="float32", learning_rate=3e-3,
                      dropout_rate=0.0, pipeline_schedule="1f1b",
                      mesh=MeshConfig(data=4, pipe=2))
    result = train(cfg)
    assert result.final_metrics["accuracy"] >= 0.35, result.final_metrics


@pytest.mark.slow
def test_pipelined_moe_aux_collected_and_schedules_agree(devices8):
    """The router-collapse trap (round-2 review weak #3): a pipelined MoE
    must NOT silently drop the load-balancing loss. Checks: (a) the
    collected aux is positive and reported by both schedules, (b) the
    two schedules agree on metrics AND updated params — GPipe gets the
    aux gradient from plain AD through pipeline_apply, so 1F1B matching
    its params proves the hand-seeded aux cotangents are right too,
    (c) the router (gate) gradient is nonzero, which is exactly what a
    dropped aux loss would zero out on a uniform-logit router."""
    from tensorflow_distributed_tpu.train.tasks import make_moe_loss

    mesh = make_mesh(MeshConfig(data=2, pipe=2), devices8[:4])
    model, _, batch = _setup(mesh, microbatches=4, moe_experts=4)
    # SGD, not Adam: updates are lr * grad, so param parity below is a
    # direct gradient-parity assertion (Adam's 1/sqrt(v) normalizer
    # amplifies float-order noise on near-zero-gradient elements).
    state = create_train_state(model, optax.sgd(1e-2),
                               np.zeros((2, 16), np.int32), mesh)
    moe_loss = make_moe_loss(0.01, 1e-3)
    step_g = make_train_step(mesh, loss=moe_loss,
                             batch_shardings=mlm_batch_shardings(mesh),
                             donate=False)
    step_f = make_1f1b_train_step(model, mesh, donate=False,
                                  moe_aux_weight=0.01,
                                  moe_zloss_weight=1e-3)
    st_g, met_g = step_g(state, batch)
    st_f, met_f = step_f(state, batch)
    assert float(met_g["aux_loss"]) > 0.0
    np.testing.assert_allclose(float(met_f["aux_loss"]),
                               float(met_g["aux_loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(met_f["z_loss"]),
                               float(met_g["z_loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(met_f["loss"]),
                               float(met_g["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-6, rtol=1e-4),
        st_g.params, st_f.params)
    # The gate moved: optimizer update implies a nonzero router grad.
    gate_before = state.params["blocks"]["moe_mlp"]["gate"]
    gate_after = st_f.params["blocks"]["moe_mlp"]["gate"]
    assert float(jnp.max(jnp.abs(
        nn_unbox(gate_after) - nn_unbox(gate_before)))) > 0.0


def nn_unbox(x):
    import flax.linen as nn
    return nn.meta.unbox(x)


@pytest.mark.slow
def test_pipelined_flash_attention_matches_xla(devices8, monkeypatch):
    """The Pallas kernel INSIDE the pipe shard_map: the attention
    dispatcher nests a shard_map over the auto (data/model) axes, so
    the Mosaic call sits in fully-manual axes (interpret mode off-TPU
    via TFD_FLASH_INTERPRET). Must reproduce the XLA-attention step:
    same loss, same updated params, PP x TP x DP mesh."""
    monkeypatch.setenv("TFD_FLASH_INTERPRET", "1")
    mesh = make_mesh(MeshConfig(data=2, pipe=2, model=2), devices8)
    models = {
        flash: pipelined_lm(mesh, num_microbatches=4, n_layers=4,
                            max_len=16, dropout_rate=0.0,
                            compute_dtype=jnp.float32, use_flash=flash)
        for flash in (True, False)}
    state = create_train_state(models[True], optax.sgd(1e-2),
                               np.zeros((2, 16), np.int32), mesh)
    ds = synthetic_clm(n=32, seq_len=16, vocab_size=64)
    batch = shard_batch(mesh, ds.batch(np.arange(16)), seq_axis=1)
    results = {}
    for flash, model in models.items():
        step = make_1f1b_train_step(model, mesh, donate=False)
        results[flash] = step(state, batch)
    np.testing.assert_allclose(float(results[True][1]["loss"]),
                               float(results[False][1]["loss"]),
                               rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4),
        results[True][0].params, results[False][0].params)


def test_pipelined_small_factory():
    """size="small" is the GPT-2-small flagship config (round-2 review
    weak #5 asked for exactly this); construction is lazy so this is
    cheap."""
    import jax as _jax
    mesh = make_mesh(MeshConfig(data=1, pipe=1), _jax.devices("cpu")[:1])
    m = pipelined_lm(mesh, size="small", num_microbatches=8)
    assert (m.cfg.n_layers, m.cfg.d_model, m.cfg.n_heads) == (12, 768, 12)
    assert m.cfg.use_flash and m.cfg.causal
    assert m.num_microbatches == 8


def test_bubble_fraction():
    assert bubble_fraction(8, 1, "gpipe") == 0.0
    assert bubble_fraction(8, 4, "gpipe") == pytest.approx(3 / 11)
    assert bubble_fraction(8, 4, "1f1b") == pytest.approx(6 / 14)
    # More microbatches shrink the bubble for both schedules.
    assert bubble_fraction(64, 4, "1f1b") < bubble_fraction(8, 4, "1f1b")
    with pytest.raises(ValueError, match="schedule"):
        bubble_fraction(8, 4, "interleaved")


def test_1f1b_config_validation():
    cfg = TrainConfig(pipeline_schedule="zigzag")
    with pytest.raises(ValueError, match="pipeline_schedule"):
        cfg.validate()
    cfg = TrainConfig(pipeline_backward="checkpointless")
    with pytest.raises(ValueError, match="pipeline_backward"):
        cfg.validate()
    # Reject silently-ignored combinations (GPipe's backward is AD;
    # non-pipelined families have no schedule at all).
    cfg = TrainConfig(model="pipelined_lm", pipeline_schedule="gpipe",
                      pipeline_backward="stash")
    with pytest.raises(ValueError, match="applies only"):
        cfg.validate()
    cfg = TrainConfig(model="gpt_lm", pipeline_backward="stash")
    with pytest.raises(ValueError, match="applies only"):
        cfg.validate()
    cfg = TrainConfig(model="pipelined_lm", pipeline_schedule="1f1b",
                      grad_accum_steps=2, batch_size=256)
    with pytest.raises(ValueError, match="accumulates"):
        cfg.validate()
    # The exclusion is gated on the pipelined model: other families
    # keep grad accumulation under the (now default) 1f1b setting.
    TrainConfig(model="gpt_lm", grad_accum_steps=2,
                batch_size=256).validate()
