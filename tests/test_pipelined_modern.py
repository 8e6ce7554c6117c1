"""Pipelined LM with the modern knobs: RoPE and weight tying.

Round-3 review weak #3: these were hard-errored walls with soft
justifications — positions are microbatch-invariant (microbatches
slice batch, not sequence) and both tok_emb and lm_head live in the
same shell module. These tests pin that the walls are genuinely down:
the pipelined forward equals the non-pipelined CausalLM with the SAME
weights, and both schedules (GPipe AD / hand-rolled 1F1B) agree.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
from tensorflow_distributed_tpu.data.lm import synthetic_clm
from tensorflow_distributed_tpu.models.pipelined import pipelined_lm
from tensorflow_distributed_tpu.models.transformer import CausalLM
from tensorflow_distributed_tpu.parallel.mesh import make_mesh
from tensorflow_distributed_tpu.parallel.pipeline import stack_stage_params
from tensorflow_distributed_tpu.parallel.sharding import shard_batch
from tensorflow_distributed_tpu.train.pipeline_step import (
    make_1f1b_train_step)
from tensorflow_distributed_tpu.train.state import create_train_state
from tensorflow_distributed_tpu.train.step import make_train_step
from tensorflow_distributed_tpu.train.tasks import (
    mlm_batch_shardings, mlm_loss)

MODERN = dict(pos_emb="rope", tie_embeddings=True, n_layers=4,
              max_len=16, dropout_rate=0.0, compute_dtype=jnp.float32)


def _remap_to_pipelined(seq_params, n_layers, stages, tied):
    """CausalLM param tree -> PipelinedLM {shell, blocks} tree with the
    SAME weights (layer_i leaves stacked [S, layers_per_stage, ...])."""
    shell = {"tok_emb": seq_params["tok_emb"], "ln_f": seq_params["ln_f"]}
    if "pos_emb" in seq_params:
        shell["pos_emb"] = seq_params["pos_emb"]
    if not tied:
        shell["lm_head"] = seq_params["lm_head"]
    layers = [seq_params[f"layer_{i}"] for i in range(n_layers)]
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *layers)
    return {"params": {"shell": shell,
                       "blocks": stack_stage_params(stacked, stages)}}


@pytest.mark.parametrize("knobs", [
    dict(pos_emb="rope"),
    dict(tie_embeddings=True),
    dict(pos_emb="rope", tie_embeddings=True, mlp_variant="swiglu",
         norm="rmsnorm", n_kv_heads=2),  # the full Llama-shaped stack
])
def test_pipelined_forward_matches_causal_lm(devices8, knobs):
    """Pipelined logits == CausalLM logits with identical weights —
    the schedule is a layout, not a model change."""
    from tensorflow_distributed_tpu.models.transformer import tiny_config

    cfg = tiny_config(causal=True, tp_partitioning=False, n_layers=4,
                      max_len=16, dropout_rate=0.0,
                      compute_dtype=jnp.float32, use_flash=False, **knobs)
    mesh = make_mesh(MeshConfig(data=2, pipe=4), devices8)
    tokens = np.arange(8 * 16, dtype=np.int32).reshape(8, 16) % 64

    seq_model = CausalLM(cfg, None)
    seq_vars = seq_model.init(jax.random.key(0), tokens)
    want = seq_model.apply(seq_vars, tokens)

    pipe_model = pipelined_lm(
        mesh, use_flash=False, n_layers=4, max_len=16,
        dropout_rate=0.0, compute_dtype=jnp.float32, **knobs)
    pipe_vars = _remap_to_pipelined(
        seq_vars["params"], 4, 4, tied=knobs.get("tie_embeddings", False))
    got = jax.jit(lambda v, t: pipe_model.apply(v, t))(pipe_vars, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


def test_1f1b_matches_gpipe_with_rope_and_tying(devices8):
    """Schedule parity holds for the modern stack too: 1F1B's
    hand-rolled backward must reproduce GPipe-by-AD gradients when the
    head is the tied embedding (its gradient now has BOTH an
    embed-path and a head-path contribution)."""
    mesh = make_mesh(MeshConfig(data=2, pipe=4), devices8)
    model = pipelined_lm(mesh, num_microbatches=8, use_flash=False,
                         **MODERN)
    state = create_train_state(model, optax.adam(1e-2),
                               np.zeros((2, 16), np.int32), mesh)
    ds = synthetic_clm(n=32, seq_len=16, vocab_size=64)
    batch = shard_batch(mesh, ds.batch(np.arange(16)), seq_axis=1)
    step_g = make_train_step(mesh, loss=mlm_loss,
                             batch_shardings=mlm_batch_shardings(mesh),
                             donate=False, grad_norm_metric=True)
    step_f = make_1f1b_train_step(model, mesh, donate=False,
                                  grad_norm_metric=True)
    st_g, met_g = step_g(state, batch)
    st_f, met_f = step_f(state, batch)
    np.testing.assert_allclose(float(met_f["loss"]),
                               float(met_g["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(met_f["grad_norm"]),
                               float(met_g["grad_norm"]), rtol=1e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-6, rtol=1e-4),
        st_g.params, st_f.params)


def test_pipelined_ring_attention_parity(devices8):
    """Ring attention INSIDE the pipeline (round-4 review item 3): on a
    data=2 x pipe=2 x seq=2 mesh the Block routes seq-sharded
    activations to ring_attention, whose shard_map nests over the
    remaining auto axes inside the pipe-manual region. The pipelined
    forward must equal the non-pipelined CausalLM with identical
    weights — the two flagship axes (long-context SP and pipeline)
    finally composing."""
    from tensorflow_distributed_tpu.models.transformer import tiny_config

    cfg = tiny_config(causal=True, tp_partitioning=False, n_layers=4,
                      max_len=16, dropout_rate=0.0,
                      compute_dtype=jnp.float32, use_flash=False,
                      pos_emb="rope")
    mesh = make_mesh(MeshConfig(data=2, pipe=2, seq=2), devices8)
    tokens = np.arange(8 * 16, dtype=np.int32).reshape(8, 16) % 64

    seq_model = CausalLM(cfg, None)
    seq_vars = seq_model.init(jax.random.key(0), tokens)
    want = seq_model.apply(seq_vars, tokens)

    pipe_model = pipelined_lm(
        mesh, use_flash=False, n_layers=4, max_len=16, dropout_rate=0.0,
        compute_dtype=jnp.float32, pos_emb="rope")
    pipe_vars = _remap_to_pipelined(seq_vars["params"], 4, 2, tied=False)
    with mesh:
        sharded = shard_batch(mesh, {"t": tokens}, seq_axis=1)["t"]
        got = jax.jit(lambda v, t: pipe_model.apply(v, t))(
            pipe_vars, sharded)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


def test_pipelined_ring_1f1b_matches_gpipe(devices8):
    """The hand-rolled 1F1B backward differentiates through the nested
    ring shard_map (ppermute transposes to the reverse rotation): both
    schedules agree on loss, grad norm, and updated params on the
    pipe x seq mesh."""
    mesh = make_mesh(MeshConfig(data=2, pipe=2, seq=2), devices8)
    model = pipelined_lm(mesh, num_microbatches=4, use_flash=False,
                         **MODERN)
    state = create_train_state(model, optax.adam(1e-2),
                               np.zeros((2, 16), np.int32), mesh)
    ds = synthetic_clm(n=32, seq_len=16, vocab_size=64)
    batch = shard_batch(mesh, ds.batch(np.arange(16)), seq_axis=1)
    step_g = make_train_step(mesh, loss=mlm_loss,
                             batch_shardings=mlm_batch_shardings(mesh),
                             donate=False, grad_norm_metric=True)
    step_f = make_1f1b_train_step(model, mesh, donate=False,
                                  grad_norm_metric=True)
    st_g, met_g = step_g(state, batch)
    st_f, met_f = step_f(state, batch)
    np.testing.assert_allclose(float(met_f["loss"]),
                               float(met_g["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(met_f["grad_norm"]),
                               float(met_g["grad_norm"]), rtol=1e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-6, rtol=1e-4),
        st_g.params, st_f.params)


def test_config_accepts_pipelined_modern_knobs():
    """The round-3 validation walls are gone: rope + tying + pipelined
    is a legal TrainConfig."""
    TrainConfig(model="pipelined_lm", pos_emb="rope",
                tie_embeddings=True, rope_theta=500000.0).validate()


@pytest.mark.slow
def test_pipelined_modern_trains_end_to_end(devices8):
    """Full loop: pipelined Llama-shaped tiny model (rope + tied +
    swiglu + rmsnorm) learns the synthetic progression above chance."""
    from tensorflow_distributed_tpu.train.loop import train

    cfg = TrainConfig(model="pipelined_lm", model_size="tiny",
                      dataset="synthetic", batch_size=32, train_steps=40,
                      eval_every=0, log_every=0, eval_batch_size=32,
                      compute_dtype="float32", learning_rate=3e-3,
                      dropout_rate=0.0, pos_emb="rope",
                      tie_embeddings=True, mlp_variant="swiglu",
                      norm="rmsnorm", pipeline_schedule="1f1b",
                      mesh=MeshConfig(data=4, pipe=2))
    result = train(cfg)
    assert result.final_metrics["accuracy"] >= 0.35, result.final_metrics
