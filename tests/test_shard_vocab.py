"""shard_vocab: Megatron vocab-parallel embedding (round-3 review
weak #6a — the docstring claimed a knob that didn't exist; now it does).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
from tensorflow_distributed_tpu.models import build_model
from tensorflow_distributed_tpu.parallel.mesh import make_mesh
from tensorflow_distributed_tpu.parallel.sharding import shard_batch
from tensorflow_distributed_tpu.train.state import create_train_state
from tensorflow_distributed_tpu.train.step import make_train_step
from tensorflow_distributed_tpu.train.tasks import (
    mlm_batch_shardings, mlm_loss)


def _one_step(mesh, **model_kw):
    from tensorflow_distributed_tpu.data.lm import synthetic_clm

    model = build_model("gpt_lm", mesh=mesh, size="tiny",
                        dropout_rate=0.0, compute_dtype=jnp.float32,
                        **model_kw)
    state = create_train_state(model, optax.adam(1e-2),
                               np.zeros((2, 16), np.int32), mesh, seed=0)
    ds = synthetic_clm(n=32, seq_len=16, vocab_size=64)
    batch = shard_batch(mesh, ds.batch(np.arange(16)), seq_axis=1)
    step = make_train_step(mesh, loss=mlm_loss,
                           batch_shardings=mlm_batch_shardings(mesh),
                           donate=False)
    new_state, met = step(state, batch)
    return state, new_state, met


def test_vocab_table_is_model_sharded(devices8):
    """The table's vocab dim actually lands on the "model" axis, and
    the step's math is unchanged vs the replicated layout."""
    mesh = make_mesh(MeshConfig(data=2, model=2, seq=2), devices8)
    state_s, new_s, met_s = _one_step(mesh, shard_vocab=True)
    spec = state_s.params["tok_emb"]["embedding"].sharding.spec
    assert tuple(spec) == ("model", None), spec

    state_r, new_r, met_r = _one_step(mesh, shard_vocab=False)
    spec_r = state_r.params["tok_emb"]["embedding"].sharding.spec
    assert tuple(spec_r) != ("model", None)
    np.testing.assert_allclose(float(met_s["loss"]), float(met_r["loss"]),
                               rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-6, rtol=1e-4),
        jax.device_get(new_s.params), jax.device_get(new_r.params))


def test_tied_sharded_logits_match(devices8):
    """Tied + sharded: the vocab-sharded tied einsum equals the
    replicated tied logits."""
    mesh = make_mesh(MeshConfig(data=2, model=4), devices8)
    _, new_s, met_s = _one_step(mesh, shard_vocab=True,
                                tie_embeddings=True)
    _, new_r, met_r = _one_step(mesh, shard_vocab=False,
                                tie_embeddings=True)
    np.testing.assert_allclose(float(met_s["loss"]), float(met_r["loss"]),
                               rtol=1e-5)


def test_shard_vocab_validation():
    TrainConfig(model="gpt_lm", shard_vocab=True).validate()
    with pytest.raises(ValueError, match="no effect"):
        TrainConfig(model="mnist_cnn", shard_vocab=True).validate()
    with pytest.raises(ValueError, match="pipelined_lm"):
        TrainConfig(model="pipelined_lm", shard_vocab=True).validate()
    with pytest.raises(ValueError, match="tp_partitioning"):
        from tensorflow_distributed_tpu.models.transformer import (
            CausalLM, tiny_config)
        cfg = tiny_config(causal=True, tp_partitioning=False,
                          shard_vocab=True)
        CausalLM(cfg, None).init(jax.random.key(0),
                                 np.zeros((2, 16), np.int32))
