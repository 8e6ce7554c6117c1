"""FSDP (ZeRO-style) param/optimizer sharding over the data axis.

The reference kept ONE full copy of the weights (on the ps CPU,
mnist_python_m.py:177) and streamed it to every worker every step;
plain SPMD data parallelism keeps a full copy on EVERY device. FSDP
(param_partition="fsdp") is the third point: each data-parallel device
holds 1/N of every large tensor and its Adam slots, and GSPMD inserts
the all-gather/reduce-scatter pair — same math, proven here by exact
parity with the replicated layout on the same batches.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
from tensorflow_distributed_tpu.models.cnn import MnistCNN
from tensorflow_distributed_tpu.parallel.mesh import make_mesh
from tensorflow_distributed_tpu.parallel.sharding import shard_batch
from tensorflow_distributed_tpu.train.state import create_train_state
from tensorflow_distributed_tpu.train.step import make_train_step


def _model():
    return MnistCNN(dropout_rate=0.0, compute_dtype=jnp.float32)


def _state(mesh, fsdp):
    x = jnp.zeros((2, 28, 28, 1), jnp.float32)
    return create_train_state(_model(), optax.adam(1e-3), x, mesh,
                              seed=0, fsdp=fsdp)


def _batch(n=32, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, 28, 28, 1)).astype(np.float32),
            rng.integers(0, 10, size=(n,)).astype(np.int32))


def _shard_fractions(tree):
    """leaf path -> local shard elements / global elements."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if not hasattr(leaf, "addressable_shards") or leaf.ndim == 0:
            continue
        local = leaf.addressable_shards[0].data.size
        out[jax.tree_util.keystr(path)] = local / leaf.size
    return out


def test_fsdp_shards_large_params_and_slots(mesh8):
    state = _state(mesh8, fsdp=True)
    pf = _shard_fractions(state.params)
    # The big tensors live 1/8-sharded; small ones stay replicated.
    sharded = {k for k, f in pf.items() if f == 1 / 8}
    assert any("fc1" in k and "kernel" in k for k in sharded), pf
    assert all(f == 1.0 for k, f in pf.items() if "bias" in k), pf
    # Adam m/v mirror the param placement (train.state slot matching).
    of = _shard_fractions(state.opt_state)
    assert any(f == 1 / 8 for f in of.values()), of


def test_fsdp_exact_parity_with_replicated(mesh8):
    """Same seed, same batches: fsdp and replicated layouts are the
    same training run — GSPMD's gather/scatter changes layout, not
    math."""
    s_rep = _state(mesh8, fsdp=False)
    s_fsdp = _state(mesh8, fsdp=True)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), s_rep.params, s_fsdp.params)

    step = make_train_step(mesh8, donate=False)
    for i in range(3):
        batch = shard_batch(mesh8, _batch(seed=i))
        s_rep, m_rep = step(s_rep, batch)
        s_fsdp, m_fsdp = step(s_fsdp, batch)
        np.testing.assert_allclose(float(m_rep["loss"]),
                                   float(m_fsdp["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
        s_rep.params, s_fsdp.params)
    assert int(s_fsdp.step) == 3


def test_zero1_shards_slots_only_with_exact_parity(mesh8):
    """ZeRO-1 (param_partition=\"zero1\"): params replicated, Adam m/v
    sharded over data — same training run as fully-replicated."""
    x = jnp.zeros((2, 28, 28, 1), jnp.float32)
    s_z1 = create_train_state(_model(), optax.adam(1e-3), x, mesh8,
                              seed=0, opt_fsdp=True)
    pf = _shard_fractions(s_z1.params)
    assert all(f == 1.0 for f in pf.values()), pf  # params replicated
    of = _shard_fractions(s_z1.opt_state)
    assert any(f == 1 / 8 for f in of.values()), of  # slots sharded

    s_rep = _state(mesh8, fsdp=False)
    step = make_train_step(mesh8, donate=False)
    step_z1 = make_train_step(
        mesh8, donate=False,
        params_out_shardings=jax.tree_util.tree_map(
            lambda a: a.sharding, s_z1.params))
    for i in range(3):
        batch = shard_batch(mesh8, _batch(seed=i))
        s_rep, m_rep = step(s_rep, batch)
        s_z1, m_z1 = step_z1(s_z1, batch)
        np.testing.assert_allclose(float(m_rep["loss"]),
                                   float(m_z1["loss"]), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=5e-6),
        s_rep.params, s_z1.params)
    # The defining layout invariant HOLDS THROUGH TRAINING: params are
    # still replicated after 3 steps (GSPMD would otherwise propagate
    # the slot sharding into them), slots still sharded.
    assert all(f == 1.0 for f in _shard_fractions(s_z1.params).values())
    assert any(f == 1 / 8
               for f in _shard_fractions(s_z1.opt_state).values())


def test_fsdp_composes_with_tensor_parallel(devices8):
    """On a data=4 x model=2 mesh, TP-annotated dims keep their axis
    and FSDP takes a *different* dim — both appear in the sharding."""
    from tensorflow_distributed_tpu.models.transformer import (
        BertMLM, tiny_config)
    from tensorflow_distributed_tpu.train.tasks import (
        mlm_batch_shardings, mlm_loss)
    from tensorflow_distributed_tpu.data.lm import LmBatcher, synthetic_mlm

    mesh = make_mesh(MeshConfig(data=4, model=2), devices8)
    model = BertMLM(tiny_config(max_len=32), mesh)
    sample = np.zeros((2, 32), np.int32)
    # tiny-config tensors sit below the production FSDP_MIN_SIZE
    # threshold; lower it so the composition logic is exercised.
    state = create_train_state(model, optax.adam(3e-3), sample, mesh,
                               seed=0, fsdp=True, fsdp_min_size=1024)
    specs = {
        jax.tree_util.keystr(p): leaf.sharding.spec
        for p, leaf in jax.tree_util.tree_flatten_with_path(
            state.params)[0]}
    both = [s for s in specs.values()
            if "data" in jax.tree_util.tree_leaves(tuple(s))
            and "model" in jax.tree_util.tree_leaves(tuple(s))]
    assert both, specs

    step = make_train_step(mesh, loss=mlm_loss,
                           batch_shardings=mlm_batch_shardings(mesh),
                           donate=False)
    ds = synthetic_mlm(n=64, seq_len=32, vocab_size=64, seed=0)
    batch = shard_batch(
        mesh, LmBatcher(ds, 16, 0).forever(0).__next__(), seq_axis=1)
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2.step) == 1


def test_fsdp_checkpoint_roundtrip(mesh8, tmp_path):
    from tensorflow_distributed_tpu.train import checkpoint as ckpt

    state = _state(mesh8, fsdp=True)
    step = make_train_step(mesh8, donate=False)
    state, _ = step(state, shard_batch(mesh8, _batch()))
    ckpt.save(str(tmp_path), state)

    fresh = _state(mesh8, fsdp=True)
    restored = ckpt.restore(str(tmp_path), fresh)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), state.params, restored.params)
    # Restored leaves keep the FSDP placement of the template.
    assert _shard_fractions(restored.params) == _shard_fractions(
        state.params)


def test_zero1_pipelined_1f1b_exact_parity(devices8):
    """ZeRO-1 composes with the hand-scheduled 1F1B pipeline (round-4
    review item 2): optimizer slots are consumed in tx.update OUTSIDE the
    pipe shard_map, so sharding them over "data" must not change the
    training run. Pinned: (a) slots data-sharded while params keep the
    pipe-only layout, (b) exact parity with the replicated layout over
    3 steps, (c) both layout invariants HOLD THROUGH TRAINING (the
    params_out_shardings constraint is what stops GSPMD propagating
    the slot sharding into the params)."""
    from tensorflow_distributed_tpu.data.lm import synthetic_clm
    from tensorflow_distributed_tpu.models.pipelined import pipelined_lm
    from tensorflow_distributed_tpu.train.pipeline_step import (
        make_1f1b_train_step)
    from tensorflow_distributed_tpu.train.tasks import mlm_batch_shardings

    mesh = make_mesh(MeshConfig(data=2, pipe=2), devices8[:4])
    model = pipelined_lm(mesh, num_microbatches=4, n_layers=4,
                         max_len=16, dropout_rate=0.0, use_flash=False,
                         compute_dtype=jnp.float32)
    sample = np.zeros((2, 16), np.int32)
    s_rep = create_train_state(model, optax.adam(1e-2), sample, mesh,
                               seed=0)
    s_z1 = create_train_state(model, optax.adam(1e-2), sample, mesh,
                              seed=0, opt_fsdp=True, fsdp_min_size=1024)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), s_rep.params, s_z1.params)
    # (a) slots sharded over data; params identical placement to rep.
    assert any(f < 1.0 for f in _shard_fractions(s_z1.opt_state).values())
    param_layout = _shard_fractions(s_z1.params)
    assert param_layout == _shard_fractions(s_rep.params)

    ds = synthetic_clm(n=64, seq_len=16, vocab_size=64)
    pos = jax.tree_util.tree_map(lambda a: a.sharding, s_z1.params)
    step = make_1f1b_train_step(model, mesh, donate=False,
                                batch_shardings=mlm_batch_shardings(mesh))
    step_z1 = make_1f1b_train_step(model, mesh, donate=False,
                                   batch_shardings=mlm_batch_shardings(mesh),
                                   params_out_shardings=pos)
    for i in range(3):
        batch = shard_batch(mesh, ds.batch(np.arange(i * 16, i * 16 + 16)),
                            seq_axis=1)
        s_rep, m_rep = step(s_rep, batch)
        s_z1, m_z1 = step_z1(s_z1, batch)
        np.testing.assert_allclose(float(m_rep["loss"]),
                                   float(m_z1["loss"]), rtol=1e-5)
    # (b) same params after 3 steps. atol covers Adam's 1/sqrt(v)
    # amplifying reduction-order float noise: the slot-sharded update
    # legitimately reassociates the moment math per data slice
    # (measured max |diff| ~1.2e-5 over 3 steps on the CPU mesh).
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=3e-5),
        s_rep.params, s_z1.params)
    # (c) layouts held: params pipe-only, slots still data-sharded.
    assert _shard_fractions(s_z1.params) == param_layout
    assert any(f < 1.0 for f in _shard_fractions(s_z1.opt_state).values())


def test_zero1_pipelined_cli_end_to_end(devices8):
    """--param-partition zero1 --model pipelined_lm trains through the
    full loop (the config wall narrowed to fsdp, round-4 review item 2)."""
    from tensorflow_distributed_tpu.train.loop import train

    cfg = TrainConfig(model="pipelined_lm", model_size="tiny",
                      dataset="synthetic", batch_size=16, train_steps=3,
                      eval_every=0, log_every=0, eval_batch_size=16,
                      compute_dtype="float32", pipeline_schedule="1f1b",
                      param_partition="zero1",
                      mesh=MeshConfig(data=4, pipe=2))
    cfg.validate()
    result = train(cfg)
    assert np.isfinite(result.final_metrics["loss"])


def test_config_rejects_fsdp_pipelined():
    cfg = TrainConfig(model="pipelined_lm", model_size="tiny",
                      param_partition="fsdp",
                      mesh=MeshConfig(data=1, pipe=2))
    with pytest.raises(ValueError, match="fsdp"):
        cfg.validate()
    with pytest.raises(ValueError, match="param_partition"):
        TrainConfig(param_partition="zero9").validate()
