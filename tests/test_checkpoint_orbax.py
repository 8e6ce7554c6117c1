"""Orbax checkpoint backend: sharded saves, auto-detected restores.

The native backend allgathers cross-process-sharded leaves to the
chief's host before writing (documented in train/checkpoint.py as fine
for this framework's sizes, with orbax named as the scale path). This
pins that path: every process writes its own shards (no allgather),
restore reads shards directly into the template's shardings, and
--resume auto-detects which backend wrote the checkpoint.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
from tensorflow_distributed_tpu.models.cnn import MnistCNN
from tensorflow_distributed_tpu.train import checkpoint as ckpt
from tensorflow_distributed_tpu.train.state import create_train_state


def _state(mesh, fsdp=False, seed=0):
    model = MnistCNN(dropout_rate=0.0, compute_dtype=jnp.float32)
    return create_train_state(model, optax.adam(1e-3),
                              jnp.zeros((2, 28, 28, 1), jnp.float32),
                              mesh, seed, fsdp=fsdp)


@pytest.mark.parametrize("fsdp", [False, True])
def test_orbax_roundtrip_matches_native(tmp_path, mesh8, fsdp):
    """Same state through both backends: identical restored values,
    including FSDP-sharded params (orbax reads shards straight into
    the sharded template — the allgather-free path)."""
    state = _state(mesh8, fsdp=fsdp)
    state = state.replace(step=jnp.asarray(7, jnp.int32))
    ckpt.save(str(tmp_path / "native"), state)
    ckpt.save(str(tmp_path / "orbax"), state, backend="orbax")
    assert ckpt.latest_step(str(tmp_path / "orbax")) == 7

    tmpl = _state(mesh8, fsdp=fsdp, seed=1)
    r_native = ckpt.restore(str(tmp_path / "native"), tmpl)
    r_orbax = ckpt.restore(str(tmp_path / "orbax"), tmpl)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        jax.device_get(ckpt._fetch_host(r_native.params)),
        jax.device_get(ckpt._fetch_host(r_orbax.params)))
    if fsdp:
        # The restored leaves keep the template's FSDP shardings.
        leaf = jax.tree_util.tree_leaves(r_orbax.params)[0]
        assert leaf.sharding == jax.tree_util.tree_leaves(
            tmpl.params)[0].sharding


def test_orbax_end_to_end_resume_and_prune(tmp_path):
    """The full loop on the orbax backend: cadence saves, keep-N
    pruning, resume (auto-detected format), exact parity with an
    uninterrupted run."""
    from tensorflow_distributed_tpu.train.loop import train

    base = dict(dataset="synthetic", batch_size=64, eval_every=0,
                log_every=0, eval_batch_size=128,
                compute_dtype="float32", dropout_rate=0.0,
                checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2,
                checkpoint_backend="orbax", keep_checkpoints=2,
                mesh=MeshConfig(data=8), seed=0)
    train(TrainConfig(**base, train_steps=6))
    steps = ckpt.available_steps(str(tmp_path / "ck"))
    assert steps == [4, 6]  # keep-N pruned 2

    r = train(TrainConfig(**base, train_steps=8, resume=True))
    assert int(jax.device_get(r.state.step)) == 8

    single = train(TrainConfig(
        dataset="synthetic", batch_size=64, train_steps=8, eval_every=0,
        log_every=0, eval_batch_size=128, compute_dtype="float32",
        dropout_rate=0.0, mesh=MeshConfig(data=8), seed=0))
    for k, v in single.final_metrics.items():
        np.testing.assert_allclose(r.final_metrics[k], v, rtol=1e-4,
                                   atol=1e-5)


def test_orbax_validation_walls():
    with pytest.raises(ValueError, match="checkpoint_backend"):
        TrainConfig(checkpoint_backend="s3", batch_size=32).validate()
    # The r4 wall is gone: local SGD composes with the orbax backend
    # (restore_averaged auto-detects the OCDBT layout — round-4 review
    # item 7).
    TrainConfig(checkpoint_backend="orbax", param_sync_every=2,
                batch_size=32, mesh=MeshConfig(data=8)).validate()


def test_orbax_local_sgd_restore_averaged(tmp_path, mesh8):
    """Local SGD's replica-stacked state round-trips through the orbax
    backend AND restore_averaged reads the OCDBT layout into a PLAIN
    template (the two r4 marquee features no longer exclude each
    other). The averaged restore must equal averaged_view of the live
    state."""
    from tensorflow_distributed_tpu.train.local_sgd import (
        averaged_view, stack_state)

    state = _state(mesh8)
    stacked = stack_state(state, mesh8)
    # Make replicas visibly distinct so the mean is a real check.
    stacked = stacked.replace(params=jax.tree_util.tree_map(
        lambda p: p + jnp.arange(p.shape[0], dtype=p.dtype).reshape(
            (-1,) + (1,) * (p.ndim - 1)), stacked.params))
    ckpt.save(str(tmp_path), stacked, backend="orbax")

    tmpl = _state(mesh8, seed=1)
    restored = ckpt.restore_averaged(str(tmp_path), tmpl)
    want = averaged_view(stacked)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6),
        jax.device_get(restored.params), jax.device_get(want.params))
    # Template shardings won: the restored state lives plain.
    assert jax.tree_util.tree_leaves(restored.params)[0].shape == \
        jax.tree_util.tree_leaves(tmpl.params)[0].shape


def test_unmarked_orbax_dir_never_shadows_previous(tmp_path, mesh8):
    """Crash-mid-save atomicity: an orbax step dir WITHOUT the commit
    marker (what a crash leaves behind — the marker lands only after
    orbax confirms the write) is invisible to available_steps, so
    --resume falls back to the intact previous checkpoint instead of
    failing on debris; pruning is deferred to the same marker phase,
    so a failed save can never have deleted the last good one."""
    import os

    state = _state(mesh8)
    ckpt.save(str(tmp_path), state.replace(step=jnp.asarray(3)),
              backend="orbax")
    assert ckpt.latest_step(str(tmp_path)) == 3
    # Simulate the crash: a step-5 dir exists but the commit marker
    # does not (strip it after a real save to get realistic debris).
    ckpt.save(str(tmp_path), state.replace(step=jnp.asarray(5)),
              backend="orbax")
    os.remove(str(tmp_path / "step_00000005" / "ORBAX_COMMITTED"))
    assert ckpt.latest_step(str(tmp_path)) == 3
    restored = ckpt.restore(str(tmp_path), _state(mesh8, seed=1))
    assert int(jax.device_get(restored.step)) == 3


def test_orbax_ema_toggle_across_restore(tmp_path, mesh8):
    """The EMA on/off flip across an orbax save/restore mirrors the
    native contract: newly-enabled EMA seeds from the restored params;
    newly-disabled EMA drops the saved average."""
    model = MnistCNN(dropout_rate=0.0, compute_dtype=jnp.float32)

    def mk(ema, seed=0):
        return create_train_state(model, optax.adam(1e-3),
                                  jnp.zeros((2, 28, 28, 1), jnp.float32),
                                  mesh8, seed, ema=ema)

    ckpt.save(str(tmp_path / "no_ema"), mk(False), backend="orbax")
    on = ckpt.restore(str(tmp_path / "no_ema"), mk(True, seed=1))
    assert on.ema is not None
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        jax.device_get(on.ema), jax.device_get(on.params))

    ckpt.save(str(tmp_path / "with_ema"), mk(True), backend="orbax")
    off = ckpt.restore(str(tmp_path / "with_ema"), mk(False, seed=1))
    assert off.ema is None
