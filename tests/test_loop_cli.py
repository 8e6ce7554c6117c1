"""End-to-end loop + CLI tests: the accuracy-bar integration test the
reference performed by hand (SURVEY.md §4 "accuracy-as-test")."""

import jax
import numpy as np
import pytest

from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
from tensorflow_distributed_tpu.train.loop import train
from tests.conftest import FIXTURE_DIR


def _cfg(**kw):
    base = dict(dataset="synthetic", batch_size=128, train_steps=40,
                eval_every=0, log_every=0, eval_batch_size=128,
                compute_dtype="float32", mesh=MeshConfig(data=8))
    base.update(kw)
    return TrainConfig(**base)


@pytest.mark.slow
def test_train_reaches_accuracy_bar():
    """The integration bar: the loop must reach high accuracy on the
    synthetic digits within a small budget (the analog of the
    reference's 95.75%-at-120-steps ceiling, performance:6 — which our
    'improved' init scheme beats by design)."""
    result = train(_cfg(train_steps=60))
    assert result.final_metrics["accuracy"] >= 0.97
    assert int(jax.device_get(result.state.step)) == 60
    assert result.images_per_sec > 0


def test_train_on_fixture_real_bytes_reaches_bar():
    """DEFAULT-TIER accuracy bar on REAL idx bytes (round-3 review item
    4): train end-to-end on the committed fixture — real on-disk
    idx1/idx3 files through the full parser/batcher/loop path, not
    synthetic arrays handed past it — and demand a fixture-appropriate
    accuracy (round 4 recorded 100% at step 75, batch 64, from this
    exact path)."""
    from tensorflow_distributed_tpu.data import load_dataset

    # Guard the guard: load_dataset falls back to synthetic digits on
    # missing files (which would also pass the bar) — prove the
    # fixture actually loads as real mnist before training on it.
    train_ds, _, _ = load_dataset("mnist", FIXTURE_DIR,
                                  validation_size=64)
    assert train_ds.name == "mnist", train_ds.name
    cfg = _cfg(dataset="mnist", data_dir=FIXTURE_DIR,
               validation_size=64, batch_size=64, train_steps=50,
               eval_every=0, eval_batch_size=64, learning_rate=2e-3)
    result = train(cfg)
    assert result.final_metrics["accuracy"] >= 0.95, result.final_metrics


@pytest.mark.slow
def test_train_resume_roundtrip(tmp_path):
    cfg = _cfg(train_steps=10, checkpoint_dir=str(tmp_path),
               checkpoint_every=5)
    r1 = train(cfg)
    cfg2 = _cfg(train_steps=14, checkpoint_dir=str(tmp_path),
                checkpoint_every=5, resume=True)
    r2 = train(cfg2)
    assert int(jax.device_get(r2.state.step)) == 14


def test_train_resume_roundtrip_async_checkpoints(tmp_path):
    """checkpoint_async=True: cadence saves overlap training, the loop
    flushes the writer on exit, and resume lands on the same step.

    Runs in a SUBPROCESS: concurrent device_put (prefetch thread) +
    dispatch + the background writer thread intermittently SIGSEGVs
    the XLA:CPU runtime on the CI container — reproducible on the
    untouched seed tree — and an in-process crash aborts the whole
    pytest run. Isolation turns a host-runtime crash into a plain
    failure; one retry absorbs the known flake (a real regression in
    the checkpoint logic fails both attempts deterministically).
    """
    import subprocess
    import sys

    script = """
import jax
from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
from tensorflow_distributed_tpu.train import checkpoint as ckpt
from tensorflow_distributed_tpu.train.loop import train

def cfg(**kw):
    base = dict(dataset="synthetic", batch_size=128, train_steps=40,
                eval_every=0, log_every=0, eval_batch_size=128,
                compute_dtype="float32", mesh=MeshConfig(data=8))
    base.update(kw)
    return TrainConfig(**base)

d = %r
train(cfg(train_steps=10, checkpoint_dir=d, checkpoint_every=5,
          checkpoint_async=True))
assert ckpt.latest_step(d) == 10  # flushed before return
r2 = train(cfg(train_steps=14, checkpoint_dir=d, checkpoint_every=5,
               checkpoint_async=True, resume=True))
assert int(jax.device_get(r2.state.step)) == 14
print("ASYNC_RESUME_OK")
""" % str(tmp_path)
    for attempt in (1, 2):
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True,
                              timeout=300)
        if proc.returncode == 0:
            assert "ASYNC_RESUME_OK" in proc.stdout
            return
        if proc.returncode >= 0:  # real assertion/exception: no retry
            break
    raise AssertionError(
        f"async resume subprocess failed (rc={proc.returncode}):\n"
        f"{proc.stdout}\n{proc.stderr[-2000:]}")


def test_eval_only_mode(tmp_path):
    """mode=eval restores the checkpoint and reproduces the training
    run's final validation metrics without a single training step.
    (Cross-mesh-shape restore itself is pinned in
    test_checkpoint.test_restore_across_mesh_shapes.)"""
    from tensorflow_distributed_tpu.train.loop import evaluate_only

    cfg = _cfg(train_steps=10, checkpoint_dir=str(tmp_path),
               checkpoint_every=0, eval_every=10)
    r = train(cfg)

    m8 = evaluate_only(_cfg(mode="eval", checkpoint_dir=str(tmp_path)))
    for k, v in r.final_metrics.items():
        np.testing.assert_allclose(m8[k], v, rtol=1e-5, atol=1e-6)

    with pytest.raises(ValueError, match="mode=eval"):
        _cfg(mode="eval").validate()


def test_grad_norm_metric_opt_in():
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.models.cnn import MnistCNN
    from tensorflow_distributed_tpu.parallel.sharding import shard_batch
    from tensorflow_distributed_tpu.train.state import create_train_state
    from tensorflow_distributed_tpu.train.step import make_train_step
    import jax.numpy as jnp
    import numpy as np
    import optax

    mesh = make_mesh(MeshConfig(data=8))
    model = MnistCNN(dropout_rate=0.0, compute_dtype=jnp.float32)
    state = create_train_state(model, optax.adam(1e-3),
                               jnp.zeros((2, 28, 28, 1), jnp.float32), mesh)
    batch = shard_batch(mesh, (
        np.random.default_rng(0).normal(size=(32, 28, 28, 1)).astype(
            np.float32),
        np.random.default_rng(0).integers(0, 10, size=(32,)).astype(
            np.int32)))
    _, m_off = make_train_step(mesh, donate=False)(state, batch)
    assert "grad_norm" not in m_off  # default dicts stay stable
    _, m_on = make_train_step(mesh, donate=False,
                              grad_norm_metric=True)(state, batch)
    gn = float(m_on["grad_norm"])
    assert np.isfinite(gn) and gn > 0


def test_halt_on_nonfinite_raises():
    cfg = _cfg(train_steps=20, log_every=1, halt_on_nonfinite=True,
               learning_rate=1e38)
    with pytest.raises(FloatingPointError, match="non-finite"):
        train(cfg)


def test_performance_table_emitted():
    result = train(_cfg(train_steps=10, eval_every=5))
    table = result.logger.performance_table(1e-3)
    lines = table.splitlines()
    assert lines[0].startswith("Steps,")
    assert len(lines) >= 3  # header + 2 eval rows


@pytest.mark.slow
def test_cli_main_runs():
    from tensorflow_distributed_tpu.cli import main
    rc = main(["--dataset", "synthetic", "--train-steps", "5",
               "--batch-size", "64", "--eval-every", "0",
               "--log-every", "0", "--eval-batch-size", "64",
               "--compute-dtype", "float32"])
    assert rc == 0


def _load_graft_entry():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "__graft_entry__.py")
    spec = importlib.util.spec_from_file_location("__graft_entry__", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_graft_entry_single():
    mod = _load_graft_entry()
    fn, args = mod.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 10)


@pytest.mark.slow
def test_graft_entry_multichip():
    _load_graft_entry().dryrun_multichip(8)


def test_first_step_hits_log_and_checkpoint_cadence(tmp_path):
    """The warm-up compile step is still step 1: with log_every=1 and
    checkpoint_every=1 it must be logged and checkpointed."""
    cfg = _cfg(train_steps=3, log_every=1, checkpoint_dir=str(tmp_path),
               checkpoint_every=1)
    result = train(cfg)
    logged_steps = [r.step for r in result.logger.records]
    assert 1 in logged_steps
    from tensorflow_distributed_tpu.train import checkpoint as ckpt
    assert 1 in ckpt.available_steps(str(tmp_path))


@pytest.mark.slow
def test_resume_continues_sample_stream():
    """A resumed run must consume the same batches an uninterrupted run
    would have (data-stream fast-forward on resume)."""
    from tensorflow_distributed_tpu.data.mnist import Dataset, ShardedBatcher
    import numpy as np
    ds = Dataset(np.zeros((64, 1, 1, 1), np.float32),
                 np.arange(64, dtype=np.int32))
    b = ShardedBatcher(ds, 16, seed=1)
    stream = b.forever()
    full = [next(stream)[1] for _ in range(10)]
    resumed = b.forever(start_step=6)
    tail = [next(resumed)[1] for _ in range(4)]
    for a, c in zip(full[6:], tail):
        np.testing.assert_array_equal(a, c)
