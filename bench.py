"""Headline benchmark: MNIST-CNN training throughput per chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Baseline: the reference's only recorded numbers (`performance:2-6`,
mirrored in BASELINE.md) give ~0.205 global steps/s at 256 images per
sync step => ~52 images/s AGGREGATE across its whole 1-ps + 2-worker
cluster. We report per-chip throughput here and still compare against
that aggregate figure, which is conservative in our favor on any
multi-chip run and exactly apples-to-oranges-free on one chip.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

REFERENCE_AGG_IMAGES_PER_SEC = 52.0  # BASELINE.md "derived throughput"


def _require_chip() -> str:
    """The bench measures a chip: no accelerator is an error. A backend
    that cannot come up raises out of jax; a CPU backend is refused
    here, so a host number is never posted under the per-chip metric's
    name."""
    import jax

    platform = jax.default_backend()
    if platform == "cpu":
        raise SystemExit(
            "bench.py: JAX found no accelerator (platform 'cpu'); "
            "the per-chip throughput metric is only defined on a chip")
    return platform


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default="",
                        help="also write the record to this JSONL file "
                        "(observe.registry format; summarizable "
                        "artifacts, not scraped stdout)")
    args = parser.parse_args(argv)
    platform = _require_chip()
    import jax
    import optax

    from tensorflow_distributed_tpu.utils.compilecache import (
        enable_persistent_cache)
    enable_persistent_cache()

    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.data.mnist import synthetic_mnist
    from tensorflow_distributed_tpu.data.prefetch import prefetch_with
    from tensorflow_distributed_tpu.data.u8 import U8Dataset, U8ShardedBatcher
    from tensorflow_distributed_tpu.models.cnn import MnistCNN
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.train.multistep import (
        make_multi_step, stacked_batch_shardings)
    from tensorflow_distributed_tpu.train.state import create_train_state

    n_dev = len(jax.devices())
    mesh = make_mesh(MeshConfig(data=n_dev))
    global_batch = 256 * n_dev  # reference global batch per chip-pair scale
    train_ds, _, _ = synthetic_mnist(
        n_train=max(8 * global_batch, 8192), n_test=256,
        validation_size=256, seed=0)

    model = MnistCNN()  # bfloat16 compute — MXU-native
    state = create_train_state(
        model, optax.adam(1e-3), np.zeros((2, 28, 28, 1), np.float32), mesh)

    # End-to-end measurement: every pixel still flows host -> device
    # each step (the reference likewise paid its feed_dict path every
    # step) — but the TPU-native way: K steps per dispatch
    # (train.multistep), raw uint8 on the wire (4x fewer bytes),
    # normalization on device, transfers double-buffered against
    # compute.
    K = 20
    step_k = make_multi_step(
        mesh, preprocess=lambda b: (
            b[0].astype(jax.numpy.float32) / 255.0, b[1]))
    batcher = U8ShardedBatcher(U8Dataset.from_float(train_ds),
                               global_batch, 0, raw=True)
    shardings = stacked_batch_shardings(mesh)

    def host_stacks(it):
        while True:
            xs, ys = zip(*(next(it) for _ in range(K)))
            yield (np.stack(xs), np.stack(ys))

    def place(host):
        return jax.tree_util.tree_map(jax.device_put, host, shardings)

    it = prefetch_with(host_stacks(batcher.forever()), place, size=2)

    # Compile + warmup outside the timed window. One fresh-model step
    # first to capture the initial loss for the learning sanity check.
    state, metrics = step_k(state, next(it))
    initial_loss = float(jax.device_get(metrics["loss"]))
    state, metrics = step_k(state, next(it))
    float(jax.device_get(metrics["loss"]))
    jax.block_until_ready(state.params)

    dispatches = 30
    t0 = time.perf_counter()
    for _ in range(dispatches):
        state, metrics = step_k(state, next(it))
    # block_until_ready on the last step's outputs is the barrier;
    # the loss readback feeds the learning sanity check below.
    jax.block_until_ready(state.params)
    final_loss = float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0

    # Learning sanity: a degenerate step (NaN loss, dead graph) must not
    # post a throughput number. ~640 Adam steps on an 8k-image synthetic
    # set decisively beats the fresh-model loss.
    assert np.isfinite(final_loss), f"non-finite loss {final_loss}"
    assert final_loss < initial_loss, (
        f"loss did not decrease: {initial_loss} -> {final_loss}")

    steps = dispatches * K
    images_per_sec = steps * global_batch / dt
    per_chip = images_per_sec / n_dev
    record = {
        "metric": "mnist_cnn_train_images_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / REFERENCE_AGG_IMAGES_PER_SEC, 2),
        "platform": platform,
    }
    # Provenance for the regress ledger: git sha + the calibration
    # profile id in effect (observe.registry.artifact_stamp).
    from tensorflow_distributed_tpu.observe.registry import (
        artifact_stamp, default_calibration_path)
    record.update(artifact_stamp(default_calibration_path()))
    print(json.dumps(record))
    if args.out:
        from tensorflow_distributed_tpu.observe.registry import write_jsonl
        write_jsonl(args.out, [record])


if __name__ == "__main__":
    main()
