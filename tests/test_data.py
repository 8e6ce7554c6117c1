"""Data layer tests: idx parsing, synthetic set, sharded batching
(SURVEY.md N13 replacement)."""

import struct

import numpy as np
import pytest

from tensorflow_distributed_tpu.data.mnist import (
    Dataset, ShardedBatcher, parse_idx, synthetic_mnist)


def _idx_images(arr: np.ndarray) -> bytes:
    n, r, c = arr.shape
    return struct.pack(">iiii", 2051, n, r, c) + arr.tobytes()


def _idx_labels(arr: np.ndarray) -> bytes:
    return struct.pack(">ii", 2049, arr.shape[0]) + arr.tobytes()


def test_parse_idx_images_roundtrip():
    arr = np.arange(2 * 28 * 28, dtype=np.uint8).reshape(2, 28, 28)
    out = parse_idx(_idx_images(arr))
    np.testing.assert_array_equal(out, arr)


def test_parse_idx_labels_roundtrip():
    arr = np.array([3, 1, 4, 1, 5], dtype=np.uint8)
    np.testing.assert_array_equal(parse_idx(_idx_labels(arr)), arr)


def test_parse_idx_rejects_garbage():
    with pytest.raises(ValueError):
        parse_idx(b"\x00\x00\x00\x99" + b"\x00" * 16)
    with pytest.raises(ValueError):
        parse_idx(b"ab")


def test_synthetic_shapes_and_determinism():
    tr, va, te = synthetic_mnist(n_train=256, n_test=64, validation_size=32,
                                 seed=7)
    assert tr.images.shape == (224, 28, 28, 1)
    assert va.images.shape == (32, 28, 28, 1)
    assert te.images.shape == (64, 28, 28, 1)
    assert tr.images.dtype == np.float32
    assert 0.0 <= tr.images.min() and tr.images.max() <= 1.0
    assert set(np.unique(tr.labels)) <= set(range(10))
    tr2, _, _ = synthetic_mnist(n_train=256, n_test=64, validation_size=32,
                                seed=7)
    np.testing.assert_array_equal(tr.images, tr2.images)


from tests.conftest import FIXTURE_DIR


def test_load_mnist_fixture_real_idx_bytes():
    """load_mnist on the COMMITTED idx fixture (tests/fixtures/mnist):
    real on-disk idx1/idx3 bytes — big-endian headers, magic
    0x801/0x803, .gz and plain — through the full loader, not synthetic
    arrays handed past the parser (round-2 review missing #3b)."""
    from tensorflow_distributed_tpu.data.mnist import load_mnist

    train, val, test = load_mnist(FIXTURE_DIR, validation_size=64)
    assert train.images.shape == (960, 28, 28, 1)   # 1024 - 64 val
    assert val.images.shape == (64, 28, 28, 1)
    assert test.images.shape == (256, 28, 28, 1)
    assert train.images.dtype == np.float32
    assert 0.0 <= train.images.min() and train.images.max() <= 1.0
    assert set(np.unique(test.labels)) <= set(range(10))
    # The pixels decode to the generator's content (u8-quantized
    # synthetic glyphs, seed 7) — full byte-level round trip.
    gen = synthetic_mnist(n_train=1024, n_test=256, validation_size=0,
                          seed=7)[0]
    want = (gen.images[64:, ..., 0] * 255).round() / 255.0
    np.testing.assert_allclose(train.images[..., 0], want, atol=1e-6)


def test_native_reader_parses_fixture():
    """The C++ idx reader (native/tfd_native.cc) on the committed
    fixture files, against the numpy parser — both .gz and plain."""
    import gzip

    from tensorflow_distributed_tpu.native import runtime as native

    if not native.available():
        pytest.skip("native toolchain unavailable")
    plain = FIXTURE_DIR + "/t10k-images-idx3-ubyte"
    gz = FIXTURE_DIR + "/train-images-idx3-ubyte.gz"
    np.testing.assert_array_equal(
        native.idx_read(plain), parse_idx(open(plain, "rb").read()))
    np.testing.assert_array_equal(
        native.idx_read(gz), parse_idx(gzip.open(gz, "rb").read()))


def test_batcher_epoch_covers_dataset_once():
    ds = Dataset(np.arange(64, dtype=np.float32).reshape(64, 1, 1, 1),
                 np.arange(64, dtype=np.int32))
    b = ShardedBatcher(ds, global_batch=16, seed=0)
    seen = []
    for imgs, labels in b.epoch(0):
        assert imgs.shape == (16, 1, 1, 1)
        seen.extend(labels.tolist())
    assert sorted(seen) == list(range(64))


def test_batcher_process_shards_are_disjoint_and_union_to_global():
    """The upgrade over the reference's independent per-worker sampling
    (SURVEY.md N13): P processes partition each global batch exactly."""
    ds = Dataset(np.zeros((128, 1, 1, 1), np.float32),
                 np.arange(128, dtype=np.int32))
    global_stream = [
        labels for _, labels in ShardedBatcher(ds, 32, seed=3).epoch(0)]
    per_proc = [
        [labels for _, labels in
         ShardedBatcher(ds, 32, seed=3, num_processes=4,
                        process_index=p).epoch(0)]
        for p in range(4)
    ]
    for step, glabels in enumerate(global_stream):
        shards = [per_proc[p][step] for p in range(4)]
        np.testing.assert_array_equal(np.concatenate(shards), glabels)


def test_batcher_reshuffles_per_epoch():
    ds = Dataset(np.zeros((64, 1, 1, 1), np.float32),
                 np.arange(64, dtype=np.int32))
    b = ShardedBatcher(ds, 64, seed=0)
    (_, e0), (_, e1) = next(iter(b.epoch(0))), next(iter(b.epoch(1)))
    assert not np.array_equal(e0, e1)


def test_batcher_validates():
    ds = Dataset(np.zeros((8, 1, 1, 1), np.float32), np.zeros(8, np.int32))
    with pytest.raises(ValueError):
        ShardedBatcher(ds, global_batch=3, num_processes=2)
    with pytest.raises(ValueError):
        ShardedBatcher(ds, global_batch=16)
