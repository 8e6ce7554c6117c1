"""Model-layer tests: exact reference shapes + loss math golden numbers."""

import jax
import jax.numpy as jnp
import numpy as np

from tensorflow_distributed_tpu.models.cnn import MnistCNN
from tensorflow_distributed_tpu.ops.losses import accuracy, softmax_cross_entropy


def _init(model, batch=2):
    x = jnp.zeros((batch, 28, 28, 1), jnp.float32)
    return model.init(jax.random.key(0), x, train=False), x


def test_parameter_shapes_match_reference():
    """Exact parity with the reference weight dicts
    (mnist_python_m.py:185-196): wc1 [5,5,1,32], wc2 [5,5,32,64],
    wd1 [3136,1024], out [1024,10] + matching biases."""
    model = MnistCNN(compute_dtype=jnp.float32)
    variables, _ = _init(model)
    p = variables["params"]
    assert p["conv1"]["kernel"].shape == (5, 5, 1, 32)
    assert p["conv1"]["bias"].shape == (32,)
    assert p["conv2"]["kernel"].shape == (5, 5, 32, 64)
    assert p["conv2"]["bias"].shape == (64,)
    assert p["fc1"]["kernel"].shape == (3136, 1024)
    assert p["fc1"]["bias"].shape == (1024,)
    assert p["out"]["kernel"].shape == (1024, 10)
    assert p["out"]["bias"].shape == (10,)
    total = sum(x.size for x in jax.tree_util.tree_leaves(p))
    # 832 + 51264 + 3212288 + 10250 (conv+bias, fc+bias) — the reference
    # model's exact parameter count.
    assert total == 3_274_634


def test_forward_shapes_and_dtype():
    model = MnistCNN(compute_dtype=jnp.float32)
    variables, x = _init(model, batch=4)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (4, 10)
    assert logits.dtype == jnp.float32


def test_accepts_flat_784_input():
    """The reference's placeholder was [None, 784]
    (mnist_python_m.py:198)."""
    model = MnistCNN(compute_dtype=jnp.float32)
    variables, _ = _init(model)
    flat = jnp.zeros((3, 784), jnp.float32)
    assert model.apply(variables, flat, train=False).shape == (3, 10)


def test_dropout_only_active_in_train_mode():
    model = MnistCNN(compute_dtype=jnp.float32, dropout_rate=0.5)
    variables, x = _init(model, batch=8)
    e1 = model.apply(variables, x + 1.0, train=False)
    e2 = model.apply(variables, x + 1.0, train=False)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    t1 = model.apply(variables, x + 1.0, train=True,
                     rngs={"dropout": jax.random.key(1)})
    t2 = model.apply(variables, x + 1.0, train=True,
                     rngs={"dropout": jax.random.key(2)})
    assert not np.array_equal(np.asarray(t1), np.asarray(t2))


def test_reference_init_trains_materially_worse():
    """TRAINING-OUTCOME faithful-vs-improved comparison (round-2 review
    weak #7): same data, same fixed step budget —
    init_scheme="reference" with the reference's Adam lr 0.01
    (mnist_python_m.py:185-196,208) lands materially below "improved".
    The reference's own performance table is exactly such a
    fixed-budget curve (40 steps -> 90%, performance:2). On real MNIST
    the bad init also caps the ceiling at 95.75% (performance:6); the
    synthetic glyph set is easy enough that even stddev-1.0 init
    eventually recovers (measured: 0.996 by step 80 of batch 64), so
    the fixed-budget comparison is the honest, deterministic form of
    the outcome gap here. Measured (fixed seeds, CPU, batch 32 x 32
    steps): reference 0.605 vs improved 0.828."""
    import optax

    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.data.mnist import synthetic_mnist
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.parallel.sharding import shard_batch
    from tensorflow_distributed_tpu.train.state import create_train_state
    from tensorflow_distributed_tpu.train.step import (
        make_eval_step, make_train_step)

    mesh = make_mesh(MeshConfig(data=8))
    train_ds, val_ds, _ = synthetic_mnist(n_train=4096, n_test=512,
                                          validation_size=256, seed=0)
    # lr rides in the optimizer STATE (inject_hyperparams), so one
    # compiled step serves both schemes — the graphs are identical,
    # only initial params and lr differ.
    tx = optax.inject_hyperparams(optax.adam)(learning_rate=1e-3)
    sample = np.zeros((2, 28, 28, 1), np.float32)
    step = make_train_step(mesh, donate=False)
    eval_step = make_eval_step(mesh)
    val_batch = shard_batch(mesh, (val_ds.images, val_ds.labels))

    accs = {}
    for scheme, lr in (("reference", 0.01), ("improved", 1e-3)):
        model = MnistCNN(init_scheme=scheme, compute_dtype=jnp.float32)
        state = create_train_state(model, tx, sample, mesh)
        state.opt_state.hyperparams["learning_rate"] = jnp.asarray(lr)
        for i in range(32):
            lo = (i * 32) % 2048
            b = shard_batch(mesh, (train_ds.images[lo:lo + 32],
                                   train_ds.labels[lo:lo + 32]))
            state, metrics = step(state, b)
            # Block each step: unbounded async dispatch of 8-device
            # SPMD programs aborts XLA:CPU's collective rendezvous on
            # oversubscribed hosts (see train/loop.py's inflight deque).
            jax.block_until_ready(metrics)
        accs[scheme] = float(
            jax.device_get(eval_step(state, val_batch)["accuracy"]))
    # "Materially below" at the fixed budget: the stddev-1.0 init +
    # lr 0.01 combination saturates activations and thrashes Adam.
    # Everything above is seed-fixed, so the 22-point measured gap is
    # deterministic; the margins leave slack for backend math drift.
    assert accs["improved"] >= accs["reference"] + 0.10, accs
    assert accs["improved"] >= 0.80, accs
    assert accs["reference"] <= 0.70, accs


def test_reference_init_scheme_is_wild():
    """reference init = normal stddev 1.0 (mnist_python_m.py:185-196);
    improved = He. Their weight scales must differ by orders of
    magnitude on the big fc1 matrix."""
    ref = MnistCNN(init_scheme="reference", compute_dtype=jnp.float32)
    imp = MnistCNN(init_scheme="improved", compute_dtype=jnp.float32)
    pr, _ = _init(ref)
    pi, _ = _init(imp)
    sr = float(jnp.std(pr["params"]["fc1"]["kernel"]))
    si = float(jnp.std(pi["params"]["fc1"]["kernel"]))
    assert 0.9 < sr < 1.1          # stddev ~1.0
    assert si < 0.05               # He: sqrt(2/3136) ~ 0.025


def test_softmax_xent_golden():
    """Hand-computed golden numbers for the loss
    (reference: tf.nn.softmax_cross_entropy_with_logits mean,
    mnist_python_m.py:205)."""
    logits = jnp.array([[2.0, 0.0], [0.0, 2.0]])
    labels = jnp.array([0, 1])
    # per-row: log(exp(2)+exp(0)) - 2 = log(1+exp(-2)) = 0.126928...
    got = float(softmax_cross_entropy(logits, labels))
    np.testing.assert_allclose(got, 0.12692805, rtol=1e-6)
    # Uniform logits -> log(num_classes).
    u = jnp.zeros((5, 10))
    np.testing.assert_allclose(
        float(softmax_cross_entropy(u, jnp.zeros(5, jnp.int32))),
        np.log(10.0), rtol=1e-6)


def test_accuracy_golden():
    logits = jnp.array([[1.0, 0.0], [0.0, 1.0], [1.0, 0.0], [0.0, 1.0]])
    labels = jnp.array([0, 1, 1, 1])
    assert float(accuracy(logits, labels)) == 0.75
