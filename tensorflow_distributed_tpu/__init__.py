"""tensorflow_distributed_tpu — a TPU-native distributed training framework.

A ground-up JAX/XLA re-design of the capabilities of
PranjalSahu/tensorflow_distributed (a TF-1.x parameter-server MNIST
trainer, see /root/reference):

- The reference's ps/worker/gRPC topology (``tf.train.Server``,
  ``replica_device_setter``, ``SyncReplicasOptimizer`` —
  mnist_python_m.py:146-233) is replaced by a single jit-compiled SPMD
  train step over a ``jax.sharding.Mesh``: gradient synchronization is an
  XLA ``psum`` allreduce over ICI, not a push/pull through a parameter
  server over TCP.
- The single-device path (mnist_single.py) and the distributed path are
  the *same* train step on meshes of different shapes — no per-role
  script copies, no chief/non-chief init dance.

Package layout:
    config          one config surface replacing the 14 tf.app.flags
    parallel/       mesh construction, sharding rules, collectives,
                    sequence-parallel ring attention
    models/         CNN (reference parity), ResNet, Transformer/BERT
    ops/            losses/metrics + Pallas TPU kernels
    data/           MNIST idx loader, synthetic data, sharded batching
    train/          train state, jitted steps, loop, checkpointing
    utils/          prng, logging, timing
    native/         C++ data-plane helpers (idx parse, batch assembly)
"""

__version__ = "0.1.0"

# The package root imports no jax: the graftcheck lint tier
# (analysis/lint.py, pure stdlib by contract) and the config surface
# must import — and run — on boxes that never installed an accelerator
# stack (tests/test_analysis.py::test_lint_engine_is_jax_free). Anything
# that actually computes fails loudly at ITS import.
from tensorflow_distributed_tpu.config import TrainConfig  # noqa: F401
