"""Force an 8-device virtual CPU platform before JAX initializes.

This is the JAX analog of the reference's in-process-server trick
(SURVEY.md §4): the reference could exercise its full gRPC ps/worker
path on one machine by pointing ps_hosts/worker_hosts at localhost;
we exercise the full SPMD psum path on one machine with
--xla_force_host_platform_device_count=8. Both variables are set here
before jax is imported (JAX_PLATFORMS is read at import, XLA_FLAGS when
the backend first initializes), which is all it takes.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import pytest  # noqa: E402

from tensorflow_distributed_tpu.utils.compilecache import (  # noqa: E402
    enable_persistent_cache)

# CPU test compiles of 8-device SPMD programs are the suite's wall-clock;
# cache them across runs.
enable_persistent_cache()


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices8):
    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    return make_mesh(MeshConfig(data=8), devices8)


@pytest.fixture(scope="session")
def mesh1(devices8):
    from tensorflow_distributed_tpu.parallel.mesh import single_device_mesh
    return single_device_mesh(devices8[0])


@pytest.fixture(scope="session")
def tiny_data():
    from tensorflow_distributed_tpu.data.mnist import synthetic_mnist
    return synthetic_mnist(n_train=2048, n_test=512, validation_size=256, seed=0)


# Committed real-idx fixture (shared by test_data / test_loop_cli).
FIXTURE_DIR = __file__.rsplit("/", 1)[0] + "/fixtures/mnist"
