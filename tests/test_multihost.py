"""Execute the multi-host path: 2 real processes over jax.distributed.

The reference's headline feature is multi-process training coordinated
over gRPC (mnist_python_m.py:146-161); its only "fake backend" was
pointing ps_hosts/worker_hosts at localhost and launching 3 local
processes (SURVEY.md §4). This is the same trick for the TPU-native
build: 2 local processes, each owning 4 virtual CPU devices, form one
8-device jax.distributed cluster and run the FULL train() loop —
bootstrap, process-disjoint data, make_array_from_process_local_data,
chief-only checkpointing — then the result is checked for exact parity
with a single-process 8-device run of the same config.

Parity holds because the sample stream is identical by construction
(ShardedBatcher: same seeded permutation everywhere, processes take
disjoint contiguous slices of the SAME global batch) and SPMD
collectives are deterministic.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # real 2-process cluster, 540 s budget

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_cluster(tmp, ckpt_dir, tag, extra_env=None):
    """Run one 2-process cluster of multihost_worker.py to completion;
    returns (results, logs)."""
    port = _free_port()
    procs, outs = [], []
    for p in range(2):
        out = tmp / f"result_{tag}_{p}.json"
        outs.append(out)
        env = {
            # Minimal, explicit env: no inherited JAX/XLA flags from
            # the pytest process.
            "PATH": os.environ["PATH"],
            "HOME": os.environ.get("HOME", "/tmp"),
            "PYTHONPATH": REPO,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPU_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "TPU_NUM_PROCESSES": "2",
            "TPU_PROCESS_ID": str(p),
            "MH_CKPT_DIR": str(ckpt_dir),
            "JAX_COMPILATION_CACHE_DIR":
                os.environ.get("JAX_COMPILATION_CACHE_DIR", ""),
            **(extra_env or {}),
        }
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tests",
                                          "multihost_worker.py"),
             str(out)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    logs = []
    for proc in procs:
        try:
            stdout, _ = proc.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost worker timed out")
        logs.append(stdout)
    for rc, log in zip([p.returncode for p in procs], logs):
        assert rc == 0, f"worker failed (rc={rc}):\n{log[-3000:]}"
    return [json.loads(out.read_text()) for out in outs], logs


@pytest.fixture(scope="module")
def multihost_results(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("multihost")
    ckpt_dir = tmp / "ckpt"
    results, logs = _launch_cluster(tmp, ckpt_dir, "main")
    return results, ckpt_dir, logs


def test_cluster_shape(multihost_results):
    results, _, _ = multihost_results
    for r in results:
        assert r["process_count"] == 2
        assert r["global_devices"] == 8
        assert r["local_devices"] == 4
        assert r["step"] == 6


def test_processes_agree(multihost_results):
    """SPMD: both processes hold bit-identical replicated params."""
    results, _, _ = multihost_results
    a, b = results
    assert a["params_checksum"] == b["params_checksum"]
    assert a["final_metrics"] == b["final_metrics"]


def test_chief_only_checkpoint(multihost_results):
    """Exactly the chief wrote the checkpoint (reference: the chief ran
    the Supervisor's saver, mnist_python_m.py:238-253)."""
    results, ckpt_dir, _ = multihost_results
    assert ckpt_dir.exists() and any(ckpt_dir.iterdir())


def test_chief_only_logging(multihost_results):
    """Process 1's stdout has no metric rows (MetricLogger is
    chief-gated), process 0's does."""
    _, _, logs = multihost_results
    assert '"event": "done"' in logs[0]
    assert '"event": "done"' not in logs[1]


def test_ring_attention_across_processes(multihost_results):
    """The zigzag causal ring with its seq axis spanning BOTH
    processes: ppermutes cross the process boundary (the DCN analog of
    the reference's cross-VM gRPC traffic), and the result matches a
    single-process 8-device run of the same config exactly."""
    results, _, _ = multihost_results
    a, b = results
    assert a["lm_params_checksum"] == b["lm_params_checksum"]
    assert a["lm_final_metrics"] == b["lm_final_metrics"]

    from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
    from tensorflow_distributed_tpu.train.loop import train

    cfg = TrainConfig(
        model="gpt_lm", model_size="tiny", dataset="synthetic",
        batch_size=16, train_steps=4, eval_every=0, log_every=0,
        eval_batch_size=32, compute_dtype="float32", dropout_rate=0.0,
        mesh=MeshConfig(data=1, seq=8), seed=0)
    single = train(cfg)
    for k, v in single.final_metrics.items():
        if k == "perplexity":
            continue  # derived as exp(loss): comparing loss covers
            # it without the ~4x relative-error amplification
        np.testing.assert_allclose(a["lm_final_metrics"][k], v,
                                   rtol=1e-4, atol=1e-5)


def test_crash_and_resume_across_processes(tmp_path_factory):
    """Failure recovery at the whole-job fault model (SURVEY.md §5:
    the reference's Supervisor re-attached a restarted worker from its
    checkpoint): a 2-process cluster trains to step 5 with durable
    checkpoints and dies; a FRESH cluster restarts with --resume and
    finishes to step 10, landing exactly where an uninterrupted run
    lands (same sample stream: the resume fast-forward is tested
    single-process in test_loop_cli; this pins it across processes
    with chief-only checkpoint writes)."""
    tmp = tmp_path_factory.mktemp("multihost_crash")
    ckpt_dir = tmp / "ckpt"
    _launch_cluster(tmp, ckpt_dir, "crash",
                    extra_env={"MH_PHASE": "crash"})
    assert ckpt_dir.exists() and any(ckpt_dir.iterdir()), \
        "no checkpoint written before crash"
    resumed, _ = _launch_cluster(tmp, ckpt_dir, "resume",
                                 extra_env={"MH_PHASE": "resume"})
    assert all(r["step"] == 10 for r in resumed)
    assert resumed[0]["params_checksum"] == resumed[1]["params_checksum"]

    # Uninterrupted oracle: the same 10 steps in one process.
    from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
    from tensorflow_distributed_tpu.train.loop import train

    cfg = TrainConfig(
        model="mnist_cnn", dataset="synthetic", batch_size=64,
        train_steps=10, eval_every=0, log_every=0, eval_batch_size=128,
        compute_dtype="float32", dropout_rate=0.0,
        mesh=MeshConfig(data=8), seed=0)
    single = train(cfg)
    for k, v in single.final_metrics.items():
        if k == "perplexity":
            continue  # derived as exp(loss): comparing loss covers
            # it without the ~4x relative-error amplification
        np.testing.assert_allclose(resumed[0]["final_metrics"][k], v,
                                   rtol=1e-4, atol=1e-5)


def test_fsdp_across_processes(tmp_path_factory):
    """FSDP with params/Adam slots sharded ACROSS the process boundary
    (param_partition="fsdp", data axis spanning both processes): the
    checkpoint save does a collective allgather fetch, the resume
    restore re-places shards per process, and the final state matches
    an uninterrupted single-process FSDP run exactly."""
    tmp = tmp_path_factory.mktemp("multihost_fsdp")
    ckpt_dir = tmp / "ckpt"
    results, _ = _launch_cluster(tmp, ckpt_dir, "fsdp",
                                 extra_env={"MH_PHASE": "fsdp"})
    assert all(r["step"] == 8 for r in results)
    assert results[0]["params_checksum"] == results[1]["params_checksum"]

    from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
    from tensorflow_distributed_tpu.train.loop import train

    cfg = TrainConfig(
        model="mnist_cnn", dataset="synthetic", batch_size=64,
        train_steps=8, eval_every=0, log_every=0, eval_batch_size=128,
        param_partition="fsdp", compute_dtype="float32",
        dropout_rate=0.0, mesh=MeshConfig(data=8), seed=0)
    single = train(cfg)
    for k, v in single.final_metrics.items():
        if k == "perplexity":
            continue  # derived as exp(loss): comparing loss covers
            # it without the ~4x relative-error amplification
        np.testing.assert_allclose(results[0]["final_metrics"][k], v,
                                   rtol=1e-4, atol=1e-5)


def test_orbax_across_processes(tmp_path_factory):
    """The orbax backend in a REAL 2-process cluster with FSDP params
    spanning the boundary: each process writes/restores its own shards
    (no allgather — unverifiable single-process), the chief's commit
    marker publishes completeness, resume works, and the final state
    matches an uninterrupted single-process FSDP run exactly."""
    tmp = tmp_path_factory.mktemp("multihost_orbax")
    ckpt_dir = tmp / "ckpt"
    results, _ = _launch_cluster(tmp, ckpt_dir, "orbax",
                                 extra_env={"MH_PHASE": "orbax"})
    assert all(r["step"] == 8 for r in results)
    assert results[0]["params_checksum"] == results[1]["params_checksum"]
    # The on-disk layout really is orbax (marker present).
    steps = sorted(p.name for p in ckpt_dir.iterdir())
    assert (ckpt_dir / steps[-1] / "ORBAX_COMMITTED").exists()

    from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
    from tensorflow_distributed_tpu.train.loop import train

    single = train(TrainConfig(
        model="mnist_cnn", dataset="synthetic", batch_size=64,
        train_steps=8, eval_every=0, log_every=0, eval_batch_size=128,
        param_partition="fsdp", compute_dtype="float32",
        dropout_rate=0.0, mesh=MeshConfig(data=8), seed=0))
    for k, v in single.final_metrics.items():
        if k == "perplexity":
            continue
        np.testing.assert_allclose(results[0]["final_metrics"][k], v,
                                   rtol=1e-4, atol=1e-5)


def test_local_sgd_across_processes(tmp_path_factory):
    """Local SGD with the 8 replicas spanning a REAL process boundary:
    the stacked step [8] is data-sharded across processes (host_step's
    index-before-device_get), the stacked checkpoint is written via
    the collective fetch and restored via per-process shard placement,
    and the final state matches an uninterrupted single-process run
    EXACTLY (replica identity = data-axis index, process-layout
    independent)."""
    tmp = tmp_path_factory.mktemp("multihost_lsgd")
    ckpt_dir = tmp / "ckpt"
    results, _ = _launch_cluster(tmp, ckpt_dir, "local_sgd",
                                 extra_env={"MH_PHASE": "local_sgd"})
    assert all(r["step"] == 6 for r in results)
    assert results[0]["params_checksum"] == results[1]["params_checksum"]

    from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
    from tensorflow_distributed_tpu.train.loop import train

    # UNINTERRUPTED oracle — no checkpointing at all, straight to step
    # 6: the cluster's crash-at-3-and-resume sequence must land exactly
    # here, which pins the stacked save/restore itself (a process-
    # layout-independent restore defect cannot hide in a replayed
    # interruption).
    single = train(TrainConfig(
        model="mnist_cnn", dataset="synthetic", batch_size=64,
        train_steps=6, eval_every=0, log_every=0, eval_batch_size=128,
        param_sync_every=2, compute_dtype="float32", dropout_rate=0.0,
        mesh=MeshConfig(data=8), seed=0))
    for k, v in single.final_metrics.items():
        if k == "perplexity":
            continue  # derived as exp(loss): comparing loss covers
            # it without the ~4x relative-error amplification
        np.testing.assert_allclose(results[0]["final_metrics"][k], v,
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_and_expert_axes_across_processes(tmp_path_factory):
    """The pipe axis (1F1B activation/cotangent ppermutes every tick)
    and the expert axis (MoE dispatch/combine all_to_alls) spanning
    BOTH processes — the deepest cross-process collectives the
    framework emits — match single-process 8-device oracles exactly."""
    tmp = tmp_path_factory.mktemp("multihost_xaxes")
    results, _ = _launch_cluster(tmp, tmp / "ckpt", "xaxes",
                                 extra_env={"MH_PHASE": "xaxes"})
    a, b = results
    assert a == b  # SPMD: both processes computed identical results

    # The oracle runs THE SAME scenario definition the workers ran
    # (multihost_worker.run_xaxes_scenarios) — single process, plain
    # device_get fetch.
    import importlib.util

    import jax

    spec = importlib.util.spec_from_file_location(
        "multihost_worker",
        os.path.join(REPO, "tests", "multihost_worker.py"))
    worker_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(worker_mod)
    oracle = worker_mod.run_xaxes_scenarios(jax.device_get)
    for key, got in a.items():
        np.testing.assert_allclose(got, oracle[key], rtol=1e-4,
                                   err_msg=key)


def test_r5_compositions_across_processes(tmp_path_factory):
    """Round-5 compositions with their new collectives spanning the
    process boundary: ring-inside-the-pipeline (pipe hops cross DCN
    while the nested ring runs per-process) and ZeRO-1 x 1F1B (slot
    shards + the restore-layout allgather cross processes). Must match
    the single-process oracle running THE SAME scenario definition."""
    tmp = tmp_path_factory.mktemp("multihost_r5")
    results, _ = _launch_cluster(tmp, tmp / "ckpt", "r5",
                                 extra_env={"MH_PHASE": "r5"})
    a, b = results
    assert a == b  # SPMD: both processes computed identical results

    import importlib.util

    import jax

    spec = importlib.util.spec_from_file_location(
        "multihost_worker",
        os.path.join(REPO, "tests", "multihost_worker.py"))
    worker_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(worker_mod)
    oracle = worker_mod.run_r5_scenarios(jax.device_get)
    for key, got in a.items():
        np.testing.assert_allclose(got, oracle[key], rtol=1e-4,
                                   err_msg=key)


def test_fused_ce_kernel_across_processes(tmp_path_factory):
    """The fused-CE Pallas path with its loss reductions spanning the
    process boundary: the dispatcher's shard_map psums ce/correct/mask
    over (data, seq), and here those axes cross processes. Must match
    the single-process oracle running THE SAME scenario definition."""
    tmp = tmp_path_factory.mktemp("multihost_fusedce")
    results, _ = _launch_cluster(tmp, tmp / "ckpt", "fusedce",
                                 extra_env={"MH_PHASE": "fusedce"})
    a, b = results
    assert a == b  # SPMD: both processes computed identical results

    import importlib.util

    import jax

    spec = importlib.util.spec_from_file_location(
        "multihost_worker",
        os.path.join(REPO, "tests", "multihost_worker.py"))
    worker_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(worker_mod)
    oracle = worker_mod.run_fusedce_scenario(jax.device_get)
    for key, got in a.items():
        np.testing.assert_allclose(got, oracle[key], rtol=1e-4,
                                   err_msg=key)


def test_parity_with_single_process(multihost_results):
    """2-process x 4-device == 1-process x 8-device, same config: the
    N-vs-1 equivalence of SURVEY.md §7 extended across process
    boundaries. Loss/accuracy match to float tolerance."""
    results, _, _ = multihost_results

    from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
    from tensorflow_distributed_tpu.train.loop import train

    cfg = TrainConfig(
        model="mnist_cnn", dataset="synthetic", batch_size=64,
        train_steps=6, eval_every=0, log_every=0, eval_batch_size=128,
        compute_dtype="float32", dropout_rate=0.0,
        mesh=MeshConfig(data=8), seed=0)
    single = train(cfg)

    multi = results[0]["final_metrics"]
    for k, v in single.final_metrics.items():
        if k == "perplexity":
            continue  # derived as exp(loss): comparing loss covers
            # it without the ~4x relative-error amplification
        np.testing.assert_allclose(multi[k], v, rtol=1e-4, atol=1e-5)
