"""Elastic restarts: checkpoint resharding + degrade-and-continue.

Covers the mesh/sharding manifest written beside every checkpoint,
``restore_resharded`` (bitwise round trips across mesh shapes and
layouts), the ``MeshMismatchError`` diagnosis, the supervisor's
``--elastic`` mesh picking (pure, jax-free units), the ``device_loss``
fault grammar, and — slow tier — the supervised
device_loss -> shrink -> continue e2e.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
from tensorflow_distributed_tpu.models.cnn import MnistCNN
from tensorflow_distributed_tpu.parallel.mesh import make_mesh
from tensorflow_distributed_tpu.parallel.sharding import shard_batch
from tensorflow_distributed_tpu.resilience import supervisor as sup
from tensorflow_distributed_tpu.resilience.faults import parse_fault_plan
from tensorflow_distributed_tpu.train import checkpoint as ckpt
from tensorflow_distributed_tpu.train.state import TrainState, create_train_state
from tensorflow_distributed_tpu.train.step import make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _state(mesh, fsdp=False, ema=True):
    model = MnistCNN(dropout_rate=0.0, compute_dtype=jnp.float32)
    return create_train_state(model, optax.adam(1e-3),
                              jnp.zeros((2, 28, 28, 1)), mesh, seed=0,
                              fsdp=fsdp, ema=ema)


def _batch(n=16, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, 28, 28, 1)).astype(np.float32),
            rng.integers(0, 10, size=(n,)).astype(np.int32))


def _assert_trees_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)),
        jax.device_get(a), jax.device_get(b))


# --- supervisor elastic units (pure, jax-free) --------------------------

def test_pick_elastic_mesh_units():
    axes = {"data": 4, "model": 1, "seq": 1, "pipe": 1, "expert": 1}
    # Shrink: data absorbs the resize.
    assert sup.pick_elastic_mesh(axes, 2, 64)["data"] == 2
    # Grow: fill the returned capacity.
    assert sup.pick_elastic_mesh(axes, 8, 64)["data"] == 8
    # Global batch must stay an integer per-device share: 6 alive but
    # 64 % 6 != 0 -> 4.
    assert sup.pick_elastic_mesh(axes, 6, 64)["data"] == 4
    # Non-data axes are preserved exactly (semantic parallelism).
    tp = {"data": 2, "model": 2, "seq": 1, "pipe": 1, "expert": 1}
    got = sup.pick_elastic_mesh(tp, 4, 64)
    assert got == tp
    assert sup.pick_elastic_mesh(tp, 2, 64) == {**tp, "data": 1}
    # Fewer devices than the non-data product: nothing to degrade to.
    assert sup.pick_elastic_mesh(tp, 1, 64) is None
    assert sup.pick_elastic_mesh(axes, 0, 64) is None


def test_rewrite_mesh_args_both_spellings_and_append():
    mesh = {"data": 2, "model": 1, "seq": 1, "pipe": 1, "expert": 1}
    assert sup.rewrite_mesh_args(["--mesh.data", "4", "--x", "y"],
                                 mesh) == ["--mesh.data", "2",
                                           "--x", "y"]
    assert sup.rewrite_mesh_args(["--mesh.data=4"],
                                 {**mesh, "data": 8}) == [
        "--mesh.data=8"]
    # Absent flag: the chosen width is appended EXPLICITLY (a
    # default -1 child must not re-fill to whatever is visible).
    assert sup.rewrite_mesh_args(["--train-steps", "5"], mesh) == [
        "--train-steps", "5", "--mesh.data", "2"]
    # Non-data axes only appear when != 1.
    out = sup.rewrite_mesh_args([], {**mesh, "model": 2})
    assert "--mesh.model" in out and "--mesh.seq" not in out


def test_plan_elastic_masks_dead_chips_and_remainder():
    # 8 visible, 6 declared lost -> mesh data=2 and the child must
    # hide 6 devices so its visible set exactly equals the mesh.
    mesh, child_mask = sup.plan_elastic(
        ["--mesh.data", "4", "--batch-size", "64"], total=8, masked=6)
    assert mesh["data"] == 2 and child_mask == 6
    # 6 alive of 8 with batch 64: data=4 and the unusable remainder
    # (2 alive chips the mesh can't shape around) is masked too.
    mesh, child_mask = sup.plan_elastic(
        ["--mesh.data", "4", "--batch-size", "64"], total=8, masked=2)
    assert mesh["data"] == 4 and child_mask == 4
    assert sup.plan_elastic(["--mesh.model", "4"], total=8,
                            masked=6) is None


def test_read_mask_absent_and_garbage(tmp_path):
    assert sup._read_mask(None) == 0
    assert sup._read_mask(str(tmp_path / "nope")) == 0
    bad = tmp_path / "DEVICE_MASK"
    bad.write_text("not json")
    assert sup._read_mask(str(bad)) == 0
    bad.write_text(json.dumps({"lost": 3}))
    assert sup._read_mask(str(bad)) == 3


def test_build_leg_args_unchanged_without_elastic():
    """Non-elastic behavior pinned: restarted train legs only gain
    --resume; no mesh flag is ever touched."""
    args = ["--mesh.data", "8", "--checkpoint-dir", "/tmp/c"]
    assert sup.build_leg_args(args, 0) == args
    assert sup.build_leg_args(args, 1) == args + ["--resume", "true"]


def test_supervisor_elastic_stops_when_no_mesh_fits(tmp_path,
                                                    monkeypatch):
    """Survivors below the non-data product: the supervisor refuses to
    launch a doomed leg and stops (in-process main with a stubbed
    probe — jax-free)."""
    mask = tmp_path / "DEVICE_MASK"
    mask.write_text(json.dumps({"lost": 7}))
    monkeypatch.setenv("TFD_DEVICE_MASK_FILE", str(mask))
    monkeypatch.setattr(sup, "_probe_devices", lambda: 8)
    rc = sup.main(["--elastic", "--", "--mesh.model", "2",
                   "--checkpoint-dir", str(tmp_path / "ckpt")])
    assert rc == 1


# --- fault grammar / config ---------------------------------------------

def test_device_loss_grammar_and_phase():
    plan = parse_fault_plan("device_loss@13:2")
    assert ("device_loss", 13) in plan._by_step
    with pytest.raises(ValueError, match="positive int"):
        parse_fault_plan("device_loss@13:0")
    with pytest.raises(ValueError, match="positive int"):
        parse_fault_plan("device_loss@13:1.5")
    # Train-phase only: a serve run must reject it at config time.
    cfg = TrainConfig(mode="serve", model="gpt_lm",
                      checkpoint_dir="/tmp/x")
    cfg.resilience.fault_plan = "device_loss@5"
    with pytest.raises(ValueError, match="train-phase only"):
        cfg.validate()
    # And it needs a checkpoint dir (mask file + resume target).
    cfg2 = TrainConfig()
    cfg2.resilience.fault_plan = "device_loss@5"
    with pytest.raises(ValueError, match="device-mask"):
        cfg2.validate()


def test_device_loss_first_leg_only(tmp_path, monkeypatch):
    """A resumed leg (bind(start_step > 0)) never re-fires the drill —
    the restart IS the recovery under test."""
    from tensorflow_distributed_tpu.resilience import faults
    killed = []
    monkeypatch.setattr(faults.os, "kill",
                        lambda *a: killed.append(a))
    monkeypatch.setenv("TFD_DEVICE_MASK_FILE",
                       str(tmp_path / "DEVICE_MASK"))
    plan = parse_fault_plan("device_loss@5:2")
    plan.bind(4)
    plan.maybe_device_loss(5, str(tmp_path))
    assert not killed and not (tmp_path / "DEVICE_MASK").exists()
    plan2 = parse_fault_plan("device_loss@5:2")
    plan2.bind(0)
    plan2.maybe_device_loss(5, str(tmp_path))
    assert killed
    assert json.loads(
        (tmp_path / "DEVICE_MASK").read_text())["lost"] == 2


# --- mesh manifest + resharded restore ----------------------------------

def test_mesh_manifest_written_and_listed(tmp_path, mesh8):
    state = _state(mesh8, ema=False)
    ckpt.save(str(tmp_path), state)
    man = ckpt.read_mesh_manifest(str(tmp_path), 0)
    assert man["mesh"]["data"] == 8
    assert man["process_count"] == 1
    assert any("kernel" in k for k in man["specs"])
    assert ckpt.steps_with_mesh(str(tmp_path)) == [(0, man["mesh"])]
    # Operator-facing errors carry the written topology.
    with pytest.raises(FileNotFoundError,
                       match=r"available steps: \[0\] \(written on "
                             r"mesh data=8\)"):
        ckpt.restore(str(tmp_path), _state(mesh8, ema=False), step=7)


@pytest.mark.parametrize("src,dst,fsdp", [
    (1, 2, False), (2, 4, False), (4, 8, False),
    (8, 2, True), (2, 8, True),
])
def test_reshard_roundtrip_matrix(tmp_path, devices8, src, dst, fsdp):
    """Save on mesh A, restore_resharded onto mesh B: gathered params,
    optimizer state AND the EMA come back bit-identical, and the
    restored layout satisfies the template's sharding contract
    (restore_resharded asserts it)."""
    mesh_a = make_mesh(MeshConfig(data=src), devices8[:src])
    mesh_b = make_mesh(MeshConfig(data=dst), devices8[:dst])
    s_a = _state(mesh_a, fsdp=fsdp)
    step = make_train_step(mesh_a, donate=False, ema_decay=0.99)
    s_a, _ = step(s_a, shard_batch(mesh_a, _batch()))
    ckpt.save(str(tmp_path), s_a)

    s_b, info = ckpt.restore_resharded(str(tmp_path),
                                       _state(mesh_b, fsdp=fsdp))
    assert info["resharded"] and info["step"] == 1
    assert info["from_mesh"]["data"] == src
    assert info["to_mesh"]["data"] == dst
    assert info["seconds"] >= 0
    _assert_trees_equal(s_a.params, s_b.params)
    _assert_trees_equal(s_a.opt_state, s_b.opt_state)
    _assert_trees_equal(s_a.ema, s_b.ema)


def test_reshard_roundtrip_tensor_layout(tmp_path, devices8):
    """A tensor-sharded leaf (P(None, 'model')) written on a
    data=2,model=2 mesh round-trips bitwise onto a pure-data mesh —
    the layouts come from the TEMPLATE, the values from the bytes."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh_tp = make_mesh(MeshConfig(data=2, model=2), devices8[:4])
    mesh_dp = make_mesh(MeshConfig(data=2), devices8[:2])
    w = np.arange(64, dtype=np.float32).reshape(8, 8)

    def tp_state(mesh, spec):
        return TrainState(
            step=jax.device_put(jnp.zeros((), jnp.int32),
                                NamedSharding(mesh, P())),
            params={"w": jax.device_put(w, NamedSharding(mesh, spec))},
            opt_state=(), apply_fn=None, tx=None)

    ckpt.save(str(tmp_path), tp_state(mesh_tp, P(None, "model")))
    man = ckpt.read_mesh_manifest(str(tmp_path), 0)
    assert man["mesh"] == {"data": 2, "pipe": 1, "seq": 1, "model": 2,
                           "expert": 1}
    assert "model" in man["specs"]["params/w"]
    restored, info = ckpt.restore_resharded(
        str(tmp_path), tp_state(mesh_dp, P("data", None)))
    assert info["resharded"]
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(restored.params["w"])), w)


def test_restore_resharded_same_mesh_is_plain(tmp_path, mesh8):
    state = _state(mesh8, ema=False)
    ckpt.save(str(tmp_path), state)
    restored, info = ckpt.restore_resharded(str(tmp_path),
                                            _state(mesh8, ema=False))
    assert not info["resharded"]
    _assert_trees_equal(state.params, restored.params)


def test_mesh_mismatch_error_names_both_meshes(tmp_path, mesh8, mesh1,
                                               monkeypatch):
    """An opaque runtime failure during a CROSS-mesh placement is
    re-raised as MeshMismatchError naming written vs requested mesh
    and pointing at restore_resharded; the SAME-mesh failure stays
    itself (not a mesh problem)."""
    state = _state(mesh8, ema=False)
    ckpt.save(str(tmp_path), state)
    tmpl1, tmpl8 = _state(mesh1, ema=False), _state(mesh8, ema=False)

    def boom(*a, **k):
        raise RuntimeError("XLA placement exploded")

    monkeypatch.setattr(jax, "device_put", boom)
    with pytest.raises(ckpt.MeshMismatchError) as ei:
        ckpt.restore(str(tmp_path), tmpl1)
    msg = str(ei.value)
    assert "data=8" in msg and "single-device" in msg
    assert "restore_resharded" in msg
    with pytest.raises(RuntimeError, match="XLA placement exploded"):
        ckpt.restore(str(tmp_path), tmpl8)


def test_quarantine_event_carries_written_mesh(tmp_path, mesh8,
                                               monkeypatch):
    events = []
    monkeypatch.setattr(
        ckpt, "emit_event",
        lambda event, **f: events.append({"event": event, **f}))
    state = _state(mesh8, ema=False)
    step = make_train_step(mesh8, donate=False)
    for _ in range(2):
        state, _ = step(state, shard_batch(mesh8, _batch()))
        ckpt.save(str(tmp_path), state)
    blob = os.path.join(str(tmp_path), "step_00000002",
                        "state.msgpack")
    with open(blob, "r+b") as f:
        f.seek(10)
        f.write(b"\xff\xff\xff")
    restored = ckpt.restore(str(tmp_path), _state(mesh8, ema=False))
    assert int(jax.device_get(restored.step)) == 1
    quar = [e for e in events if e.get("kind") == "quarantine"]
    assert quar and quar[0]["mesh"] == "data=8"


# --- report folding (jax-free inputs) -----------------------------------

def test_report_folds_mesh_changes():
    from tensorflow_distributed_tpu.observe.report import (
        render, summarize)
    mesh8 = {"data": 8, "model": 1, "seq": 1, "pipe": 1, "expert": 1}
    mesh4 = {**mesh8, "data": 4}
    recs = [
        {"event": "recovery", "kind": "mesh_change", "leg": 1,
         "from_mesh": mesh8, "to_mesh": mesh4, "alive": 4},
        {"event": "recovery", "kind": "reshard_restore", "step": 4,
         "from_mesh": mesh8, "to_mesh": mesh4, "resharded": True,
         "seconds": 0.21},
        {"event": "recovery", "kind": "restart", "leg": 1, "rc": -9},
    ]
    out = summarize(recs)
    assert out["mesh_changes"] == 1
    assert out["mesh_change_path"] == "data=8 -> data=4"
    assert out["reshard_seconds_total"] == 0.21
    assert out["recovery_counts"]["mesh_change"] == 1
    text = render(out)
    assert "mesh_changes" in text and "data=8 -> data=4" in text
    assert "reshard_seconds_total" in text
    # The loop-only flavor (manual --resume onto a new mesh): the
    # reshard events alone still fold.
    out2 = summarize(recs[1:2])
    assert out2["mesh_changes"] == 1
    assert out2["reshard_seconds_total"] == 0.21


# --- supervised e2e (slow) ----------------------------------------------

def _child_env():
    return {
        "PATH": os.environ["PATH"],
        "HOME": os.environ.get("HOME", "/tmp"),
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_COMPILATION_CACHE_DIR":
            os.environ.get("JAX_COMPILATION_CACHE_DIR", ""),
        "PYTHONUNBUFFERED": "1",
    }


@pytest.mark.slow
def test_supervisor_elastic_device_loss_shrinks_and_continues(tmp_path):
    """The acceptance scenario: device_loss@5:4 on a mesh-8 run under
    --elastic ends in a CONVERGING run on mesh 4 (exit 0), resumed at
    the last pre-kill checkpoint with the resize recorded — not a
    crash loop."""
    ckpt_dir = str(tmp_path / "ckpt")
    jsonl = str(tmp_path / "m.jsonl")
    proc = subprocess.run(
        [sys.executable, "-m",
         "tensorflow_distributed_tpu.resilience.supervisor",
         "--elastic", "--max-restarts", "3", "--backoff-base-s", "0.2",
         "--", "--dataset", "synthetic", "--mesh.data", "8",
         "--batch-size", "64", "--train-steps", "8",
         "--eval-every", "0", "--log-every", "0",
         "--eval-batch-size", "64", "--compute-dtype", "float32",
         "--checkpoint-dir", ckpt_dir, "--checkpoint-every", "2",
         "--observe.metrics-jsonl", jsonl,
         "--resilience.fault-plan", "device_loss@5:4"],
        env=_child_env(), cwd=REPO, capture_output=True, text=True,
        timeout=500)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert '"kind": "mesh_change"' in proc.stdout
    assert "--mesh.data 4" in proc.stdout  # the rewritten leg

    with open(jsonl) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    rec = [r for r in recs if r.get("event") == "recovery"]
    assert any(r.get("fault") == "device_loss" and r.get("lost") == 4
               for r in rec)
    reshard = [r for r in rec if r.get("kind") == "reshard_restore"]
    assert reshard and reshard[0]["from_mesh"]["data"] == 8 \
        and reshard[0]["to_mesh"]["data"] == 4
    resumed = [r for r in recs if r.get("event") == "resumed"]
    # Kill at dispatch of 5, cadence save at 4: zero lost steps.
    assert resumed and resumed[-1]["step"] == 4
    assert resumed[-1]["per_device_batch"] == 16
    assert [r.get("steps") for r in recs
            if r.get("event") == "summary"] == [8]
    # The run's goodput ledger charged the resize window.
    summary = [r for r in recs if r.get("event") == "summary"][-1]
    assert summary.get("reshard_seconds", 0) > 0
