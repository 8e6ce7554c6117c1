"""Acceptance e2e for the observe/ subsystem: a CPU-only tiny-
transformer run produces a metrics JSONL with step-time breakdown and
MFU fields plus a valid Chrome trace, and observe.report summarizes
the JSONL without error."""

import json

import jax
import numpy as np
import pytest

from tensorflow_distributed_tpu.config import (
    MeshConfig, ObserveConfig, TrainConfig)
from tensorflow_distributed_tpu.observe import report
from tensorflow_distributed_tpu.observe.trace import load_trace
from tensorflow_distributed_tpu.train.loop import train


def test_tiny_transformer_end_to_end_observed(tmp_path):
    jsonl = str(tmp_path / "metrics.jsonl")
    trace = str(tmp_path / "trace.json")
    cfg = TrainConfig(
        model="gpt_lm", model_size="tiny", dataset="synthetic",
        batch_size=16, train_steps=20, eval_every=10, log_every=5,
        eval_batch_size=16, compute_dtype="float32", dropout_rate=0.0,
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=10,
        mesh=MeshConfig(data=8),
        observe=ObserveConfig(metrics_jsonl=jsonl, trace=trace,
                              metrics_csv=str(tmp_path / "metrics.csv"),
                              peak_tflops=0.001))
    result = train(cfg)
    assert int(jax.device_get(result.state.step)) == 20

    records = [json.loads(line) for line in open(jsonl)]
    events = {r["event"] for r in records}
    assert {"start", "step", "eval", "summary"} <= events
    # Host tags on every record.
    assert all(r["process_index"] == 0 and "config_hash" in r
               and r["mesh"] == "data=8" for r in records)

    steps = [r for r in records if r["event"] == "step"]
    assert steps, "no step records emitted"
    windowed = steps[-1]
    # Step-time breakdown fields (rolling window).
    for key in ("data_ms", "dispatch_ms", "device_ms", "step_ms_p50",
                "step_ms_p95"):
        assert key in windowed, f"missing {key} in {sorted(windowed)}"
    # Throughput/MFU fields (peak_tflops was configured).
    assert windowed["tokens_per_sec"] > 0
    assert windowed["model_tflops"] > 0
    assert windowed["mfu"] > 0

    summary = [r for r in records if r["event"] == "summary"][-1]
    assert 0 <= summary["goodput"] <= 1
    assert summary["checkpoint_seconds"] > 0  # cadence + final saves
    assert summary["eval_seconds"] > 0
    assert summary["steps"] == 20 and summary["preempted"] is False

    # Chrome trace: valid JSON, required keys, the host phases present.
    events_list = load_trace(trace)
    assert all("ph" in e and "name" in e for e in events_list)
    spans = [e for e in events_list if e["ph"] == "X"]
    assert all("ts" in s and "dur" in s for s in spans)
    names = {s["name"] for s in spans}
    assert {"tfd.train.data", "tfd.train.dispatch",
            "tfd.train.device_wait", "tfd.train.cadence",
            "tfd.train.eval", "tfd.train.checkpoint",
            "tfd.train.compile"} <= names, names

    # The report tool regenerates the headline numbers from raw JSONL.
    assert report.main([jsonl]) == 0
    s = report.summarize(records)
    assert s["last_step"] == 20
    assert s["step_ms_p50"] > 0 and s["mean_mfu"] > 0
    assert s["goodput"] == summary["goodput"]

    # CSV sink: one row per step record, union header includes mfu.
    rows = list(open(tmp_path / "metrics.csv"))
    assert len(rows) == len(steps) + 1
    assert "mfu" in rows[0].split(",")


def test_vision_run_reports_images_per_sec(tmp_path):
    """The vision family flows through the same accountant with
    imgs/s + a real CNN FLOPs estimate (unit follows the task)."""
    jsonl = str(tmp_path / "metrics.jsonl")
    cfg = TrainConfig(
        dataset="synthetic", batch_size=128, train_steps=12,
        eval_every=0, log_every=4, eval_batch_size=128,
        compute_dtype="float32", mesh=MeshConfig(data=8),
        observe=ObserveConfig(metrics_jsonl=jsonl, peak_tflops=0.01))
    train(cfg)
    steps = [json.loads(line) for line in open(jsonl)
             if json.loads(line)["event"] == "step"]
    assert steps[-1]["images_per_sec"] > 0
    assert steps[-1]["mfu"] > 0


@pytest.mark.slow  # 158s on the CI box (jax.profiler capture startup
#                    dominates) — the single heaviest default-tier test
#                    before the round-6 curation moved it here
def test_profiler_window_closed_on_loop_exit(tmp_path):
    """Satellite regression: training that ends INSIDE the profiler's
    trace window must still finalize the trace (loop-exit stop), and
    stop() must be idempotent afterwards."""
    import glob
    import os

    from tensorflow_distributed_tpu.utils.profiling import StepProfiler

    profile_dir = str(tmp_path / "prof")
    cfg = TrainConfig(
        dataset="synthetic", batch_size=128, train_steps=8,
        eval_every=0, log_every=0, eval_batch_size=128,
        compute_dtype="float32", mesh=MeshConfig(data=8),
        profile_dir=profile_dir, profile_start_step=4,
        profile_num_steps=100)  # window extends past the last step
    train(cfg)
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "trace window left open at loop exit"
    StepProfiler(log_dir=profile_dir).stop()  # no-op, must not raise


def test_resumed_run_appends_to_jsonl(tmp_path):
    """A preempt-restart leg (--resume with a restorable checkpoint)
    APPENDS to the prior leg's JSONL; a fresh run replaces. The append
    decision keys off an actual restorable checkpoint, not the flag —
    schedulers pass --resume on every leg including the first.

    Runs in a subprocess with one retry, same rationale as
    test_loop_cli.test_train_resume_roundtrip_async_checkpoints: the
    resume-with-checkpoint pattern intermittently SIGSEGVs the XLA:CPU
    runtime on this container (seed-reproducible), and an in-process
    crash would abort the whole suite."""
    import subprocess
    import sys

    jsonl = str(tmp_path / "m.jsonl")
    script = """
import json
from tensorflow_distributed_tpu.config import (
    MeshConfig, ObserveConfig, TrainConfig)
from tensorflow_distributed_tpu.train.loop import train

jsonl, ckpt_dir = %r, %r

def run(steps):
    train(TrainConfig(
        dataset="synthetic", batch_size=128, train_steps=steps,
        eval_every=0, log_every=4, eval_batch_size=128,
        compute_dtype="float32", mesh=MeshConfig(data=8),
        checkpoint_dir=ckpt_dir, checkpoint_every=4, resume=True,
        observe=ObserveConfig(metrics_jsonl=jsonl)))

run(8)   # first leg: nothing to restore -> fresh file
first = [json.loads(line) for line in open(jsonl)]
assert [r["event"] for r in first].count("start") == 1
assert not any(r["event"] == "resumed" for r in first)

run(12)  # second leg: restores -> appends
both = [json.loads(line) for line in open(jsonl)]
events = [r["event"] for r in both]
assert events.count("start") == 2, events
assert "resumed" in events
assert both[:len(first)] == first  # leg 1 records preserved
print("RESUME_APPEND_OK")
""" % (jsonl, str(tmp_path / "ckpt"))
    for attempt in (1, 2):
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True,
                              timeout=300)
        if proc.returncode == 0:
            assert "RESUME_APPEND_OK" in proc.stdout
            return
        if proc.returncode >= 0:  # real assertion failure: no retry
            break
    raise AssertionError(
        f"resume-append subprocess failed (rc={proc.returncode}):\n"
        f"{proc.stdout}\n{proc.stderr[-2000:]}")


def test_observatory_closed_on_exception(tmp_path):
    """A run that dies mid-loop must still close the Observatory: the
    buffered CSV gets written, the trace is durable, and the process-
    global goodput counter is uninstalled (a later un-observed run
    must not charge time into a dead run's ledger)."""
    import pytest

    from tensorflow_distributed_tpu.observe import goodput

    csv_path = tmp_path / "metrics.csv"
    cfg = TrainConfig(
        dataset="synthetic", batch_size=128, train_steps=12,
        eval_every=0, log_every=2, eval_batch_size=128,
        compute_dtype="float32", mesh=MeshConfig(data=8),
        # checkpoint_dir is an existing FILE: the first cadence save's
        # makedirs raises, escaping the steady loop mid-run.
        checkpoint_dir=str(tmp_path / "not_a_dir"), checkpoint_every=4,
        observe=ObserveConfig(metrics_jsonl=str(tmp_path / "m.jsonl"),
                              metrics_csv=str(csv_path),
                              trace=str(tmp_path / "t.json")))
    (tmp_path / "not_a_dir").write_text("in the way")
    with pytest.raises(OSError):
        train(cfg)
    assert goodput.get_active() is None
    assert csv_path.exists(), "CSV sink never closed on exception"
    rows = list(open(csv_path))
    assert len(rows) >= 2  # header + at least one step row
    assert load_trace(str(tmp_path / "t.json"))  # trace durable too


def test_steptime_device_wait_appears_under_deep_dispatch(tmp_path):
    """With > 3 steps the loop's bounded async dispatch blocks on the
    oldest in-flight step — the device_wait phase must be recorded."""
    jsonl = str(tmp_path / "m.jsonl")
    cfg = TrainConfig(
        dataset="synthetic", batch_size=128, train_steps=10,
        eval_every=0, log_every=9, eval_batch_size=128,
        compute_dtype="float32", mesh=MeshConfig(data=8),
        observe=ObserveConfig(metrics_jsonl=jsonl))
    train(cfg)
    steps = [json.loads(line) for line in open(jsonl)
             if json.loads(line)["event"] == "step"]
    assert steps and steps[-1]["device_ms"] >= 0
    # No peak configured and no flops change nothing else: breakdown
    # fields still present without MFU.
    assert "step_ms_p50" in steps[-1]
