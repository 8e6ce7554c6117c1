"""Device-time attribution (observe/xprof.py) + trace.preload clock.

Fast tier is jax-free: canned Perfetto event lists through the parse/
attribution pipeline, plus the value-pinned ChromeTracer.preload
clock-shift test. One slow e2e captures a real profiler window on a
tiny GPT step and attributes it.
"""

import gzip
import json
import os

import pytest

from tensorflow_distributed_tpu.observe import xprof
from tensorflow_distributed_tpu.observe.trace import ChromeTracer


def _op(module, op, ts, dur, pid=1, tid=1):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur,
            "name": op, "args": {"hlo_module": module, "hlo_op": op}}


def _procname(pid, name):
    return {"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": name}}


def test_union_counts_concurrent_lanes_once():
    # Two ops overlapping [0,10) and [5,15) on different threads:
    # wall is the union (15), op_ms the sum (20).
    events = [_op("jit_p", "dot.1", 0, 10, tid=1),
              _op("jit_p", "dot.2", 5, 10, tid=2)]
    mods = xprof.attribute(events)["modules"]
    assert mods["jit_p"]["wall_us"] == 15.0
    assert mods["jit_p"]["op_us"] == 20.0
    assert mods["jit_p"]["ops"] == 2


def test_calls_is_modal_op_count_scan_ops_dont_inflate():
    # 3 invocations: two ops appear 3x each, one scan-body op 30x.
    events = []
    t = 0.0
    for i in range(3):
        events.append(_op("jit_p", "dot.1", t, 1))
        events.append(_op("jit_p", "add.2", t + 1, 1))
        t += 2
    for i in range(30):
        events.append(_op("jit_p", "while.body.mul", t, 0.1))
        t += 0.1
    assert xprof.attribute(events)["modules"]["jit_p"]["calls"] == 3


def test_collective_family_split_and_exposed():
    # all-reduce [0, 10); compute overlaps [0, 6) -> exposed = 4.
    events = [_op("jit_p", "all-reduce.1", 0, 10, tid=1),
              _op("jit_p", "fusion.2", 0, 6, tid=2),
              _op("jit_p", "all-gather.3", 20, 5, tid=1)]
    m = xprof.attribute(events)["modules"]["jit_p"]
    assert m["collective_us"] == 15.0
    assert m["exposed_collective_us"] == pytest.approx(9.0)
    assert m["collective_families"] == {"all_gather": 5.0,
                                        "all_reduce": 10.0}


def test_device_pid_filter_beats_host_mirror():
    events = [_procname(1, "/host:CPU"),
              _procname(2, "/device:TPU:0"),
              _op("jit_p", "dot.1", 0, 100, pid=1),   # host mirror
              _op("jit_p", "dot.1", 0, 7, pid=2)]     # device truth
    attr = xprof.attribute(events)
    assert attr["coarse"] is False
    assert attr["modules"]["jit_p"]["wall_us"] == 7.0


def test_coarse_without_device_process():
    events = [_procname(1, "/host:CPU"),
              _op("jit_p", "dot.1", 0, 5, pid=1)]
    assert xprof.attribute(events)["coarse"] is True


def test_match_program_exact_prefix_and_sanitized():
    programs = ["train_step", "serve_prefill_b16",
                "generate_n8_t0.7_k5_p1"]
    assert xprof.match_program("jit_train_step", programs) \
        == "train_step"
    assert xprof.match_program("jit_serve_prefill_b16", programs) \
        == "serve_prefill_b16"
    # The sanitized name is what the module carries (dots -> _).
    assert xprof.match_program("jit_generate_n8_t0_7_k5_p1",
                               programs) == "generate_n8_t0.7_k5_p1"
    # Numeric suffixes a lowering may append fall back to the prefix.
    assert xprof.match_program("jit_train_step_1", programs) \
        == "train_step"
    assert xprof.match_program("jit_unrelated", programs) is None


def test_device_time_records_null_on_missing_trace(tmp_path):
    recs = xprof.device_time_records(str(tmp_path))
    assert len(recs) == 1
    rec = recs[0]
    # Explicit-null contract: every measurement field present and None.
    for field in xprof.DEVICE_TIME_FIELDS:
        assert rec[field] is None
    assert "no trace under" in rec["reason"]


def _write_trace(tmp_path, events, host="testhost"):
    run = tmp_path / "plugins" / "profile" / "2026_08_03_00_00_00"
    run.mkdir(parents=True)
    path = run / f"{host}.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return str(path)


def test_device_time_records_from_written_trace(tmp_path):
    events = [_procname(1, "/host:CPU")]
    t = 0.0
    for _ in range(4):
        events.append(_op("jit_train_step", "dot.1", t, 100))
        events.append(_op("jit_train_step", "fusion.2", t + 100, 50))
        t += 1000
    _write_trace(tmp_path, events)
    recs = xprof.device_time_records(str(tmp_path),
                                     programs=["train_step"])
    assert len(recs) == 1
    rec = recs[0]
    assert rec["program"] == "train_step"
    assert rec["calls"] == 4
    assert rec["device_ms"] == pytest.approx(0.6)
    assert rec["device_ms_per_call"] == pytest.approx(0.15)
    assert rec["coarse"] is True


def test_device_time_records_newest_run_dir_wins(tmp_path):
    old = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    old.mkdir(parents=True)
    with gzip.open(old / "h.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": [_op("jit_old", "dot.1", 0, 1)]}, f)
    _write_trace(tmp_path, [_op("jit_new", "dot.1", 0, 1)])
    found = xprof.find_trace_file(str(tmp_path))
    assert "2026_08_03" in found


def test_device_time_unmatched_module_still_reported(tmp_path):
    _write_trace(tmp_path, [_op("jit_mystery", "dot.1", 0, 10)])
    recs = xprof.device_time_records(str(tmp_path),
                                     programs=["train_step"])
    assert recs[0]["program"] is None
    assert recs[0]["module"] == "jit_mystery"


def test_with_predictions_joins_roofline():
    from tensorflow_distributed_tpu.analysis.planner.score import (
        Hardware)

    hw = Hardware(platform="cpu", device_kind="x", peak_flops=1e9,
                  hbm_bw=1e9, ici_bw=1e9, calibration_id="cpu-abc")
    recs = [{"program": "train_step", "device_ms_per_call": 5.0},
            {"program": None, "module": "jit_z", "device_ms": 1.0}]
    costs = {"train_step": {"flops": 2e6, "bytes_accessed": 1e6}}
    out = xprof.with_predictions(recs, costs, hw)
    # max(2e6/1e9, 1e6/1e9) * 1e3 = 2.0 ms
    assert out[0]["predicted_ms_per_call"] == pytest.approx(2.0)
    assert out[0]["calibration_id"] == "cpu-abc"
    assert "predicted_ms_per_call" not in out[1]
    # hw=None passes through untouched.
    assert xprof.with_predictions(recs, costs, None) == recs


def test_with_predictions_includes_calibrated_overhead():
    from tensorflow_distributed_tpu.analysis.planner.score import (
        Hardware)

    hw = Hardware(platform="cpu", device_kind="x", peak_flops=1e9,
                  hbm_bw=1e9, ici_bw=1e9, overhead_ms=3.5)
    out = xprof.with_predictions(
        [{"program": "p", "device_ms_per_call": 9.0}],
        {"p": {"flops": 1e6, "bytes_accessed": 1e6}}, hw)
    assert out[0]["predicted_ms_per_call"] == pytest.approx(4.5)


# --- trace.preload clock shift (satellite: resume-leg counters) -------

def test_preload_clock_shift_keeps_counters_monotone(tmp_path):
    """Value-pinned: after preloading a dead leg's events (including
    counter tracks), the resumed tracer's FIRST new counter sample
    must land exactly gap_us after the last preloaded event's end —
    a resumed leg's counter track never runs backwards."""
    path = str(tmp_path / "t.json")
    fake_now = [100.0]
    prior = [
        {"ph": "C", "name": "slots", "pid": 0, "tid": 0,
         "ts": 1_000.0, "args": {"slots": 2}},
        {"ph": "X", "name": "tfd.serve.token_fetch", "cat": "serve", "pid": 0,
         "tid": 0, "ts": 2_000.0, "dur": 500.0},
        {"ph": "C", "name": "slots", "pid": 0, "tid": 0,
         "ts": 2_400.0, "args": {"slots": 3}},
    ]
    tracer = ChromeTracer(path, clock=lambda: fake_now[0])
    tracer.preload(prior, gap_us=1_000.0)
    # Clock has not advanced since construction: the new event's ts is
    # exactly (last preloaded end = 2000 + 500) + gap = 3500.
    tracer.counter("slots", slots=4)
    tracer.close()
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    counters = [e for e in events if e.get("ph") == "C"]
    assert [c["ts"] for c in counters] == [1_000.0, 2_400.0, 3_500.0]
    # And with wall time advancing, later samples stay monotone.
    tracer2 = ChromeTracer(path, clock=lambda: fake_now[0])
    tracer2.preload(prior, gap_us=1_000.0)
    fake_now[0] += 0.25  # +250 ms wall
    tracer2.counter("slots", slots=5)
    tracer2.close()
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    assert events[-1]["ts"] == pytest.approx(3_500.0 + 250_000.0)
    assert events[-1]["ts"] > max(e["ts"] for e in prior)


# --- slow: real capture -> parse -> attribution e2e -------------------

@pytest.mark.slow
def test_xprof_e2e_tiny_gpt_step(tmp_path):
    """Capture a profiler window around real tiny-GPT train steps and
    attribute the trace: train_step must come back with positive
    device time and a calls estimate matching the traced steps."""
    import jax
    import numpy as np
    import optax

    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.models import transformer
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.train.state import (
        create_train_state)
    from tensorflow_distributed_tpu.train.step import make_train_step
    from tensorflow_distributed_tpu.train.tasks import (
        make_mlm_loss, mlm_batch_shardings)
    from tensorflow_distributed_tpu.utils.profiling import StepProfiler

    mesh = make_mesh(MeshConfig(data=1), jax.devices()[:1])
    model = transformer.gpt_lm(mesh=mesh, size="tiny", max_len=16,
                               dropout_rate=0.0)
    sample = np.zeros((2, 16), np.int32)
    state = create_train_state(model, optax.adam(1e-3), sample, mesh)
    step = make_train_step(mesh, loss=make_mlm_loss(),
                           batch_shardings=mlm_batch_shardings(mesh))
    batch = {"tokens": np.ones((2, 16), np.int32),
             "targets": np.ones((2, 16), np.int32),
             "mask": np.ones((2, 16), np.float32)}
    state, m = step(state, batch)  # compile outside the window
    jax.block_until_ready(m)
    prof = StepProfiler(log_dir=str(tmp_path), start_step=1,
                        num_steps=3)
    for i in range(1, 6):
        prof.observe(i, pending=m)
        state, m = step(state, batch)
    prof.stop(pending=m)
    assert prof.captured
    recs = xprof.device_time_records(str(tmp_path),
                                     programs=["train_step"])
    by_prog = {r["program"]: r for r in recs}
    assert "train_step" in by_prog, recs
    rec = by_prog["train_step"]
    assert rec["device_ms"] and rec["device_ms"] > 0
    assert rec["calls"] == 3
    assert rec["collective_ms"] == 0.0
