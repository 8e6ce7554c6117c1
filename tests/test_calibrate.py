"""Calibration loop (analysis/planner/calibrate.py): fit, profile IO,
and the score.detect_hardware preference. Fast tier is jax-free except
the two detect_hardware tests (CPU backend only)."""

import json
import os
import random

import pytest

from tensorflow_distributed_tpu.analysis.planner import calibrate
from tensorflow_distributed_tpu.analysis.planner.score import (
    Hardware, roofline_ms)


def _synthetic(F=5e9, B=2e9, C=1e8, overhead=0.0, n=16, noise=0.04,
               seed=0):
    rng = random.Random(seed)
    samples = []
    for _ in range(n):
        f = rng.uniform(1e6, 5e7)
        b = rng.uniform(1e5, 5e6)
        c = rng.choice([0.0, rng.uniform(1e4, 1e5)])
        ms = overhead + max(1e3 * f / F, 1e3 * b / B) + (
            1e3 * c / C if c else 0.0)
        samples.append({"flops": f, "bytes_accessed": b,
                        "collective_bytes": c,
                        "measured_ms": ms * rng.uniform(1 - noise,
                                                        1 + noise)})
    return samples


def test_fit_recovers_rates():
    fit = calibrate.fit_rates(_synthetic())
    assert fit["peak_flops"] == pytest.approx(5e9, rel=0.2)
    assert fit["ici_bw"] == pytest.approx(1e8, rel=0.3)
    assert fit["median_abs_rel_err"] < 0.1


def test_fit_recovers_overhead_intercept():
    # Two scales of the same shape: without the intercept no single
    # rate can fit both; with it the fit nails all four.
    fit = calibrate.fit_rates(_synthetic(overhead=12.0, noise=0.01))
    assert fit["overhead_ms"] == pytest.approx(12.0, rel=0.25)
    assert fit["median_abs_rel_err"] < 0.05


def test_fit_without_collectives_leaves_ici_none():
    samples = [s for s in _synthetic() if s["collective_bytes"] == 0]
    fit = calibrate.fit_rates(samples)
    assert fit["ici_bw"] is None


def test_fit_raises_on_empty():
    with pytest.raises(ValueError):
        calibrate.fit_rates([])
    with pytest.raises(ValueError):
        calibrate.fit_rates([{"flops": None, "bytes_accessed": 1,
                              "measured_ms": 0.0}])


def test_rel_errors_improve_under_fit():
    samples = _synthetic()
    fit = calibrate.fit_rates(samples)
    fitted = calibrate.rel_errors(samples, fit["peak_flops"],
                                  fit["hbm_bw"], fit["ici_bw"],
                                  fit["overhead_ms"])
    generic = calibrate.rel_errors(samples, 1e12, 2.5e10, 2.5e10)
    assert sorted(fitted)[len(fitted) // 2] \
        < sorted(generic)[len(generic) // 2]


def test_profile_roundtrip_atomic(tmp_path):
    fit = calibrate.fit_rates(_synthetic())
    profile = calibrate.make_profile(fit, "cpu", "kind-x",
                                     source="test", devices=8)
    assert profile["calibration_id"].startswith("cpu-")
    path = str(tmp_path / "calibration.json")
    calibrate.write_calibration(profile, path)
    assert not os.path.exists(path + ".tmp")  # tmp+rename
    loaded = calibrate.load_calibration(path)
    assert loaded == profile
    assert loaded["effective"]["peak_flops"] == fit["peak_flops"]


def test_profile_id_stable_under_provenance_changes():
    fit = calibrate.fit_rates(_synthetic())
    a = calibrate.make_profile(fit, "cpu", "k", source="one")
    b = calibrate.make_profile(fit, "cpu", "k", source="two")
    c = calibrate.make_profile(fit, "tpu", "k", source="one")
    assert a["calibration_id"] == b["calibration_id"]  # rates define it
    assert a["calibration_id"] != c["calibration_id"]


def test_load_calibration_rejects_junk(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"not": "a profile"}))
    with pytest.raises(ValueError):
        calibrate.load_calibration(str(path))
    path.write_text(json.dumps({"version": 99, "effective": {}}))
    with pytest.raises(ValueError):
        calibrate.load_calibration(str(path))


def test_samples_from_planbench(tmp_path):
    path = tmp_path / "sweep.jsonl"
    lines = [
        {"metric": "planbench_candidate", "key": "data=8/data",
         "flops": 5e7, "bytes_accessed": 2e7, "collective_bytes": 0.0,
         "measured_step_ms_min": 18.5},
        # No measurement (infeasible candidate) -> dropped.
        {"metric": "planbench_candidate", "key": "data=8/fsdp",
         "flops": 5e7, "bytes_accessed": 2e7},
        {"metric": "plan_checks", "pick_tol": 0.15},
    ]
    path.write_text("\n".join(json.dumps(ln) for ln in lines))
    samples = calibrate.samples_from_planbench(str(path))
    assert len(samples) == 1
    assert samples[0]["key"] == "data=8/data"
    assert samples[0]["measured_ms"] == 18.5


def test_samples_from_metrics_joins_compile_and_device_time(tmp_path):
    path = tmp_path / "m.jsonl"
    lines = [
        {"event": "compile", "program": "train_step", "flops": 6.5e8,
         "bytes_accessed": 3e8},
        {"event": "device_time", "program": "train_step",
         "device_ms_per_call": 31.5},
        # device_time without a compile record -> no sample.
        {"event": "device_time", "program": "mystery",
         "device_ms_per_call": 5.0},
        # explicit-null device_time -> no sample.
        {"event": "device_time", "program": "eval_step",
         "device_ms_per_call": None},
    ]
    path.write_text("\n".join(json.dumps(ln) for ln in lines))
    samples = calibrate.samples_from_metrics(str(path))
    assert len(samples) == 1
    assert samples[0]["key"] == "train_step"
    assert samples[0]["measured_ms"] == 31.5


def test_roofline_adds_calibrated_overhead():
    hw = Hardware(platform="cpu", device_kind="x", peak_flops=1e9,
                  hbm_bw=1e9, ici_bw=1e9, overhead_ms=7.0)
    out = roofline_ms({"flops": 1e6, "bytes_accessed": 1e6}, 0.0, hw)
    assert out["step_ms"] == pytest.approx(8.0)
    # Table hardware (overhead 0) is unchanged: uncalibrated
    # predictions stay stable.
    hw0 = Hardware(platform="cpu", device_kind="x", peak_flops=1e9,
                   hbm_bw=1e9, ici_bw=1e9)
    assert roofline_ms({"flops": 1e6, "bytes_accessed": 1e6},
                       0.0, hw0)["step_ms"] == pytest.approx(1.0)


def test_detect_hardware_prefers_matching_calibration():
    import jax

    from tensorflow_distributed_tpu.analysis.planner.score import (
        detect_hardware)

    kind = getattr(jax.devices()[0], "device_kind", "unknown")
    profile = {"version": 1, "calibration_id": "cpu-test123",
               "platform": jax.default_backend(),
               "device_kind": kind,
               "effective": {"peak_flops": 3e9, "hbm_bw": 1.5e9,
                             "ici_bw": None, "overhead_ms": 9.0}}
    hw = detect_hardware(calibration=profile)
    assert hw.peak_flops == 3e9
    assert hw.hbm_bw == 1.5e9
    assert hw.overhead_ms == 9.0
    assert hw.calibration_id == "cpu-test123"
    # Explicit overrides still beat the profile.
    assert detect_hardware(peak_tflops=2.0,
                           calibration=profile).peak_flops == 2e12


def test_detect_hardware_ignores_mismatched_calibration(capsys):
    from tensorflow_distributed_tpu.analysis.planner.score import (
        detect_hardware)

    profile = {"version": 1, "calibration_id": "tpu-zzz",
               "platform": "tpu", "device_kind": "TPU v5",
               "effective": {"peak_flops": 3e9, "hbm_bw": 1.5e9,
                             "ici_bw": 1e9}}
    hw = detect_hardware(calibration=profile)
    assert hw.calibration_id is None
    assert hw.peak_flops != 3e9
    assert "ignoring calibration profile" in capsys.readouterr().err


def test_cli_from_planbench(tmp_path):
    src = tmp_path / "sweep.jsonl"
    lines = []
    rng = random.Random(1)
    for i in range(6):
        f = rng.uniform(1e6, 5e7)
        lines.append({"metric": "planbench_candidate", "key": f"k{i}",
                      "flops": f, "bytes_accessed": f / 4,
                      "collective_bytes": 0.0,
                      "measured_step_ms_min": 1e3 * f / 4e9 + 2.0,
                      "platform": "cpu", "devices": 8})
    src.write_text("\n".join(json.dumps(ln) for ln in lines))
    out = tmp_path / "calibration.json"
    rc = calibrate.main(["--from-planbench", str(src),
                         "--platform", "cpu",
                         "--device-kind", "test-kind",
                         "--out", str(out)])
    assert rc == 0
    profile = calibrate.load_calibration(str(out))
    assert profile["platform"] == "cpu"
    assert profile["device_kind"] == "test-kind"
    assert profile["effective"]["peak_flops"] == pytest.approx(
        4e9, rel=0.3)
    assert profile["effective"]["overhead_ms"] == pytest.approx(
        2.0, rel=0.3)


def test_cli_no_samples_fails(tmp_path):
    src = tmp_path / "empty.json"
    src.write_text("")
    rc = calibrate.main(["--from-planbench", str(src),
                         "--device-kind", "k",
                         "--out", str(tmp_path / "c.json")])
    assert rc == 1


def test_plan_calibration_config_surface():
    """--plan-calibration feeds exactly two consumers (plan auto's
    roofline, the profiled device-time join); alone it is rejected as
    a silent no-op, like every other orphaned knob."""
    from tensorflow_distributed_tpu.config import TrainConfig, parse_args

    with pytest.raises(ValueError, match="plan_calibration"):
        TrainConfig(plan_calibration="calibration.json").validate()
    TrainConfig(plan="auto", model="gpt_lm", model_size="tiny",
                dataset="synthetic",
                plan_calibration="calibration.json").validate()
    TrainConfig(profile_dir="/tmp/prof",
                plan_calibration="calibration.json").validate()
    cfg = parse_args(["--profile-dir", "/tmp/prof",
                      "--plan-calibration", "cal.json"])
    assert cfg.plan_calibration == "cal.json"
