"""Regression ledger (observe/regress.py): manifest evaluation,
direction/tolerance semantics, the degraded-artifact drill, and the
clean pass over the committed set. Stdlib-only, jax-free."""

import json

import pytest

from tensorflow_distributed_tpu.observe import regress
from tensorflow_distributed_tpu.observe.regress import (
    Check, compare_artifact, compare_check, main, manifest_for,
    manifest_names, parse_artifact, render_table)


def test_parse_artifact_jsonl_and_json():
    jsonl = "\n".join([
        json.dumps({"metric": "a", "value": 1}),
        "not json",
        json.dumps({"metric": "a", "value": 2}),  # rerun: last wins
        json.dumps({"no_metric": True}),
    ])
    doc = parse_artifact(jsonl, "jsonl")
    assert doc == {"a": {"metric": "a", "value": 2}}
    doc = parse_artifact(json.dumps({"x": {"y": 3}}), "json")
    assert doc["x"]["y"] == 3


def _cmp(check, base, fresh):
    return compare_check(check, base, fresh)["verdict"]


def test_numeric_direction_and_band():
    c = Check("m.value", "higher", rtol=0.1)
    base = {"m": {"value": 100.0}}
    assert _cmp(c, base, {"m": {"value": 95.0}}) == "ok"      # in band
    assert _cmp(c, base, {"m": {"value": 85.0}}) == "regression"
    assert _cmp(c, base, {"m": {"value": 120.0}}) == "improved"
    c = Check("m.value", "lower", atol=0.5)
    base = {"m": {"value": 2.0}}
    assert _cmp(c, base, {"m": {"value": 2.4}}) == "ok"
    assert _cmp(c, base, {"m": {"value": 2.6}}) == "regression"
    assert _cmp(c, base, {"m": {"value": 1.0}}) == "improved"


def test_zero_baseline_uses_atol():
    # "must stay 0" counts: relative tolerance is useless at base 0.
    c = Check("m.value", "lower", rtol=0.5, atol=0.0)
    assert _cmp(c, {"m": {"value": 0}}, {"m": {"value": 1}}) \
        == "regression"
    assert _cmp(c, {"m": {"value": 0}}, {"m": {"value": 0}}) == "ok"


def test_truthy_semantics():
    c = Check("m.ok", "truthy")
    assert _cmp(c, {"m": {"ok": True}}, {"m": {"ok": True}}) == "ok"
    assert _cmp(c, {"m": {"ok": True}}, {"m": {"ok": False}}) \
        == "regression"
    # Baseline already failing -> skip, not a block on unrelated PRs.
    assert _cmp(c, {"m": {"ok": False}}, {"m": {"ok": False}}) \
        == "skip"


def test_equal_and_missing_semantics():
    c = Check("m.n", "equal")
    assert _cmp(c, {"m": {"n": 32}}, {"m": {"n": 32}}) == "ok"
    assert _cmp(c, {"m": {"n": 32}}, {"m": {"n": 31}}) == "regression"
    # Gate disappeared from the fresh artifact -> regression.
    assert _cmp(c, {"m": {"n": 32}}, {}) == "regression"
    # New metric (not in baseline) -> skip.
    assert _cmp(c, {}, {"m": {"n": 32}}) == "skip"


def test_manifest_covers_the_committed_artifacts():
    names = manifest_names()
    for required in ("GRADSYNC.json", "SERVEBENCH.json",
                     "SLOBENCH.json", "FIREBENCH.json",
                     "ELASTICBENCH.json", "PLANBENCH.json"):
        assert required in names
    assert all(manifest_for(n) is not None for n in names)
    assert manifest_for("UNKNOWN.json") is None


def test_compare_artifact_explicit_paths(tmp_path):
    base = tmp_path / "base.json"
    fresh = tmp_path / "fresh.json"
    base.write_text(json.dumps(
        {"metric": "fire_goodput", "value": 0.9}) + "\n" + json.dumps(
        {"metric": "fire_checks", "goodput_ok": True,
         "lost_requests": 0, "token_identical": 32}) + "\n" + json.dumps(
        {"metric": "fire_tokens_per_sec", "value": 1800.0}))
    fresh.write_text(json.dumps(
        {"metric": "fire_goodput", "value": 0.5}) + "\n" + json.dumps(
        {"metric": "fire_checks", "goodput_ok": True,
         "lost_requests": 0, "token_identical": 32}) + "\n" + json.dumps(
        {"metric": "fire_tokens_per_sec", "value": 1801.0}))
    findings = compare_artifact("FIREBENCH.json",
                                fresh_path=str(fresh),
                                baseline_path=str(base))
    by_check = {f["check"]: f["verdict"] for f in findings}
    assert by_check["fire_goodput.value"] == "regression"
    assert by_check["fire_tokens_per_sec.value"] == "ok"
    assert by_check["fire_checks.goodput_ok"] == "ok"
    assert "REGRESSION" in render_table(findings)


def test_committed_set_passes_clean():
    # The t1 smoke contract: an untouched working tree vs HEAD has
    # zero regressions. Skip when git can't serve a baseline (e.g. a
    # tarball checkout).
    if regress.baseline_text("FIREBENCH.json") is None:
        pytest.skip("no git baseline available")
    findings = []
    for name in manifest_names():
        findings.extend(compare_artifact(name))
    bad = [f for f in findings if f["verdict"] == "regression"]
    assert not bad, bad


def test_cli_degraded_artifact_exits_nonzero(tmp_path, capsys):
    if regress.baseline_text("FIREBENCH.json") is None:
        pytest.skip("no git baseline available")
    from tensorflow_distributed_tpu.benchmarks.calibbench import (
        degraded_copy)

    degraded = degraded_copy("FIREBENCH.json", {"fire_goodput": 0.5})
    rc = main(["--artifact", "FIREBENCH.json", "--fresh", degraded])
    out = capsys.readouterr()
    assert rc == 1
    assert "REGRESSION" in out.out
    assert "fire_goodput.value" in out.out


def test_cli_list_prints_manifest(capsys):
    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "FIREBENCH.json" in out
    assert "fire_goodput.value" in out


def test_cli_missing_fresh_artifact_is_regression(tmp_path, capsys):
    rc = main(["--artifact", "FIREBENCH.json",
               "--fresh", str(tmp_path / "nope.json")])
    assert rc == 1
