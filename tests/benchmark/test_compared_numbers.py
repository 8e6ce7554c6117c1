"""Every number ``correct`` compares is in the result's line under a key
of its own, the last there, and on the last lines of standard error, each
beside its limit (PR 32): what a record of a run that is not correct
keeps is the end of both."""

import contextlib
import importlib.util
import io
import json
import os

import numpy as np

from harness import common
from harness.loader import ROOT


def test_the_results_line_ends_with_what_was_compared():
    compared = common.Compared()
    assert compared("gap_max", np.float32(0.5), 2.4, True, "(a note)")
    assert not compared("gap_mean", float("nan"), 0.1, False)
    assert not compared("failed_requests", 3, 0, False)
    line = common.result_line(False, 10, 3, {}, {"platform": "tpu"},
                              {"device_ops": [], "idle_gaps": []},
                              compared.rows)
    out = json.loads(line, parse_constant=lambda c: 1 / 0)   # strict JSON
    assert list(out)[-1] == "compared"
    assert out["compared"] == {
        "gap_max": {"value": 0.5, "limit": 2.4, "ok": True},
        "gap_mean": {"value": "nan", "limit": 0.1, "ok": False},
        "failed_requests": {"value": 3.0, "limit": 0.0, "ok": False}}
    assert compared.lines == [
        "correct: gap_max = 0.5 (limit 2.4) ok",
        "correct: gap_mean = nan (limit 0.1) FAILED",
        "correct: failed_requests = 3 (limit 0) FAILED"]
    assert "compared" not in json.loads(
        common.result_line(True, 1, 0, {}, {}))


def test_a_runs_last_lines_on_standard_error_are_the_numbers(one_chip_env):
    spec = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(ROOT, "perfbench", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    # standard error alone: the program keeps the standard output it
    # finds at its first run for its later ones (observe.StdoutSink)
    stderr = io.StringIO()
    with contextlib.redirect_stderr(stderr):
        rc = run.main(["--workload", "gpt2l-serve-steady", "--seed",
                       str(2 ** 31 + 41), "--seconds", "2", "--trace", "0",
                       "--rehearse"])
    assert rc == common.EXIT_REHEARSED
    err = stderr.getvalue().strip().splitlines()
    assert [line.split(" = ")[0] for line in err[-3:]] == [
        "correct: failed_requests", "correct: served_token_gap_max",
        "correct: served_token_gap_mean"]
    assert all("(limit " in line and line.endswith(" ok")
               for line in err[-3:])
