"""The same for the serve runner: a token altered where it is produced."""

from harness import serve_runner
from harness.loader import Cell


def run(fault):
    return serve_runner.run(Cell("gpt2l-serve-steady"), seed=2 ** 31 + 19,
                            seconds=2.0, trace=False, rehearse=True,
                            fault=fault, require_tpu=False)


def test_sound_run_is_correct(one_chip_env):
    res = run(None)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_ttft_p50_ms",
                                   "serve_tpot_p95_ms", "setup_s"}


def test_altered_token_is_not_correct(one_chip_env):
    res = run("altered_token")
    assert res["correct"] is False
    limits = Cell("gpt2l-serve-steady").config["rehearsal"]["correct_limits"]
    assert res["check"]["max"] > limits["served_token_gap_max"]
