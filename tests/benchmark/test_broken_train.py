"""Drive a whole run of the train runner (the look for a chip skipped, tiny
sizes, the CPU) with the timed path broken underneath, and see ``correct``
come out false; and a sound run come out true."""

import pytest

from harness import train_runner
from harness.loader import Cell


def run(fault):
    return train_runner.run(Cell("gpt2m-train-dp1"), seed=2 ** 31 + 17,
                            seconds=0.3, trace=False, rehearse=True,
                            fault=fault, require_tpu=False)


def test_sound_run_is_correct(one_chip_env):
    res = run(None)
    assert res["correct"] is True
    assert res["attempted"] >= 10 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("fault,number", [
    ("frozen_state", "delta_norm_gap"),    # a step that returns its state
    ("half_batch", "loss_rel_step1"),      # a part of the batch left out
])
def test_broken_step_is_not_correct(one_chip_env, fault, number):
    res = run(fault)
    assert res["correct"] is False
    limits = Cell("gpt2m-train-dp1").config["rehearsal"]["correct_limits"]
    limit = limits["loss_rel" if number.startswith("loss") else number]
    assert res["check"]["numbers"][number] > limit
