"""Traffic generators: deterministic under a seed, clipped as stated, the
same work for every seed. Percentile arithmetic."""

import json
import os

import pytest

from harness import stats, traffic
from harness.loader import ROOT

MIXES = ["chat-lognormal-0.8knee", "chat-lognormal-1.3knee"]


def mix(name):
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.serve_requests(mix(name), 2 ** 31 + 11, 20, 50257)
    b = traffic.serve_requests(mix(name), 2 ** 31 + 11, 20, 50257)
    assert a == b
    c = traffic.serve_requests(mix(name), 12, 20, 50257)
    assert a != c


@pytest.mark.parametrize("name", MIXES)
def test_lengths_clipped_and_rate_kept(name):
    m = mix(name)
    reqs = traffic.serve_requests(m, 5, 30, 50257)
    span = 30 * m.get("stop_fraction", 1.0)
    assert len(reqs) == round(m["rate_rps"] * span)
    for r in reqs:
        assert m["prompt_len"]["min"] <= len(r["prompt"]) \
            <= m["prompt_len"]["max"]
        assert m["output_len"]["min"] <= r["max_new_tokens"] \
            <= m["output_len"]["max"]
        assert len(r["prompt"]) + r["max_new_tokens"] <= 1024
        assert all(0 <= t < 50257 for t in r["prompt"])
    arrivals = [r["arrival_s"] for r in reqs]
    assert arrivals == sorted(arrivals)
    assert arrivals[-1] == pytest.approx(span, abs=1e-3)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    def work(seed):
        reqs = traffic.serve_requests(mix(name), seed, 30, 50257)
        gaps = [b["arrival_s"] - a["arrival_s"]
                for a, b in zip([{"arrival_s": 0.0}] + reqs, reqs)]
        return (sorted(len(r["prompt"]) for r in reqs),
                sorted(r["max_new_tokens"] for r in reqs), sorted(gaps))
    a, b = work(1), work(2 ** 31 + 5)
    assert a[:2] == b[:2]
    assert a[2] == pytest.approx(b[2], abs=1e-5)   # arrivals keep 6 digits


@pytest.mark.parametrize("name", MIXES)
def test_schedule_is_the_mixs_and_tokens_are_the_seeds(name):
    m = mix(name)
    a = traffic.serve_requests(m, 1, 30, 50257)
    b = traffic.serve_requests(m, 2, 30, 50257)
    shape = lambda rs: [(len(r["prompt"]), r["max_new_tokens"],  # noqa: E731
                         r["arrival_s"]) for r in rs]
    assert shape(a) == shape(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    free = dict(m)
    del free["schedule_seed"]
    assert shape(traffic.serve_requests(free, 1, 30, 50257)) != \
        shape(traffic.serve_requests(free, 2, 30, 50257))


def test_lognormal_quantiles_median_and_clip():
    q = traffic.lognormal_quantiles(1001, 128, 0.8, 16, 768)
    assert q == sorted(q) and q[500] == 128
    assert q[0] == 16 and q[-1] == 768


def test_train_shape_and_wrong_kind():
    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "train-b8x1024-dp4.json")) as f:
        m = json.load(f)
    assert traffic.train_shape(m, 4) == {
        "rows_per_chip": 8, "seq_len": 1024, "global_batch": 32}
    with pytest.raises(ValueError):
        traffic.train_shape(m, 1)
    with pytest.raises(ValueError):
        traffic.serve_requests(m, 0, 10, 64)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, 3, 4], 95) == 4
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_spread_is_iqr_over_median():
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0
    # statistics.quantiles([1..6], n=4) = [1.75, 3.5, 5.25]
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
