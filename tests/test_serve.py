"""Continuous-batching serving engine (serve/).

The load-bearing contract: engine outputs are TOKEN-IDENTICAL to
one-shot greedy generate() for every request — batching must not
change results. Plus: slot reuse after completion, the scheduler's
decode-priority starvation bound, bounded prefill program count, the
serve metrics artifact, the compile-cache counter, and the
compilecache override fix.

Scheduler-policy tests run against a fake host-side engine (no jax
compiles — they stay in the default tier); everything that compiles
the tiny GPT is marked slow per the repo's tier rules.
"""

from __future__ import annotations

import functools
import json

import numpy as np
import pytest

from tensorflow_distributed_tpu.serve.buckets import (
    default_buckets, parse_buckets, pick_bucket)
from tensorflow_distributed_tpu.serve.engine import EngineSurface
from tensorflow_distributed_tpu.serve.scheduler import Request, Scheduler


# --- buckets (pure host) -----------------------------------------------

def test_bucket_ladder_and_pick():
    assert default_buckets(100, min_bucket=16) == (16, 32, 64, 128)
    assert default_buckets(16) == (16,)
    # The cap clamps the ladder to the cache length: no unusable
    # power-of-two overshoot past max_len.
    assert default_buckets(100, cap=100) == (16, 32, 64, 100)
    assert default_buckets(128, cap=128) == (16, 32, 64, 128)
    assert default_buckets(8, min_bucket=16, cap=8) == (8,)
    with pytest.raises(ValueError, match="exceeds the bucket cap"):
        default_buckets(100, cap=64)
    assert parse_buckets("8,32,64") == (8, 32, 64)
    assert pick_bucket(1, (16, 32)) == 16
    assert pick_bucket(17, (16, 32)) == 32
    with pytest.raises(ValueError):
        pick_bucket(33, (16, 32))
    with pytest.raises(ValueError):
        parse_buckets("64,32")  # not ascending
    with pytest.raises(ValueError):
        parse_buckets("a,b")


def test_serve_config_validation():
    from tensorflow_distributed_tpu.config import TrainConfig

    cfg = TrainConfig(mode="serve", model="gpt_lm")
    cfg.validate()
    bad = TrainConfig(mode="serve", model="mnist_cnn")
    with pytest.raises(ValueError, match="causal LM"):
        bad.validate()
    bad = TrainConfig(mode="serve", model="gpt_lm")
    bad.serve.num_slots = 0
    with pytest.raises(ValueError, match="num_slots"):
        bad.validate()
    bad = TrainConfig(mode="serve", model="gpt_lm")
    bad.serve.buckets = "64,16"
    with pytest.raises(ValueError, match="ascending"):
        bad.validate()
    # The TRAIN mesh flags keep their pure-data contract under serve;
    # sharding the replica is --serve.mesh-model's job (and the
    # rejection must say so).
    bad = TrainConfig(mode="serve", model="gpt_lm")
    bad.mesh.model = 2
    with pytest.raises(ValueError, match="serve.mesh-model"):
        bad.validate()
    bad = TrainConfig(mode="serve", model="gpt_lm")
    bad.serve.mesh_model = 0
    with pytest.raises(ValueError, match="mesh_model"):
        bad.validate()


# --- compile-program cache counter (pure host) -------------------------

def test_compile_cache_counter():
    from tensorflow_distributed_tpu.models.generate import (
        compile_cache_stats, lookup_program)

    @functools.lru_cache(maxsize=8)
    def factory(key):
        return object()

    base = compile_cache_stats()
    a = lookup_program(factory, 1)          # miss
    b = lookup_program(factory, 1)          # hit
    c = lookup_program(factory, 2)          # miss
    assert a is b and c is not a
    now = compile_cache_stats()
    assert now["misses"] - base["misses"] == 2
    assert now["hits"] - base["hits"] == 1


def test_compile_cache_miss_emits_observe_record():
    from tensorflow_distributed_tpu.models.generate import lookup_program
    from tensorflow_distributed_tpu.observe import registry as reg

    @functools.lru_cache(maxsize=8)
    def factory2(key):
        return object()

    r = reg.MetricsRegistry()
    reg.set_active(r)
    try:
        lookup_program(factory2, 7)
    finally:
        reg.set_active(None)
    events = [x for x in r.records if x["event"] == "compile_cache"]
    assert len(events) == 1 and events[0]["result"] == "miss"
    assert events[0]["program"] == "factory2"


# --- compilecache: the directory is placed from outside ----------------

def test_persistent_cache_dir_is_placed_from_outside(tmp_path,
                                                      monkeypatch):
    import os

    import jax

    from tensorflow_distributed_tpu.utils import compilecache

    prev = jax.config.jax_compilation_cache_dir
    try:
        # JAX_COMPILATION_CACHE_DIR set: JAX already uses it, so the
        # program sets NO directory in code and reports that one...
        env_dir = str(tmp_path / "env-xla-cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        jax.config.update("jax_compilation_cache_dir", None)
        assert compilecache.enable_persistent_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir is None
        # ...unset (or empty, as the subprocess tests pass it): the
        # fixed <checkout>/.cache/xla.
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        default = os.path.join(repo, ".cache", "xla")
        for unset in (lambda: monkeypatch.setenv(
                          "JAX_COMPILATION_CACHE_DIR", ""),
                      lambda: monkeypatch.delenv(
                          "JAX_COMPILATION_CACHE_DIR")):
            unset()
            jax.config.update("jax_compilation_cache_dir", None)
            assert compilecache.enable_persistent_cache() == default
            assert jax.config.jax_compilation_cache_dir == default
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# --- scheduler policy against a fake engine (no compiles) --------------

class _FakeEngine(EngineSurface):
    """Host-only stand-in with the SlotDecodeEngine surface the
    scheduler drives: deterministic token stream (rid*100 + step)."""

    def __init__(self, num_slots=2, max_len=256):
        self.num_slots = num_slots
        self.max_len = max_len
        self.buckets = (32, 64)
        self.active = np.zeros((num_slots,), bool)
        self.slot_rid = {}
        self.counts = {}
        self.prefills = 0
        self.prefill_compiles = 0
        self.decode_steps = 0

    def fits(self, plen, max_new):
        return plen + max_new <= self.max_len

    def free_slots(self):
        return [s for s in range(self.num_slots) if not self.active[s]]

    def occupancy(self):
        return float(self.active.sum()) / self.num_slots

    def prefill(self, prompt, slot):
        rid = int(prompt[0])  # tests encode rid in the prompt head
        self.active[slot] = True
        self.slot_rid[slot] = rid
        self.counts[rid] = 0
        self.prefills += 1
        return rid * 100

    def step(self):
        out = np.zeros((self.num_slots,), np.int32)
        for s in range(self.num_slots):
            if self.active[s]:
                rid = self.slot_rid[s]
                self.counts[rid] += 1
                out[s] = rid * 100 + self.counts[rid]
        self.decode_steps += 1
        return out

    def free(self, slot):
        self.active[slot] = False


def _fake_requests(n, max_new=6):
    return [Request(rid=i, prompt=np.asarray([i], np.int32),
                    max_new_tokens=max_new) for i in range(n)]


def test_scheduler_fifo_and_tokens():
    eng = _FakeEngine(num_slots=2)
    done = Scheduler(eng, decode_priority=3).run(_fake_requests(5))
    assert len(done) == 5
    by_rid = {c.rid: c for c in done}
    for rid, c in by_rid.items():
        assert c.tokens == [rid * 100 + j for j in range(6)]
        assert c.finish == "length"
    # FIFO: a later request never FINISHES before an earlier one
    # STARTS (2 slots, equal lengths => finish order is start order).
    finish_order = [c.rid for c in done]
    assert finish_order == sorted(finish_order)


@pytest.mark.parametrize("K", [1, 3])
def test_scheduler_starvation_bound(K):
    eng = _FakeEngine(num_slots=2)
    done = Scheduler(eng, decode_priority=K).run(
        _fake_requests(7, max_new=9))
    # Head-of-line bound: no request waited more than K decode steps
    # once it was admittable (queue head + free slot).
    assert max(c.queue_steps for c in done) <= K
    assert eng.decode_steps > 0 and eng.prefills == 7


# The admission clock counts every decode iteration since the last
# ADMISSION. A scripted clock: a decode step is 10 ms, a prefill 1 ms,
# so a request due at 100.5 ms comes due in the tenth decode iteration.

_DUE_AFTER_10 = 0.1005


def _clocked_run(K, arrivals, max_new, num_slots=4):
    """Request ``i`` is due at ``arrivals[i]`` on the scripted clock and
    asks ``max_new[i]`` tokens (request 0 stays live throughout, so the
    engine never sleeps). Returns the completions by rid, the summary
    and, by rid, the decode steps the engine had taken at its prefill."""
    eng = _FakeEngine(num_slots=num_slots)
    admitted_at = {}
    prefill = eng.prefill

    def logged_prefill(prompt, slot):
        admitted_at[int(prompt[0])] = eng.decode_steps
        return prefill(prompt, slot)

    eng.prefill = logged_prefill
    sched = Scheduler(
        eng, decode_priority=K,
        clock=lambda: 0.01 * eng.decode_steps + 0.001 * eng.prefills)
    done = {c.rid: c for c in sched.run(
        [Request(rid=i, prompt=np.asarray([i], np.int32),
                 max_new_tokens=n, arrival_s=at)
         for i, (at, n) in enumerate(zip(arrivals, max_new))])}
    return done, sched.summary, admitted_at


def test_an_arrival_on_a_quiet_engine_is_admitted_at_once():
    """K = 4; request 0 has decoded ten iterations since the engine
    last admitted when request 1 comes due with a slot free: it goes in
    in that iteration, having endured no decode step."""
    done, summary, admitted_at = _clocked_run(
        4, [0.0, _DUE_AFTER_10], [40, 5])
    assert admitted_at == {0: 0, 1: 10}
    assert done[1].queue_steps == 0
    assert summary["admissions"] == 2
    assert summary["admitted_at_once"] == 2 == sum(
        c.queue_steps == 0 for c in done.values())


def test_a_burst_is_spaced_by_the_admission_clock():
    """K = 3; three requests come due in the same iteration with three
    slots free: they enter 0, K and 2K decode iterations later, and
    queue_steps counts head-of-line iterations only."""
    K = 3
    done, summary, admitted_at = _clocked_run(
        K, [0.0] + [_DUE_AFTER_10] * 3, [40, 5, 5, 5])
    assert [admitted_at[r] for r in (1, 2, 3)] == [10, 10 + K, 10 + 2 * K]
    assert [done[r].queue_steps for r in (1, 2, 3)] == [0, K, K]
    assert summary["admissions"] == 4
    assert summary["admitted_at_once"] == 2     # request 0 and request 1


def test_one_default_decode_priority():
    from tensorflow_distributed_tpu.config import ServeConfig

    assert Scheduler(_FakeEngine()).decode_priority == 1
    assert ServeConfig().decode_priority == 1


def test_decode_priority_moves_timing_not_tokens():
    """One workload at K = 1, 2, 4: every request's stream is the same;
    only when it was let in differs."""
    arrivals = [0.0, 0.0, 0.035, _DUE_AFTER_10, _DUE_AFTER_10, 0.2, 0.21]
    max_new = [40, 7, 9, 12, 3, 8, 5]
    streams, burst_gap = {}, {}
    for K in (1, 2, 4):
        done, _, admitted_at = _clocked_run(K, arrivals, max_new)
        streams[K] = {rid: c.tokens for rid, c in done.items()}
        burst_gap[K] = admitted_at[4] - admitted_at[3]
    assert streams[1] == streams[2] == streams[4]
    assert sorted(streams[1]) == list(range(7))
    assert all(len(streams[1][i]) == n for i, n in enumerate(max_new))
    # ... and timing does move: the burst at 100.5 ms is spaced by K
    assert burst_gap == {1: 1, 2: 2, 4: 4}


def test_scheduler_eos_and_budget_1():
    eng = _FakeEngine(num_slots=2)
    reqs = [Request(rid=0, prompt=np.asarray([0], np.int32),
                    max_new_tokens=8, eos_id=2),   # token 2 at step 2
            Request(rid=1, prompt=np.asarray([1], np.int32),
                    max_new_tokens=1),             # budget-1: prefill only
            Request(rid=3, prompt=np.asarray([3], np.int32),
                    max_new_tokens=4, eos_id=300)]  # eos IS first token
    done = {c.rid: c for c in Scheduler(eng, decode_priority=2).run(reqs)}
    assert done[0].finish == "eos" and done[0].tokens[-1] == 2
    assert done[1].finish == "length" and done[1].tokens == [100]
    assert done[3].finish == "eos" and done[3].tokens == [300]


def test_scheduler_streams_tokens():
    eng = _FakeEngine(num_slots=2)
    seen = []
    Scheduler(eng, decode_priority=2,
              on_token=lambda rid, tok, fin: seen.append(
                  (rid, tok, fin))).run(_fake_requests(3, max_new=3))
    for rid in range(3):
        toks = [(t, f) for r, t, f in seen if r == rid]
        assert [t for t, _ in toks] == [rid * 100 + j for j in range(3)]
        assert [f for _, f in toks] == [False, False, True]


def test_scheduler_rejects_oversized_request():
    eng = _FakeEngine(num_slots=2, max_len=16)
    with pytest.raises(ValueError, match="does not fit"):
        Scheduler(eng).run([Request(rid=0,
                                    prompt=np.zeros(10, np.int32),
                                    max_new_tokens=10)])


# --- observe.report serve summary (pure host) --------------------------

def test_report_summarizes_serve_records(tmp_path):
    from tensorflow_distributed_tpu.observe.report import (
        load_records, summarize)

    path = tmp_path / "m.jsonl"
    recs = ([{"event": "serve_request", "rid": i, "ttft_ms": 10.0 + i,
              "tok_ms": 2.0, "queue_steps": 0} for i in range(10)]
            + [{"event": "serve_summary", "tokens_per_sec": 500.0,
                "mean_slot_occupancy": 0.9, "total_new_tokens": 320,
                "prefill_compiles": 3}])
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    out = summarize(load_records(str(path)))
    assert out["serve_requests"] == 10
    assert out["serve_ttft_ms_p50"] == pytest.approx(14.5, abs=1.0)
    assert out["serve_ttft_ms_p95"] == pytest.approx(19.0, abs=1.0)
    assert out["serve_tok_ms_mean"] == pytest.approx(2.0)
    assert out["serve_tokens_per_sec"] == 500.0
    assert out["serve_mean_slot_occupancy"] == 0.9
    assert out["serve_prefill_compiles"] == 3


def test_report_prints_the_share_admitted_at_once(tmp_path):
    from tensorflow_distributed_tpu.observe.report import (
        load_records, render, summarize)

    path = tmp_path / "m.jsonl"
    path.write_text(json.dumps({
        "event": "serve_summary", "wall_s": 1.0, "tokens_per_sec": 9.0,
        "admissions": 8, "admitted_at_once": 6,
        "phase_ms": {"tfd.serve.admit": {
            "count": 8, "sum_ms": 400.0, "max_ms": 60.0, "max_step": 2,
            "max_at_s": 0.5}},
        "iter_ms": {"admit": 400.0, "step": 500.0, "other": 100.0}})
        + "\n")
    text = render(summarize(load_records(str(path)))).splitlines()
    kinds = next(ln for ln in text if "by kind of iteration" in ln)
    assert kinds.endswith("admissions=8 (75.0% at once)")
    assert not any(ln.strip().startswith("admitted_at_once")
                   for ln in text)


# --- the real engine (compiles the tiny GPT — slow tier) ---------------

def _tiny_lm():
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models.transformer import (
        CausalLM, tiny_config)

    model = CausalLM(tiny_config(causal=True,
                                 compute_dtype=jnp.float32))
    prompt = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt)["params"]
    return model, params


@pytest.mark.slow
def test_serve_e2e_token_identical_and_metrics(tmp_path):
    """N mixed-length requests through the engine produce
    token-identical outputs to one-shot greedy generate() per request;
    slots are reused after completion; prefill programs stay within
    the bucket ladder; the metrics JSONL carries TTFT and tokens/s."""
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.models.generate import generate
    from tensorflow_distributed_tpu.observe.registry import (
        JsonlSink, MetricsRegistry)
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

    model, params = _tiny_lm()
    rng = np.random.default_rng(0)
    lens = [3, 9, 17, 30, 5, 12]
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, 64, size=L).astype(np.int32),
                    max_new_tokens=10) for i, L in enumerate(lens)]

    path = tmp_path / "serve.jsonl"
    registry = MetricsRegistry(sinks=[JsonlSink(str(path))])
    engine = SlotDecodeEngine(model, params, num_slots=3)
    sched = Scheduler(engine, decode_priority=3, registry=registry)
    done = {c.rid: c for c in sched.run(reqs)}
    registry.close()

    # Token-identical to the one-shot path, every request.
    for r in reqs:
        ref = np.asarray(generate(model, params,
                                  jnp.asarray(r.prompt[None, :]), 10))[0]
        np.testing.assert_array_equal(
            np.asarray(done[r.rid].tokens), ref,
            err_msg=f"request {r.rid} (prompt len {len(r.prompt)}) "
                    f"diverged from one-shot generate()")

    # Slot reuse: 6 requests through 3 slots.
    assert engine.prefills == 6 and engine.num_slots == 3
    # Bounded prefill programs (the acceptance criterion): distinct
    # compiled prefill executables <= bucket-ladder size.
    assert engine.prefill_compiles <= len(engine.buckets)
    # Starvation bound honored on the real engine too.
    assert max(c.queue_steps for c in done.values()) <= 3

    # Metrics artifact: per-request TTFT + an aggregate tokens/s.
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    req_recs = [r for r in recs if r["event"] == "serve_request"]
    assert len(req_recs) == 6
    assert all(r["ttft_ms"] > 0 and r["tok_ms"] > 0 for r in req_recs)
    summ = [r for r in recs if r["event"] == "serve_summary"]
    assert len(summ) == 1 and summ[0]["tokens_per_sec"] > 0
    assert 0 < summ[0]["mean_slot_occupancy"] <= 1


@pytest.mark.slow
def test_serve_mode_driver(tmp_path):
    """mode=serve end-to-end through config parsing and serve_run:
    synthetic workload, fresh-init params, JSONL artifact."""
    from tensorflow_distributed_tpu.config import parse_args
    from tensorflow_distributed_tpu.serve.run import serve_run

    path = tmp_path / "serve.jsonl"
    cfg = parse_args([
        "--mode", "serve", "--model", "gpt_lm", "--model-size", "tiny",
        "--serve.num-slots", "4", "--serve.num-requests", "6",
        "--serve.prompt-len-min", "4", "--serve.prompt-len-max", "20",
        "--serve.max-new-tokens", "8",
        "--observe.metrics-jsonl", str(path)])
    summary = serve_run(cfg)
    assert summary["requests"] == 6
    assert summary["total_new_tokens"] == 6 * 8
    assert summary["tokens_per_sec"] > 0
    assert summary["prefill_compiles"] <= len(
        summary["buckets"].split(","))
    assert path.exists()
