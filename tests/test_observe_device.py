"""Device-side telemetry: compiled-program registry (observe/device.py)
and on-device model-health metrics (observe/health.py).

Covers the acceptance surface: program records for train AND serve
jits with cost/memory fields present-or-explicitly-null, health
records landing in the JSONL only on cadence steps with zero extra
host transfers off-cadence (transfer-counting shim), the report's
Programs/Health sections, and the malformed-JSONL skip path."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_distributed_tpu.config import (
    MeshConfig, ObserveConfig, TrainConfig)
from tensorflow_distributed_tpu.observe import device, health, report


@pytest.fixture(autouse=True)
def _device_registry_isolation():
    """Each test sees a clean process-level program registry and a
    disarmed instrument gate."""
    device.reset()
    device.set_enabled(False)
    yield
    device.set_enabled(False)
    device.reset()


# --- register_compiled / instrument ------------------------------------

def test_register_compiled_degrades_to_explicit_nulls():
    rec = device.register_compiled("nothing", None, None)
    for key in ("flops", "bytes_accessed", "argument_bytes",
                "output_bytes", "temp_bytes", "generated_code_bytes",
                "donated_bytes", "peak_hbm_bytes", "lower_s",
                "compile_s"):
        assert key in rec and rec[key] is None, key
    assert device.programs()[-1]["program"] == "nothing"


def test_register_compiled_real_program_cost_and_memory():
    @jax.jit
    def f(x):
        return jnp.tanh(x @ x)

    x = jnp.ones((32, 32))
    lowered = f.lower(x)
    compiled = lowered.compile()
    rec = device.register_compiled("matmul", lowered, compiled,
                                   lower_s=0.01, compile_s=0.5)
    assert rec["flops"] and rec["flops"] > 0
    assert rec["bytes_accessed"] and rec["bytes_accessed"] > 0
    assert rec["argument_bytes"] == 32 * 32 * 4
    assert rec["peak_hbm_bytes"] is not None
    assert rec["compile_s"] == 0.5


def test_register_compiled_donated_bytes():
    # A run-unique constant keeps this program out of the persistent
    # compile cache: only a FRESH compile reliably reports alias
    # (donation) bytes — cache-deserialized executables can report 0.
    import os
    salt = float(int.from_bytes(os.urandom(4), "little") % 997 + 1)
    jitted = jax.jit(lambda x: x + salt, donate_argnums=(0,))
    x = jnp.ones((64, 64))
    lowered = jitted.lower(x)
    rec = device.register_compiled("donating", lowered,
                                   lowered.compile())
    # The donated input aliases the output: the savings are real bytes
    # and the peak estimate counts the buffer once.
    assert rec["donated_bytes"] == 64 * 64 * 4
    assert rec["peak_hbm_bytes"] is not None


def test_instrument_registers_once_per_enable_and_delegates():
    calls = []

    @jax.jit
    def f(x):
        return x * 2

    wrapped = device.instrument("double", f)
    # Disarmed: executes, registers nothing.
    assert float(wrapped(jnp.asarray(3.0))) == 6.0
    assert device.programs() == []
    # Armed: first call registers, later calls don't re-register.
    device.set_enabled(True)
    assert float(wrapped(jnp.asarray(4.0))) == 8.0
    assert [r["program"] for r in device.programs()] == ["double"]
    wrapped(jnp.asarray(5.0))
    assert len(device.programs()) == 1
    # A re-enable (new run in the same process, e.g. the lru-cached
    # generate/serve programs) registers again so the new run's JSONL
    # gets its own compile record.
    device.set_enabled(False)
    device.set_enabled(True)
    wrapped(jnp.asarray(6.0))
    assert [r["program"] for r in device.programs()] == ["double"] * 2
    del calls


def test_instrument_never_breaks_the_call_on_bad_registration():
    device.set_enabled(True)
    wrapped = device.instrument("plain_python", lambda x: x + 1)
    assert wrapped(41) == 42  # no .lower -> null record, call intact
    rec = device.programs()[-1]
    assert rec["program"] == "plain_python"
    assert rec["flops"] is None and "error" in rec


def test_budget_table_and_rollup():
    device.register_compiled("big", None, None)
    # Hand-shape a record via a real compiled program for the table.
    jitted = jax.jit(lambda x: x @ x)
    x = jnp.ones((16, 16))
    lo = jitted.lower(x)
    device.register_compiled("small", lo, lo.compile())
    table = device.budget_table()
    assert "big" in table and "small" in table
    budget = device.hbm_budget()
    assert budget["programs"] == 2
    assert budget["peak_hbm_bytes_sum"] > 0


# --- the real train + serve programs -----------------------------------

def _tiny_causal_model():
    from tensorflow_distributed_tpu.models.transformer import (
        CausalLM, tiny_config)
    model = CausalLM(tiny_config(causal=True, max_len=32))
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def test_train_step_program_registered(mesh8):
    import optax

    from tensorflow_distributed_tpu.models.cnn import MnistCNN
    from tensorflow_distributed_tpu.train.state import create_train_state
    from tensorflow_distributed_tpu.train.step import make_train_step

    device.set_enabled(True)
    state = create_train_state(MnistCNN(), optax.adam(1e-3),
                               np.zeros((2, 28, 28, 1), np.float32),
                               mesh8)
    step = make_train_step(mesh8)
    batch = (jnp.zeros((16, 28, 28, 1)), jnp.zeros((16,), jnp.int32))
    state, metrics = step(state, batch)
    jax.block_until_ready(metrics)
    by_name = {r["program"]: r for r in device.programs()}
    assert "train_step" in by_name
    rec = by_name["train_step"]
    # Fields present — real values on this backend, or explicit nulls.
    for key in ("flops", "peak_hbm_bytes", "donated_bytes",
                "compile_s"):
        assert key in rec
    # CPU exposes the analyses; the step donates its state. The
    # donated-bytes VALUE is cache-dependent — an executable
    # deserialized from the warm persistent compile cache reports
    # alias bytes as 0 — so assert the
    # field is populated, not its magnitude (the fresh-compile
    # magnitude is pinned by test_register_compiled_donated_bytes).
    assert rec["flops"] and rec["flops"] > 0
    assert rec["donated_bytes"] is not None and rec["donated_bytes"] >= 0


def test_serve_engine_programs_registered():
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

    device.set_enabled(True)
    model, params = _tiny_causal_model()
    engine = SlotDecodeEngine(model, params, num_slots=2,
                              buckets=(8, 16))
    engine.prefill(np.arange(5, dtype=np.int32) % 7, slot=0)
    engine.step()
    names = {r["program"] for r in device.programs()}
    assert {"serve_prefill_b8", "serve_insert_row",
            "serve_decode_step"} <= names, names
    for rec in device.programs():
        assert "peak_hbm_bytes" in rec and "flops" in rec


# --- health stats (unit) ------------------------------------------------

def test_health_stats_cadence_gating_on_device():
    params = {"layer_0": {"w": jnp.ones((4, 4))},
              "head": {"w": jnp.full((2, 2), 2.0)}}
    grads = {"layer_0": {"w": jnp.full((4, 4), 0.5)},
             "head": {"w": jnp.full((2, 2), 0.25)}}
    updates = {"layer_0": {"w": jnp.full((4, 4), -0.01)},
               "head": {"w": jnp.full((2, 2), -0.02)}}

    @jax.jit
    def at_step(step):
        return health.stats(params, grads, updates, step,
                            health_every=10)

    on = at_step(jnp.asarray(9))    # (9 + 1) % 10 == 0 -> emit
    off = at_step(jnp.asarray(3))
    assert float(on[health.EMIT_KEY]) == 1.0
    assert float(off[health.EMIT_KEY]) == 0.0
    # Emitting step: real vitals.
    assert float(on["health/layer_0/grad_norm"]) == pytest.approx(
        0.5 * 4, rel=1e-5)          # sqrt(16 * 0.25)
    assert float(on["health/layer_0/param_rms"]) == pytest.approx(
        1.0, rel=1e-5)
    assert float(on["health/head/update_ratio"]) == pytest.approx(
        (0.02 * 2) / (2.0 * 2), rel=1e-5)
    # Off-cadence: zeros (the cond's cheap branch), same key set.
    assert set(on) == set(off)
    assert all(float(v) == 0.0 for v in off.values())


def test_health_split_and_group():
    host = {"loss": 1.5, "health_emit": 1.0,
            "health/layer_0/grad_norm": 0.1,
            "health/layer_0/act_rms": 0.9,
            "health/head/update_ratio": 2e-3}
    plain, scalars, emitted = health.split(host)
    assert plain == {"loss": 1.5} and emitted
    groups = dict(health.group(scalars))
    assert groups["layer_0"] == {"grad_norm": 0.1, "act_rms": 0.9}
    assert groups["head"] == {"update_ratio": 2e-3}


# --- e2e: tiny GPT with health + program registry -----------------------

def _health_cfg(tmp_path, *, health, steps=20, log_every=10):
    return TrainConfig(
        model="gpt_lm", model_size="tiny", dataset="synthetic",
        batch_size=16, train_steps=steps, eval_every=0,
        log_every=log_every, eval_batch_size=16,
        compute_dtype="float32", dropout_rate=0.0,
        mesh=MeshConfig(data=8),
        observe=ObserveConfig(
            metrics_jsonl=str(tmp_path / "m.jsonl"),
            health=health, health_taps=health))


def test_health_e2e_records_only_on_cadence(tmp_path):
    from tensorflow_distributed_tpu.train.loop import train

    train(_health_cfg(tmp_path, health=True))
    records = [json.loads(line) for line in open(tmp_path / "m.jsonl")]
    healths = [r for r in records if r["event"] == "health"]
    assert healths, "no health records emitted"
    # Per-layer records land ONLY on cadence steps.
    assert sorted({h["step"] for h in healths}) == [10, 20]
    modules = {h["module"] for h in healths}
    assert {"layer_0", "layer_1", "tok_emb", "lm_head"} <= modules
    by_mod = {h["module"]: h for h in healths if h["step"] == 20}
    for mod in ("layer_0", "tok_emb"):
        assert by_mod[mod]["grad_norm"] > 0
        assert by_mod[mod]["update_ratio"] > 0
        assert by_mod[mod]["param_rms"] > 0
    # Activation taps rode the same records for the blocks.
    assert by_mod["layer_0"]["act_rms"] > 0
    # Health scalars must NOT pollute the step records' columns.
    steps = [r for r in records if r["event"] == "step"]
    assert steps and not any(k.startswith("health/") or k == "health_emit"
                             for k in steps[-1])
    # The program registry rode the same run (observe.programs default).
    compiled = {r["program"] for r in records if r["event"] == "compile"}
    assert "train_step" in compiled and "eval_step" in compiled
    assert any(r["event"] == "hbm_budget" for r in records)


def test_health_off_cadence_adds_zero_device_gets(tmp_path,
                                                  monkeypatch):
    """The acceptance contract: enabling health changes WHAT the
    cadence fetch carries, never HOW OFTEN the host reads the device —
    counted through a jax.device_get shim over two otherwise-identical
    tiny runs."""
    from tensorflow_distributed_tpu.train import loop as loop_mod

    real_get = jax.device_get

    def run(health):
        count = [0]

        def counting_get(*a, **k):
            count[0] += 1
            return real_get(*a, **k)

        monkeypatch.setattr(jax, "device_get", counting_get)
        try:
            loop_mod.train(_health_cfg(
                tmp_path / ("on" if health else "off"), health=health,
                steps=12, log_every=4))
        finally:
            monkeypatch.setattr(jax, "device_get", real_get)
        return count[0]

    (tmp_path / "on").mkdir()
    (tmp_path / "off").mkdir()
    assert run(health=True) == run(health=False)


# --- report sections ----------------------------------------------------

def test_report_programs_and_health_sections():
    records = [
        {"event": "compile", "program": "train_step", "flops": 1e9,
         "peak_hbm_bytes": 3 * 1024 * 1024, "donated_bytes": 1024,
         "compile_s": 1.25},
        {"event": "compile", "program": "no_analysis", "flops": None,
         "peak_hbm_bytes": None, "donated_bytes": None,
         "compile_s": None},
        {"event": "hbm_budget", "programs": 2,
         "peak_hbm_bytes_sum": 3 * 1024 * 1024},
        {"event": "health", "step": 10, "module": "layer_0",
         "grad_norm": 0.5, "update_ratio": 1e-3, "param_rms": 0.1},
        {"event": "health", "step": 20, "module": "layer_0",
         "grad_norm": 0.7, "update_ratio": 5e-3, "param_rms": 0.11},
    ]
    summary = report.summarize(records)
    progs = {p["program"]: p for p in summary["programs"]}
    assert progs["train_step"]["flops"] == 1e9
    assert progs["no_analysis"]["flops"] is None
    assert summary["peak_hbm_bytes_sum"] == 3 * 1024 * 1024
    h = summary["health"]["layer_0"]
    assert h["worst_update_ratio"] == pytest.approx(5e-3)
    assert h["worst_update_ratio_step"] == 20
    assert h["grad_norm_first"] == pytest.approx(0.5)
    assert h["grad_norm_last"] == pytest.approx(0.7)
    text = report.render(summary)
    assert "Programs" in text and "Health" in text
    assert "train_step" in text and "3.0MiB" in text
    assert "layer_0" in text and "worst_update_ratio" in text


def test_load_records_skips_malformed_lines(tmp_path, capsys):
    path = tmp_path / "m.jsonl"
    path.write_text(
        json.dumps({"event": "step", "step": 1}) + "\n"
        + "\n"                                  # blank: fine, skipped
        + '{"event": "step", "ste'              # truncated (crash)
        + "\n"
        + "not json at all\n"
        + json.dumps({"event": "summary"}) + "\n")
    records = report.load_records(str(path))
    assert [r["event"] for r in records] == ["step", "summary"]
    err = capsys.readouterr().err
    assert "skipped 2 malformed line(s)" in err
    assert "first at line 3" in err
    # The CLI still summarizes the survivors.
    assert report.main([str(path)]) == 0
