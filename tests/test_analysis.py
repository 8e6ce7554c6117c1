"""graftcheck: the self-hosting static-analysis toolchain.

Three layers under test:

- the AST lint engine (analysis/lint.py + rules/): per-rule
  positive/negative fixtures, suppression handling, CLI exit codes,
  and the SELF-HOSTING gate — the whole package must lint clean. The
  engine is pure stdlib by contract (a subprocess test proves it
  imports with jax poisoned away).
- the jaxpr census (analysis/jaxprcheck.py): the audited programs'
  collective/upcast counts vs the committed goldens — the
  failing-on-drift test — plus the drift reporter itself.
- the runtime layer (analysis/runtime.py): the sharding-contract
  assertion catches a drifted layout and accepts equivalent ones; the
  transfer guard blocks implicit transfers; check-mode training runs
  end to end.

The lint fixtures are jax-free; census/runtime tests import jax inside
the test body (tracing only — no SPMD compiles, so they stay in the
default tier).
"""

from __future__ import annotations

import subprocess
import sys
import textwrap

import pytest

from tensorflow_distributed_tpu.analysis.lint import (
    lint_paths, lint_source, main as lint_main, PACKAGE_ROOT)


def findings(src: str, path: str = "mod.py"):
    return lint_source(textwrap.dedent(src), path)


def rules_of(src: str, path: str = "mod.py"):
    return [f.rule for f in findings(src, path)]


# --- host-sync-under-trace ---------------------------------------------

def test_host_sync_under_trace_positive():
    src = """
    import jax

    @jax.jit
    def f(x):
        return float(x) + x.item()
    """
    assert rules_of(src) == ["host-sync-under-trace"] * 2


def test_host_sync_under_trace_via_jit_reference():
    # Not decorated — passed to jax.jit by name, like every step
    # builder in train/.
    src = """
    import jax

    def make(mesh):
        def step(state, batch):
            return jax.device_get(state)
        return jax.jit(step, donate_argnums=(0,))
    """
    assert rules_of(src) == ["host-sync-under-trace"]


def test_host_sync_under_trace_transitive_callee():
    # step is traced; helper is called from step's body — traced too.
    src = """
    import jax
    import numpy as np

    def make():
        def helper(x):
            return np.asarray(x)

        def step(x):
            return helper(x) + 1
        return jax.jit(step)
    """
    assert rules_of(src) == ["host-sync-under-trace"]


def test_host_sync_negative_outside_trace():
    src = """
    import jax

    def report(metrics):
        return float(jax.device_get(metrics)["loss"])
    """
    assert rules_of(src) == []


# --- host-sync-in-loop -------------------------------------------------

def test_host_sync_in_loop_positive_hot_module():
    src = """
    import jax

    def train(step_fn, state, batches):
        for b in batches:
            state, m = step_fn(state, b)
            loss = jax.device_get(m)
        return state
    """
    assert rules_of(src, "pkg/train/loop.py") == ["host-sync-in-loop"]


def test_host_sync_in_loop_transitive_helper():
    # No loop inside _inspect — it is called from one (the actual
    # shape of train/loop.py's per-step policy helper).
    src = """
    import jax

    def train(step_fn, state, batches):
        def _inspect(m):
            return float(jax.device_get(m)) > 0

        for b in batches:
            state, m = step_fn(state, b)
            _inspect(m)
        return state
    """
    assert rules_of(src, "pkg/train/loop.py") == ["host-sync-in-loop"]


def test_host_sync_methods_in_hot_module():
    # Methods can't be followed through self.engine.step() attribute
    # calls, so in a hot module EVERY method is assumed hot (the serve
    # engine's per-decode-step device reads are the real case).
    src = """
    import jax
    import numpy as np

    class Engine:
        def step(self):
            return np.asarray(jax.device_get(self.tok))
    """
    assert rules_of(src, "pkg/serve/engine.py") == [
        "host-sync-in-loop"] * 2
    assert rules_of(src, "pkg/models/thing.py") == []


def test_host_sync_in_loop_cold_module_not_flagged():
    src = """
    import jax

    def summarize(records):
        for r in records:
            yield jax.device_get(r)
    """
    assert rules_of(src, "pkg/observe/report.py") == []


# --- prng-reuse --------------------------------------------------------

def test_prng_reuse_positive():
    src = """
    import jax

    def sample(seed):
        k = jax.random.key(seed)
        a = jax.random.normal(k, (3,))
        b = jax.random.uniform(k, (3,))
        return a + b
    """
    assert rules_of(src) == ["prng-reuse"]


def test_prng_reuse_rngs_keyword():
    src = """
    import jax

    def init_and_apply(model, x, seed):
        k = jax.random.key(seed)
        params = model.init(x, rngs={"dropout": k})
        out = model.apply(params, x, rngs={"dropout": k})
        return out
    """
    assert rules_of(src) == ["prng-reuse"]


def test_prng_reuse_in_loop():
    # The canonical bug: one key drawn from on every iteration.
    bad = """
    import jax

    def sample(seed, n):
        k = jax.random.key(seed)
        out = []
        for i in range(n):
            out.append(jax.random.normal(k, (3,)))
        return out
    """
    good = """
    import jax

    def sample(seed, n):
        k = jax.random.key(seed)
        out = []
        for i in range(n):
            k, sub = jax.random.split(k)
            out.append(jax.random.normal(sub, (3,)))
        return out
    """
    assert rules_of(bad) == ["prng-reuse"]
    assert rules_of(good) == []


def test_prng_split_and_fold_in_negative():
    src = """
    import jax

    def sample(seed):
        k = jax.random.key(seed)
        k, sub = jax.random.split(k)
        a = jax.random.normal(sub, (3,))
        k = jax.random.fold_in(k, 1)
        b = jax.random.uniform(k, (3,))
        return a + b
    """
    assert rules_of(src) == []


# --- jit-in-loop -------------------------------------------------------

def test_jit_in_loop_positive_and_hoisted_negative():
    bad = """
    import jax

    def run(xs):
        out = []
        for x in xs:
            out.append(jax.jit(lambda y: y + 1)(x))
        return out
    """
    good = """
    import jax

    def run(xs):
        f = jax.jit(lambda y: y + 1)
        return [f(x) for x in xs]
    """
    assert rules_of(bad) == ["jit-in-loop"]
    assert rules_of(good) == []


# --- use-after-donation ------------------------------------------------

def test_use_after_donation_positive():
    src = """
    import jax

    def run(f, state, batch):
        step = jax.jit(f, donate_argnums=(0,))
        new_state, m = step(state, batch)
        return new_state, state.params
    """
    assert rules_of(src) == ["use-after-donation"]


def test_use_after_donation_factory_registry():
    src = """
    from tensorflow_distributed_tpu.train.step import make_train_step

    def run(mesh, state, batch):
        step = make_train_step(mesh)
        new_state, m = step(state, batch)
        print(state)
        return new_state
    """
    assert rules_of(src) == ["use-after-donation"]


def test_use_after_donation_loop_without_rebind():
    src = """
    from tensorflow_distributed_tpu.train.step import make_train_step

    def bench(mesh, state, batches):
        step = make_train_step(mesh)
        for b in batches:
            _, m = step(state, b)
        return m
    """
    assert rules_of(src) == ["use-after-donation"]


def test_use_after_donation_safe_rebind_negative():
    # The repo idiom: same-statement rebind, including in a loop.
    src = """
    from tensorflow_distributed_tpu.train.step import make_train_step

    def run(mesh, state, batches):
        step = make_train_step(mesh)
        for b in batches:
            state, m = step(state, b)
        return state, m
    """
    assert rules_of(src) == []


def test_use_after_donation_is_scope_and_flow_sensitive():
    # A sibling scope's `step = make_train_step(...)` must not
    # contaminate a scope where `step` is something else — and a name
    # rebound to a non-donor later in the SAME scope stops donating.
    siblings = """
    from tensorflow_distributed_tpu.train.step import make_train_step

    def build(mesh):
        step = make_train_step(mesh)
        return step

    def unrelated(step_impl, state, batch):
        step = step_impl
        out = step(state, batch)
        return state
    """
    rebound = """
    from tensorflow_distributed_tpu.train.step import make_train_step

    def run(mesh, undonated, state, batch):
        step = make_train_step(mesh)
        new_state, m = step(state, batch)
        step = undonated
        out = step(new_state, batch)
        return new_state
    """
    inherited = """
    import jax

    step = jax.jit(lambda s, b: s, donate_argnums=(0,))

    def run(state, batch):
        new_state = step(state, batch)
        return state
    """
    assert rules_of(siblings) == []
    assert rules_of(rebound) == []
    # Module-level donor bindings ARE visible inside functions.
    assert rules_of(inherited) == ["use-after-donation"]


def test_use_after_donation_suppressed_read_keeps_tracking():
    # A suppressed read must not consume the one-finding-per-donation
    # budget — the NEXT unsuppressed read still reports.
    src = """
    from tensorflow_distributed_tpu.train.step import make_train_step

    def run(mesh, state, batch):
        step = make_train_step(mesh)
        new_state, m = step(state, batch)
        x = state.meta  # graftcheck: disable=use-after-donation -- host field
        return new_state, state.params
    """
    assert rules_of(src) == ["use-after-donation"]


def test_hot_module_suffix_is_separator_anchored():
    src = """
    import jax

    def run(batches):
        for b in batches:
            out = jax.device_get(b)
        return out
    """
    # observe/run.py must NOT match the serve/run.py hot suffix.
    assert rules_of(src, "pkg/observe/run.py") == []
    assert rules_of(src, "pkg/serve/run.py") == ["host-sync-in-loop"]


def test_use_after_donation_undonated_factory_negative():
    src = """
    from tensorflow_distributed_tpu.train.step import make_train_step

    def run(mesh, state, batch):
        step = make_train_step(mesh, donate=False)
        new_state, m = step(state, batch)
        return new_state, state.params
    """
    assert rules_of(src) == []


def test_donation_audit_repo_call_sites_clean():
    """The executable audit of the satellite task: the four donating
    step builders' real call sites (the train loop and the builders'
    own modules) contain no use-after-donation finding — every site
    uses the safe same-statement rebind."""
    import os
    audited = [
        "train/loop.py", "train/step.py", "train/multistep.py",
        "train/local_sgd.py", "train/pipeline_step.py",
    ]
    paths = [os.path.join(PACKAGE_ROOT, p) for p in audited]
    assert [f for f in lint_paths(paths)
            if f.rule == "use-after-donation"] == []


# --- effect-under-trace ------------------------------------------------

def test_effect_under_trace_positive():
    src = """
    import jax
    import time

    @jax.jit
    def f(x):
        print("tracing")
        t = time.time()
        return x + t
    """
    assert rules_of(src) == ["effect-under-trace"] * 2


def test_effect_in_scan_body():
    src = """
    import jax

    def run(xs):
        def body(c, x):
            print(x)
            return c, x
        return jax.lax.scan(body, 0, xs)
    """
    assert rules_of(src) == ["effect-under-trace"]


def test_effect_outside_trace_negative():
    src = """
    def report(x):
        print(x)
    """
    assert rules_of(src) == []


# --- suppressions ------------------------------------------------------

def test_suppression_same_line():
    src = """
    import jax

    @jax.jit
    def f(x):
        return x.item()  # graftcheck: disable=host-sync-under-trace -- fixture
    """
    assert rules_of(src) == []


def test_suppression_comment_block_above():
    src = """
    import jax

    @jax.jit
    def f(x):
        # this value is static by construction (documented why)
        # graftcheck: disable=host-sync-under-trace -- static config read
        return x.item()
    """
    assert rules_of(src) == []


def test_suppression_multiline_statement():
    src = """
    import jax

    def train(step_fn, state, batches):
        for b in batches:
            # graftcheck: disable=host-sync-in-loop -- fixture
            loss = float(jax.device_get(
                b["loss"]))
        return state
    """
    assert rules_of(src, "pkg/train/loop.py") == []


def test_suppression_wrong_rule_does_not_silence():
    src = """
    import jax

    @jax.jit
    def f(x):
        return x.item()  # graftcheck: disable=prng-reuse -- wrong rule
    """
    assert rules_of(src) == ["host-sync-under-trace"]


def test_suppression_on_code_line_above_does_not_leak():
    # A trailing suppression on the PREVIOUS code line belongs to that
    # line, not to the statement below it.
    src = """
    import jax

    @jax.jit
    def f(x, y):
        a = y.item()  # graftcheck: disable=host-sync-under-trace -- this line
        return x.item() + a
    """
    assert rules_of(src) == ["host-sync-under-trace"]


def test_suppression_multiple_rules():
    src = """
    import jax

    def train(step_fn, state, batches):
        for b in batches:
            # graftcheck: disable=host-sync-in-loop,jit-in-loop -- fixture
            loss = jax.device_get(jax.jit(lambda y: y)(b))
        return state
    """
    assert rules_of(src, "pkg/train/loop.py") == []
    # A suppression covers ONLY the statement below its comment block —
    # the next statement still reports.
    src_two = """
    import jax

    def train(step_fn, state, batches):
        for b in batches:
            # graftcheck: disable=jit-in-loop -- fixture
            f = jax.jit(lambda y: y)
            loss = jax.device_get(b)
        return state
    """
    assert rules_of(src_two, "pkg/train/loop.py") == ["host-sync-in-loop"]


# --- driver / CLI ------------------------------------------------------

def test_lint_cli_exit_codes(tmp_path):
    dirty = tmp_path / "dirty.py"
    dirty.write_text(
        "import jax\n\n@jax.jit\ndef f(x):\n    return x.item()\n")
    clean = tmp_path / "clean.py"
    clean.write_text("def f(x):\n    return x + 1\n")
    assert lint_main([str(dirty)]) == 1
    assert lint_main([str(clean)]) == 0
    assert lint_main([str(tmp_path)]) == 1   # directory recursion
    assert lint_main(["--list-rules"]) == 0


def test_lint_engine_is_jax_free():
    """The lint tier's contract: importing and running the linter must
    not touch jax (proven by poisoning the import in a subprocess)."""
    code = textwrap.dedent("""
        import builtins
        real = builtins.__import__
        def guard(name, *a, **k):
            if name == "jax" or name.startswith("jax."):
                # name= matters: the package root re-raises any
                # ModuleNotFoundError that is not jax/jaxlib itself.
                raise ModuleNotFoundError(
                    f"No module named {name!r}", name="jax")
            return real(name, *a, **k)
        builtins.__import__ = guard
        from tensorflow_distributed_tpu.analysis.lint import lint_source
        fs = lint_source("import jax\\n\\n@jax.jit\\ndef f(x):\\n"
                         "    return x.item()\\n", "m.py")
        assert [f.rule for f in fs] == ["host-sync-under-trace"], fs
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_repo_lints_clean():
    """SELF-HOSTING: the whole package must have zero unsuppressed
    findings — graftcheck gates the code that ships it."""
    assert [f.render() for f in lint_paths([PACKAGE_ROOT])] == []


# --- jaxpr census vs goldens -------------------------------------------

def test_census_matches_golden():
    """The failing-on-drift gate: the audited programs' collective and
    upcast counts equal the committed budgets. A red here means a PR
    changed the program contract — fix it, or regenerate the golden
    with `python -m tensorflow_distributed_tpu.analysis.jaxprcheck
    --update` and justify the diff."""
    from tensorflow_distributed_tpu.analysis import jaxprcheck

    current = jaxprcheck.census()
    drift = jaxprcheck.diff_censuses(jaxprcheck.load_golden(), current)
    assert drift == [], "\n".join(drift)


def test_census_structure_sane():
    """Ground truths the census must reflect regardless of exact
    counts: the pipelined schedule moves activations with ppermute;
    the single-device LM/decode programs have no collectives; every
    bf16 program upcasts somewhere (loss/norm math)."""
    from tensorflow_distributed_tpu.analysis import jaxprcheck

    golden = jaxprcheck.load_golden()
    assert set(golden) == {"gpt_train", "moe_train", "pipelined_train",
                           "serve_decode", "gpt_train_health",
                           "moe_train_health",
                           "pipelined_train_health",
                           "gpt_train_overlap", "moe_train_overlap",
                           "serve_verify", "serve_decode_int8",
                           "serve_decode_paged", "serve_verify_paged",
                           "serve_prefill_paged", "serve_decode_tp",
                           "serve_verify_tp"}
    assert golden["pipelined_train"]["collectives"].get("ppermute", 0) > 0
    assert golden["gpt_train"]["collectives"] == {}
    assert golden["serve_decode"]["collectives"] == {}
    # Fast-path serving invariants: the speculative verify and the
    # int8 decode stay collective-free (per-token cost work is local),
    # and int8's quantize-on-write/scale-adjusted-attend adds only a
    # BOUNDED number of converts next to the plain decode program.
    assert golden["serve_verify"]["collectives"] == {}
    assert golden["serve_decode_int8"]["collectives"] == {}
    plain_up = golden["serve_decode"]["upcasts"].get(
        "bfloat16->float32", 0)
    int8_up = golden["serve_decode_int8"]["upcasts"].get(
        "bfloat16->float32", 0)
    # <= 8 extra converts per layer (tiny = 2): the q8 absmax/scale
    # math + the two scale-adjusted dots — NOT a chain-wide f32 drift.
    assert plain_up < int8_up <= plain_up + 16
    # Paged-KV serving invariants (serve/paging): page-table
    # addressing is local gather/scatter — zero collectives in all
    # three paged executables, and the paged decode's upcast count
    # EQUALS the dense decode's (same attend math over the same
    # logical layout; paging relocates bytes, it does not widen them).
    for name in ("serve_decode_paged", "serve_verify_paged",
                 "serve_prefill_paged"):
        assert golden[name]["collectives"] == {}, name
    assert (golden["serve_decode_paged"]["upcasts"]
            == golden["serve_decode"]["upcasts"])
    # Tensor-parallel serving invariants: the model=2 decode/verify
    # programs MUST carry collectives (head-sharded attention + MLP
    # reassemble activations every step — TP that compiles to zero
    # collectives silently replicated somewhere), while the upcast
    # counts equal the dense program's (sharding relocates math, it
    # does not widen it). These census entries are HLO-derived
    # (GSPMD emits the collectives after partitioning), hence the
    # hyphenated names.
    for name in ("serve_decode_tp", "serve_verify_tp"):
        tp_coll = golden[name]["collectives"]
        assert sum(tp_coll.values()) > 0, name
        assert (golden[name]["upcasts"]
                == golden["serve_decode"]["upcasts"]), name
    # The overlap grad-sync invariant: an explicit reduce-scatter AND
    # an explicit all-gather per scatter bucket (counts equal — a
    # bucket that scatters but never gathers back would train on
    # stale params), plus >= 1 psum (replicated small leaves + the
    # metric pmean).
    for name in ("gpt_train_overlap", "moe_train_overlap"):
        c = golden[name]["collectives"]
        assert c.get("reduce_scatter", 0) > 1, name
        assert c["reduce_scatter"] == c["all_gather"], name
        assert c.get("psum", 0) >= 1, name
    for prog in golden.values():
        assert prog["upcasts"].get("bfloat16->float32", 0) > 0
    # The device-telemetry invariant the health entries exist to pin:
    # enabling per-layer vitals adds NO collectives to any schedule
    # (the stats are local reductions riding the existing metrics).
    for name in ("gpt_train", "moe_train", "pipelined_train"):
        assert (golden[f"{name}_health"]["collectives"]
                == golden[name]["collectives"]), name


def test_census_drift_reporting():
    from tensorflow_distributed_tpu.analysis.jaxprcheck import (
        diff_censuses)

    golden = {"p": {"collectives": {"psum": 2}, "upcasts": {}}}
    current = {"p": {"collectives": {"psum": 2, "all_gather": 1},
                     "upcasts": {"bfloat16->float32": 3}}}
    drift = diff_censuses(golden, current)
    assert any("all_gather] 0 -> 1" in d for d in drift)
    assert any("bfloat16->float32] 0 -> 3" in d for d in drift)
    assert diff_censuses(golden, {"p": golden["p"]}) == []
    # A FULL run missing a golden program is drift (a deleted PROGRAMS
    # entry must not silently disarm its budget)...
    assert any("missing from the run" in d
               for d in diff_censuses(golden, {}))
    # ...but an explicit partial run compares only what it traced.
    assert diff_censuses(golden, {}, required=[]) == []
    assert diff_censuses({"p": golden["p"], "q": golden["p"]},
                         {"p": golden["p"]}, required=["p"]) == []


# --- runtime layer (--check) -------------------------------------------

def test_sharding_contract_assertion(mesh8):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tensorflow_distributed_tpu.analysis.runtime import (
        ShardingContractError, assert_sharding_contract, sharding_tree)

    x = jax.device_put(np.ones((8, 4), np.float32),
                       NamedSharding(mesh8, P("data")))
    declared = sharding_tree({"w": x})
    # Equivalent spec spelled differently still satisfies the contract.
    x_eq = jax.device_put(np.ones((8, 4), np.float32),
                          NamedSharding(mesh8, P("data", None)))
    assert_sharding_contract({"w": x_eq}, declared)
    # A genuinely different layout does not.
    x_drifted = jax.device_put(np.ones((8, 4), np.float32),
                               NamedSharding(mesh8, P()))
    with pytest.raises(ShardingContractError, match=r"\['w'\]"):
        assert_sharding_contract({"w": x_drifted}, declared)


def test_transfer_guard_blocks_implicit():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflow_distributed_tpu.analysis.runtime import (
        transfer_guard)

    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(4))  # warm: compile outside the guard
    with transfer_guard(True):
        f(jax.device_put(np.ones(4, np.float32)))  # explicit: allowed
    with pytest.raises(Exception, match="[Dd]isallowed.*transfer"):
        with transfer_guard(True):
            f(np.ones(4, np.float32))              # implicit: caught
    with transfer_guard(False):                    # off: transparent
        f(np.ones(4, np.float32))


def test_check_mode_rewind_recovers(mesh8, tmp_path):
    """--check must not strangle recovery: a policy-ordered rewind
    restores a checkpoint (implicit warm-up transfers by design) from
    INSIDE the guarded steady-state loop — the cold path is exempted
    via runtime.transfer_allowed, so the run recovers instead of dying
    on 'Disallowed host-to-device transfer'."""
    import jax

    from tensorflow_distributed_tpu.config import (
        MeshConfig, ResilienceConfig, TrainConfig)
    from tensorflow_distributed_tpu.train.loop import train

    cfg = TrainConfig(dataset="synthetic", batch_size=64,
                      train_steps=16, eval_every=0, log_every=0,
                      eval_batch_size=64, compute_dtype="float32",
                      mesh=MeshConfig(data=8), check=True,
                      checkpoint_dir=str(tmp_path / "ckpt"),
                      checkpoint_every=4,
                      resilience=ResilienceConfig(
                          nonfinite="rewind", max_rewinds=1,
                          fault_plan="nan_grad@8"))
    result = train(cfg)
    assert int(jax.device_get(result.state.step)) == 16


def test_check_mode_train_e2e(mesh8):
    """--check end to end: a short training run under the transfer
    guard + sharding contract completes (the loop's transfers are all
    explicit, and the step hands the params back in their declared
    layout)."""
    import jax

    from tensorflow_distributed_tpu.config import MeshConfig, TrainConfig
    from tensorflow_distributed_tpu.train.loop import train

    cfg = TrainConfig(dataset="synthetic", batch_size=64, train_steps=4,
                      eval_every=0, log_every=0, eval_batch_size=64,
                      compute_dtype="float32",
                      mesh=MeshConfig(data=8), check=True)
    result = train(cfg)
    assert int(jax.device_get(result.state.step)) == 4
