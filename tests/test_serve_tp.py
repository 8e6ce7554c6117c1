"""Tensor-parallel serve engine: the replica itself sharded.

The identity test runs in the default tier: a model=2 engine (dense,
int8 KV, speculative) serves every stream token-identical to the
model=1 engine on the same weights, at half the cache bytes a device.
The other two are slow tier (they compile 2-device SPMD decode programs
on the virtual 8-CPU topology the conftest forces). The first pins the
cache sharding CONTRACT — the decode cache comes back from step 1 in
the exact head-sharded layout it was created with, and the per-device
byte arithmetic is honest (a width-1 twin reports 2x). The second is
the resilience acceptance at TP: a model=2 serving process SIGKILLed
mid-traffic resumes from its journal and finishes every stream
token-identical to an unfaulted model=2 run.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from tensorflow_distributed_tpu.serve import journal as journal_mod
from tensorflow_distributed_tpu.serve.scheduler import Request, Scheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tp_engine(num_slots=2):
    """A SlotDecodeEngine over a model=2 mesh: gpt_lm-tiny (4 heads,
    divisible) with params placed by its own partition metadata."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.parallel.sharding import param_sharding
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

    mesh = make_mesh(MeshConfig(data=1, model=2), jax.devices()[:2])
    model = gpt_lm(mesh, size="tiny", max_len=64, dropout_rate=0.0,
                   compute_dtype=jnp.float32)
    sample = jnp.zeros((1, 8), jnp.int32)
    abstract = jax.eval_shape(lambda k: model.init(k, sample),
                              jax.random.key(0))
    variables = jax.jit(
        lambda k: nn.meta.unbox(model.init(k, sample)),
        out_shardings=param_sharding(mesh, abstract))(jax.random.key(0))
    return SlotDecodeEngine(model, variables["params"],
                            num_slots=num_slots), model, mesh


def _requests(n=3, max_new=8):
    return [Request(rid=i,
                    prompt=np.random.default_rng(i).integers(
                        0, 64, size=L).astype(np.int32),
                    max_new_tokens=max_new)
            for i, L in enumerate([3, 9, 5][:n])]


@pytest.mark.parametrize("config", ["dense", "int8", "spec"])
def test_tp_engine_token_identical_to_model_1(config):
    """Greedy determinism survives GSPMD's psums: the model=2 engine,
    serving the model=1 engine's own weights placed by the TP model's
    partition metadata, emits the same tokens for every request — with
    a plain cache, an int8 cache, and a speculative verify program
    against model=1's PLAIN run — and holds half the cache bytes a
    device."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from tensorflow_distributed_tpu.config import MeshConfig
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.parallel.sharding import param_sharding
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine
    from tensorflow_distributed_tpu.serve.speculate import SelfDraft

    k = 3 if config == "spec" else 0
    kw = dict(size="tiny", max_len=32, dropout_rate=0.0,
              compute_dtype=jnp.float32,
              kv_cache_quant="int8" if config == "int8" else "none")
    mesh = make_mesh(MeshConfig(data=1, model=2), jax.devices()[:2])
    m1, m2 = gpt_lm(None, **kw), gpt_lm(mesh, **kw)
    sample = jnp.zeros((1, 8), jnp.int32)
    params_1 = nn.meta.unbox(m1.init(jax.random.key(0), sample))["params"]
    abstract = jax.eval_shape(lambda key: m2.init(key, sample),
                              jax.random.key(0))
    params_2 = jax.device_put(
        params_1, param_sharding(mesh, abstract)["params"])

    def served(eng, speculator=None):
        sched = Scheduler(eng, decode_priority=2, speculator=speculator)
        tokens = {c.rid: c.tokens for c in sched.run(_requests())}
        assert bool(sched.summary.get("verify_steps")) == bool(speculator)
        return tokens

    eng_1 = SlotDecodeEngine(m1, params_1, num_slots=2, buckets=(16,))
    eng_2 = SlotDecodeEngine(m2, params_2, num_slots=2, buckets=(16,),
                             spec_tokens=k)
    assert (eng_1.tp_width, eng_2.tp_width) == (1, 2)
    assert eng_1.cache_bytes_per_slot() == 2 * eng_2.cache_bytes_per_slot()
    want = served(eng_1)
    assert served(eng_2, SelfDraft(2, k) if k else None) == want
    assert all(len(t) == 8 for t in want.values())


@pytest.mark.slow
def test_tp_cache_sharding_contract_and_per_device_bytes():
    """The decode cache's head-sharded layout survives real traffic:
    the contract is ARMED automatically at tp_width>1 (step 1 asserts
    inside step()), the final cache still matches the creation-time
    snapshot, a KV leaf is physically split over the model axis, and
    cache_bytes_per_slot reports per-DEVICE bytes (width-1 twin = 2x)."""
    import jax
    import jax.numpy as jnp

    from tensorflow_distributed_tpu.analysis import runtime as graftcheck
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    from tensorflow_distributed_tpu.serve.engine import SlotDecodeEngine

    eng, model, mesh = _tp_engine()
    assert eng.tp_width == 2
    declared = eng._declared_cache
    assert declared is not None, "TP must arm the contract without --check"
    specs = [str(getattr(s, "spec", "")) for s in
             jax.tree_util.tree_leaves(declared) if s is not None]
    assert any("model" in s for s in specs), specs

    done = {c.rid: c for c in
            Scheduler(eng, decode_priority=2).run(_requests())}
    assert len(done) == 3 and eng.decode_steps >= 1
    assert all(len(c.tokens) == 8 for c in done.values())
    # Post-traffic re-assertion (step() checked step 1; this pins that
    # later steps didn't drift either). Raises on violation.
    graftcheck.assert_sharding_contract(eng.cache, declared,
                                        what="decode cache")
    # Physical split: a rank-4 KV leaf holds half its heads per device.
    kv = [lf for lf in jax.tree_util.tree_leaves(eng.cache)
          if getattr(lf, "ndim", 0) == 4]
    assert kv, "no rank-4 KV leaves in the dense cache?"
    leaf = kv[0]
    assert leaf.addressable_shards[0].data.shape[2] * 2 == leaf.shape[2]

    m1 = gpt_lm(None, size="tiny", max_len=64, dropout_rate=0.0,
                compute_dtype=jnp.float32)
    params1 = m1.init(jax.random.key(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]
    eng1 = SlotDecodeEngine(m1, params1, num_slots=2)
    assert eng1.cache_bytes_per_slot() == 2 * eng.cache_bytes_per_slot()


def _child_env():
    # Unlike test_serve_fire's children, TP children NEED the forced
    # multi-device CPU topology, and it must be set before the child's
    # backend initializes.
    return {
        "PATH": os.environ["PATH"],
        "HOME": os.environ.get("HOME", "/tmp"),
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_COMPILATION_CACHE_DIR":
            os.environ.get("JAX_COMPILATION_CACHE_DIR", ""),
        "PYTHONUNBUFFERED": "1",
    }


_TP_SERVE_ARGS = [
    "--mode", "serve", "--model", "gpt_lm", "--model-size", "tiny",
    "--seq-len", "48", "--compute-dtype", "float32",
    "--serve.mesh-model", "2",
    "--serve.num-slots", "2", "--serve.num-requests", "6",
    "--serve.prompt-len-min", "4", "--serve.prompt-len-max", "10",
    "--serve.max-new-tokens", "16",
]


@pytest.mark.slow
def test_tp_supervisor_sigkill_journal_resume_identity(tmp_path):
    """SIGKILL a model=2 serving process mid-traffic; the supervisor
    restarts it, the new leg replays the journal onto a FRESH
    tensor-parallel engine (sharded cache re-prefilled from
    continuations), and every final stream is identical to an
    unfaulted model=2 run — resume composes with TP."""
    clean_j = str(tmp_path / "clean.journal")
    proc = subprocess.run(
        [sys.executable, "-m", "tensorflow_distributed_tpu.cli",
         *_TP_SERVE_ARGS, "--serve.journal", clean_j],
        env=_child_env(), cwd=REPO, capture_output=True, text=True,
        timeout=500)
    assert proc.returncode == 0, \
        proc.stdout[-2000:] + proc.stderr[-2000:]
    clean = journal_mod.replay(clean_j)
    assert len(clean) == 6 and all(e["done"] for e in clean.values())

    journal = str(tmp_path / "tp.journal")
    proc = subprocess.run(
        [sys.executable, "-m",
         "tensorflow_distributed_tpu.resilience.supervisor",
         "--max-restarts", "2", "--backoff-base-s", "0.2", "--",
         *_TP_SERVE_ARGS, "--serve.journal", journal,
         "--resilience.fault-plan", "sigkill@20"],
        env=_child_env(), cwd=REPO, capture_output=True, text=True,
        timeout=500)
    assert proc.returncode == 0, \
        proc.stdout[-3000:] + proc.stderr[-2000:]
    assert '"kind": "restart"' in proc.stdout
    played = journal_mod.replay(journal)
    assert len(played) == 6 and all(e["done"] for e in played.values())
    assert {r: e["tokens"] for r, e in played.items()} == \
        {r: e["tokens"] for r, e in clean.items()}
