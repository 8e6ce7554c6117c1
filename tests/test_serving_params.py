"""The serving tree (``serve/params.py``): what an engine holds of a
trained parameter tree.

A model family says which leaves its programs only ever read through a
cast to the compute dtype (``TransformerLM.serving_params``); the serve
path holds those leaves IN that dtype, made once. The work stays the
same: the same operands, in the same dtypes, enter the same operations,
so on the CPU the serving tree's logits are the trained tree's BIT FOR
BIT. The second half guards that the declaration matches the programs:
a float32 use of a declared leaf added later fails here instead of
silently rounding twice.
"""

import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

from tensorflow_distributed_tpu.models.generate import (
    decode_token, prefill_cache)
from tensorflow_distributed_tpu.models.transformer import (
    HEAD_TABLE, gpt_lm, moe_lm)
from tensorflow_distributed_tpu.serve.params import (
    made_leaves, serving_tree)

VOCAB = 64

# The GPT family's variants that change which leaves exist.
VARIANTS = {
    "tied": dict(tie_embeddings=True),
    "untied": dict(),
    "tied_moe": dict(tie_embeddings=True, moe=True),
    "untied_moe": dict(moe=True),
    "untied_swiglu_gqa_rope": dict(mlp_variant="swiglu", n_kv_heads=2,
                                   pos_emb="rope", norm="rmsnorm"),
}


def _gpt(compute_dtype=jnp.bfloat16, moe=False, **over):
    build = moe_lm if moe else gpt_lm
    model = build(None, size="tiny", dropout_rate=0.0, max_len=64,
                  compute_dtype=compute_dtype, **over)
    params = nn.meta.unbox(model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    return model, params


def _keyed(tree):
    return {jax.tree_util.keystr(p): x for p, x
            in jax.tree_util.tree_leaves_with_path(tree)}


def _trajectory(model, params, steps=8):
    """Prefill logits and ``steps`` decode steps' logits, two rows at
    different depths (what a slot engine's programs compute)."""
    prompt = jax.random.randint(jax.random.key(1), (2, 12), 0, VOCAB)
    logits, cache = jax.jit(
        lambda p, t: prefill_cache(model, p, t))(params, prompt)
    step = jax.jit(lambda p, c, t, q: decode_token(model, p, c, t, q))
    out = [logits]
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    pos = jnp.asarray([12, 12], jnp.int32)
    for _ in range(steps):
        last, cache = step(params, cache, tok, pos)
        out.append(last)
        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        pos = pos + 1
    return [np.asarray(x) for x in out]


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_serving_tree_logits_are_the_trained_trees_bit_for_bit(variant):
    model, params = _gpt(**VARIANTS[variant])
    tree, held = serving_tree(model, params)
    assert held["leaves_cast"] > 0
    assert held["bytes_held"] < held["bytes_trained"]
    for a, b in zip(_trajectory(model, params), _trajectory(model, tree)):
        assert a.dtype == np.float32 and np.isfinite(a).all()
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_what_is_held_in_which_dtype(variant):
    """Every ``Dense`` kernel and bias (attention, MLP, swiglu's
    ``gate``, the untied head) and the experts' matrices in bfloat16;
    norms, ``pos_emb``, the router's ``gate`` and the LOOKUP table in
    float32; a tied head's copy of the table as one more leaf."""
    over = VARIANTS[variant]
    model, params = _gpt(**over)
    tree, held = serving_tree(model, params)
    got, was = _keyed(tree), _keyed(params)
    bf16 = {k for k, x in got.items() if x.dtype == jnp.bfloat16}
    assert all(x.dtype == jnp.float32 for x in was.values())
    assert {k for k in got if k not in bf16} == {
        k for k in was
        if any(f"['{name}']" in k for name in
               ("ln1", "ln2", "ln_f", "pos_emb", "tok_emb"))
        or k.endswith("['moe_mlp']['gate']")}
    dense = [k for k in was if k.endswith("['kernel']")]
    assert dense and set(dense) | {
        k[:-len("['kernel']")] + "['bias']" for k in dense} <= bf16
    if over.get("mlp_variant") == "swiglu":
        assert "['layer_0']['mlp']['gate']['kernel']" in bf16
    if over.get("moe"):
        assert got["['layer_0']['moe_mlp']['gate']"].dtype == jnp.float32
        assert {"['layer_1']['moe_mlp']['wi']",
                "['layer_1']['moe_mlp']['wo']"} <= bf16
    extra = set(got) - set(was)
    if over.get("tie_embeddings"):
        assert extra == {f"['{HEAD_TABLE}']"}
        table = was["['tok_emb']['embedding']"]
        np.testing.assert_array_equal(
            np.asarray(got[f"['{HEAD_TABLE}']"]),
            np.asarray(table.astype(jnp.bfloat16)))
        # the lookup's table is the trained buffer itself
        assert got["['tok_emb']['embedding']"] is table
    else:
        assert not extra
        assert "['lm_head']['kernel']" in bf16
    assert held["leaves_cast"] == len(bf16)
    assert held["bytes_trained"] == sum(x.nbytes for x in was.values())
    assert held["bytes_held"] == sum(x.nbytes for x in got.values())
    # what is not cast is not copied
    assert all(got[k] is was[k] for k in was if k not in bf16)


# glm_moe_dsa at toy widths, under the source's key names: a family whose
# parameters are bfloat16 as built, and which declares nothing.
GLM_TINY = dict(
    vocab_size=96, hidden_size=32, num_attention_heads=4, q_lora_rank=16,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    index_n_heads=2, index_head_dim=16, index_topk=8,
    intermediate_size=64, moe_intermediate_size=16, n_routed_experts=4,
    n_routed_experts_published=16, experts_held=[1, 5, 6, 12],
    n_shared_experts=1, num_experts_per_tok=4, routed_scaling_factor=2.5,
    norm_topk_prob=True, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 10000.0},
    max_position_embeddings=64, num_hidden_layers=2,
    first_k_dense_replace=1, mlp_layer_types=["dense", "sparse"],
    indexer_types=["full", "shared"])


def _glm():
    from tensorflow_distributed_tpu.models import glm_moe_dsa as G
    model = G.GlmMoeDsaLM(G.config_from_source(dict(GLM_TINY)))
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.mark.parametrize("family", ["gpt_float32", "gpt_float32_tied",
                                    "glm_bfloat16"])
def test_nothing_to_cast_gives_the_very_same_arrays(family):
    if family == "glm_bfloat16":
        model, params = _glm()
    else:
        model, params = _gpt(compute_dtype=jnp.float32,
                             tie_embeddings=family.endswith("tied"))
    tree, held = serving_tree(model, params, donate=True)
    assert tree is params
    assert held["leaves_cast"] == 0
    assert held["bytes_held"] == held["bytes_trained"] > 0
    assert not any(x.is_deleted()
                   for x in jax.tree_util.tree_leaves(params))


def test_donate_gives_back_the_cast_leaves_and_only_those():
    model, params = _gpt(tie_embeddings=True)
    tree, _ = serving_tree(model, params, donate=True)
    cast = {k for k, x in _keyed(tree).items() if x.dtype == jnp.bfloat16}
    for key, leaf in _keyed(params).items():
        assert leaf.is_deleted() == (key in cast), key
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(tree))


def test_cast_leaves_keep_the_trained_leafs_sharding():
    """A tensor-parallel replica's tree: each cast leaf lands where the
    trained leaf was, so the engine's programs see the layout they were
    built for."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    model, params = _gpt(tie_embeddings=True)

    def spec(path, leaf):
        key = jax.tree_util.keystr(path)
        if key.endswith("['up']['kernel']"):
            return P(None, "model")
        if key.endswith("['down']['kernel']"):
            return P("model", None)
        return P()

    params = jax.tree_util.tree_map_with_path(
        lambda p, x: jax.device_put(x, NamedSharding(mesh, spec(p, x))),
        params)
    tree, _ = serving_tree(model, params)
    got, was = _keyed(tree), _keyed(params)
    for key, leaf in was.items():
        assert got[key].sharding.is_equivalent_to(leaf.sharding,
                                                  leaf.ndim), key
    up = got["['layer_0']['mlp']['up']['kernel']"]
    assert up.dtype == jnp.bfloat16 and up.sharding.spec == P(None, "model")
    assert got[f"['{HEAD_TABLE}']"].sharding.is_fully_replicated


# -- the declaration matches the programs --------------------------------

def _sub_jaxprs(eqn):
    """The jaxprs an equation calls with ITS operands, in order (pjit,
    remat, custom_jvp/vjp, closed_call): operand i is invar i."""
    for name in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(name)
        if sub is not None:
            sub = getattr(sub, "jaxpr", sub)
            if len(sub.invars) == len(eqn.invars):
                return [sub]
    return []


def _uses(jaxpr, tracked, out):
    """``out[i]``: every use of program input ``i``, as
    ``(primitive, dtype it converts to or None)``, seen through calls."""
    for eqn in jaxpr.eqns:
        hits = [(n, v) for n, v in enumerate(eqn.invars)
                if not isinstance(v, Literal) and v in tracked]
        if not hits:
            continue
        subs = _sub_jaxprs(eqn)
        if subs:
            for sub in subs:
                _uses(sub, {sub.invars[n]: tracked[v] for n, v in hits},
                      out)
            continue
        for _, v in hits:
            out.setdefault(tracked[v], []).append(
                (eqn.primitive.name, eqn.params.get("new_dtype")))
    return out


def _param_uses(fn, params, *args):
    """{path of a parameter leaf: its uses in ``fn(params, *args)``}."""
    closed = jax.make_jaxpr(fn)(params, *args)
    keys = list(_keyed(params))
    tracked = {v: keys[i] for i, v in enumerate(closed.jaxpr.invars[:len(keys)])}
    return _uses(closed.jaxpr, tracked, {})


def _programs(model):
    """The engine's two programs as functions of the parameters: one
    prefill bucket and the decode step."""
    from tensorflow_distributed_tpu.serve import engine
    prefill = engine._compiled_prefill.__wrapped__(model, 16)
    step = engine._compiled_step.__wrapped__(model)
    return prefill, step


def _program_args(model, params, slots=2):
    cache = jax.eval_shape(
        lambda p: model.apply(
            {"params": p}, jnp.zeros((slots, 1), jnp.int32), decode=True,
            positions=jnp.zeros((slots, 1), jnp.int32),
            mutable=["cache"])[1]["cache"], params)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache)
    prefill_args = (jnp.zeros((1, 16), jnp.int32), jnp.int32(5))
    step_args = (cache, jnp.zeros((slots,), jnp.int32),
                 jnp.zeros((3, slots), jnp.int32))
    return prefill_args, step_args


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_no_program_rounds_a_serving_leaf_and_every_declared_leaf_was_only_rounded(
        variant):
    model, params = _gpt(**VARIANTS[variant])
    tree, _ = serving_tree(model, params)
    _, made = made_leaves(model, params)
    declared = {k for k in made if k in _keyed(params)}
    assert declared
    prefill, step = _programs(model)
    f32_to_bf16 = ("convert_element_type", jnp.dtype(jnp.bfloat16))
    for fn, which in zip((prefill, step), (0, 1)):
        # On the serving tree no parameter is converted float32 ->
        # bfloat16 any more ...
        uses = _param_uses(fn, tree, *_program_args(model, tree)[which])
        for key, leaf in _keyed(tree).items():
            if leaf.dtype == jnp.float32:
                assert f32_to_bf16 not in uses.get(key, []), (key, uses[key])
        # ... and on the trained tree a declared leaf has no other use
        # than that conversion: nothing reads it in float32.
        uses = _param_uses(fn, params, *_program_args(model, params)[which])
        for key in declared:
            assert set(uses[key]) == {f32_to_bf16}, (key, uses[key])
        if VARIANTS[variant].get("tie_embeddings"):
            # The table's two readers: the lookup, and the head's cast
            # (which the serving tree's extra leaf takes over).
            table = uses["['tok_emb']['embedding']"]
            assert f32_to_bf16 in table and len(set(table)) > 1


# -- serve_run: the record, the count, and the live swap -----------------

def _serve_cfg(tmp_path, name, compute_dtype, **over):
    from tensorflow_distributed_tpu.config import TrainConfig
    cfg = TrainConfig(mode="serve", model="gpt_lm", model_size="tiny",
                      compute_dtype=compute_dtype, tie_embeddings=True,
                      seed=5, seq_len=64, **over)
    cfg.serve.num_requests = 5
    cfg.serve.num_slots = 2
    cfg.serve.max_new_tokens = 10
    cfg.serve.prompt_len_min = 4
    cfg.serve.prompt_len_max = 14
    cfg.observe.metrics_jsonl = str(tmp_path / f"{name}.jsonl")
    cfg.validate()
    return cfg


def _records(cfg, event):
    with open(cfg.observe.metrics_jsonl) as f:
        return [r for r in map(json.loads, f) if r["event"] == event]


@pytest.mark.parametrize("compute_dtype", ["bfloat16", "float32"])
def test_start_record_says_what_the_engine_holds(tmp_path, compute_dtype):
    from tensorflow_distributed_tpu.serve.run import serve_run

    cfg = _serve_cfg(tmp_path, compute_dtype, compute_dtype)
    assert serve_run(cfg)["requests"] == 5
    (start,) = _records(cfg, "start")
    held = start["serving_params"]
    model, params = _gpt(tie_embeddings=True)
    # ``params`` counts the MODEL's parameters, not the tree's extra leaf
    assert start["params"] == sum(x.size for x in
                                  jax.tree_util.tree_leaves(params))
    assert held["bytes_trained"] == 4 * start["params"]
    if compute_dtype == "bfloat16":
        assert held["leaves_cast"] > 0
        assert held["bytes_held"] < held["bytes_trained"]
    else:
        assert held["leaves_cast"] == 0
        assert held["bytes_held"] == held["bytes_trained"]


def test_live_swap_through_serve_run_is_token_identical_and_a_cache_hit(
        tmp_path, monkeypatch):
    """``reload_fn``'s path: restore into the TRAINING layout, cast as
    the booted tree was, ``swap_params`` accepts it (structure, dtypes,
    shardings), and the decode program is not traced again."""
    from tensorflow_distributed_tpu.parallel.mesh import make_mesh
    from tensorflow_distributed_tpu.serve import engine as engine_mod
    from tensorflow_distributed_tpu.serve import run as run_mod
    from tensorflow_distributed_tpu.train import checkpoint as ckpt
    from tensorflow_distributed_tpu.train.loop import (
        _build_model_and_state, _GenTask)

    ckpt_dir = str(tmp_path / "ckpt")
    base_cfg = _serve_cfg(tmp_path, "base", "bfloat16",
                          checkpoint_dir=ckpt_dir)
    shim = _GenTask(vocab_size=64,
                    sample_input=np.zeros((2, 64), np.int32))
    _, state = _build_model_and_state(base_cfg, make_mesh(base_cfg.mesh),
                                      shim)
    # Other weights than a fresh init's, so a swap that restored nothing
    # could not pass.
    state = state.replace(step=3, params=jax.tree_util.tree_map(
        lambda x: x * 1.5 + 0.01, state.params))
    ckpt.save(ckpt_dir, state)

    seen = {}

    class Engine(run_mod.SlotDecodeEngine):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            seen["engine"] = self

        def swap_params(self, new_params):
            seen["traces_before"] = self._step_fn._cache_size()
            seen["old"] = self.params
            super().swap_params(new_params)

    class Sched(run_mod.Scheduler):
        def run(self, requests):
            done = super().run(requests)
            seen["tokens"] = {c.rid: list(c.tokens) for c in done}
            return done

    monkeypatch.setattr(run_mod, "SlotDecodeEngine", Engine)
    monkeypatch.setattr(run_mod, "Scheduler", Sched)

    def tokens(cfg):
        run_mod.serve_run(cfg)
        return seen.pop("tokens")

    engine_mod._compiled_step.cache_clear()
    base = tokens(base_cfg)
    assert len(base) == 5 and "old" not in seen
    engine_mod._compiled_step.cache_clear()
    swap_cfg = _serve_cfg(tmp_path, "swap", "bfloat16",
                          checkpoint_dir=ckpt_dir)
    swap_cfg.resilience.fault_plan = "reload@4"
    swap_cfg.validate()
    assert tokens(swap_cfg) == base
    eng = seen["engine"]
    assert eng.swaps == 1 and eng.params is not seen["old"]
    # the swapped tree is a jit cache hit: nothing was traced for it
    assert eng._step_fn._cache_size() == seen["traces_before"]
    got, was = _keyed(eng.params), _keyed(seen["old"])
    assert f"['{HEAD_TABLE}']" in got
    assert {k: (x.shape, x.dtype) for k, x in got.items()} == {
        k: (x.shape, x.dtype) for k, x in was.items()}
    (swap,) = [r for r in _records(swap_cfg, "recovery")
               if r.get("kind") == "weight_swap"]
    assert swap["ckpt_step"] == 3
