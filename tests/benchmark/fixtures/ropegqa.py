"""A second architecture for tests/benchmark/test_second_model.py, which
copies this file to ``perfbench/models/ropegqa.py`` of a temporary root: a
decoder with rotary positions, grouped-query attention and an untied head,
which the program serves today as ``gpt_lm --pos-emb rope --n-kv-heads n
--tie-embeddings false``. Other leaves than GPT-2's, another forward pass,
and the sizes under the key names such a model is published with. It is
only served, so it defines no training part.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

STD = 0.02
LN_EPS = 1e-6
ROPE_THETA = 10000.0         # the program's default; no configuration moves it


def sizes(src: Dict[str, Any]) -> Dict[str, int]:
    out = {k: int(src[k]) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "intermediate_size",
        "max_position_embeddings")}
    out["n_positions"] = out["max_position_embeddings"]
    return out


def _leaves(s):
    """(path, shape, centre) of every leaf, in the program's tree."""
    d, h, nk = (s["hidden_size"], s["num_attention_heads"],
                s["num_key_value_heads"])
    dh, f, v = d // h, s["intermediate_size"], s["vocab_size"]
    norm = lambda name: [((name, "scale"), (d,), 1.0),  # noqa: E731
                         ((name, "bias"), (d,), 0.0)]
    dense = lambda name, kshape, bshape: [  # noqa: E731
        (name + ("kernel",), kshape, 0.0), (name + ("bias",), bshape, 0.0)]
    out = [(("tok_emb", "embedding"), (v, d), 0.0)] + norm("ln_f") \
        + dense(("lm_head",), (d, v), (v,))
    for i in range(s["num_hidden_layers"]):
        layer = norm("ln1") + norm("ln2") \
            + dense(("attn", "q"), (d, h, dh), (h, dh)) \
            + dense(("attn", "kv"), (d, 2, nk, dh), (2, nk, dh)) \
            + dense(("attn", "out"), (h, dh, d), (d,)) \
            + dense(("mlp", "up"), (d, f), (f,)) \
            + dense(("mlp", "down"), (f, d), (d,))
        out += [((f"layer_{i}",) + p, shape, c) for p, shape, c in layer]
    return out


def make_params(key, sizes: Dict[str, int], stacked: bool = False):
    """The program's tree. The reference below reads that same layout, so
    ``stacked`` changes nothing here."""
    tree: Dict[str, Any] = {}
    for i, (path, shape, centre) in enumerate(_leaves(sizes)):
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = centre + STD * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
    return tree


def _round(x, precision: str):
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"precision {precision!r}; have f32, bf16, fp8")


def _mm(spec, a, b, precision):
    return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _ln(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _rope(x):
    """x [B, L, H, Dh]: rotate the pairs (x[i], x[i + Dh/2]) by the
    position times theta^(-i / (Dh/2))."""
    half = x.shape[-1] // 2
    freqs = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits_fn(params, tokens, precision: str = "f32"):
    """tokens [B, L] -> logits [B, L, V], float32."""
    L = tokens.shape[1]
    x = params["tok_emb"]["embedding"][tokens]
    causal = jnp.tril(jnp.ones((L, L), bool))[None, None]
    n = sum(k.startswith("layer_") for k in params)
    for i in range(n):
        p = params[f"layer_{i}"]
        y = _ln(x, p["ln1"])
        q = _mm("bld,dhe->blhe", y, p["attn"]["q"]["kernel"],
                precision) + p["attn"]["q"]["bias"]
        kv = _mm("bld,dtke->bltke", y, p["attn"]["kv"]["kernel"],
                 precision) + p["attn"]["kv"]["bias"]
        groups = q.shape[2] // kv.shape[3]
        k = jnp.repeat(_rope(kv[:, :, 0]), groups, axis=2)
        v = jnp.repeat(kv[:, :, 1], groups, axis=2)
        q = _rope(q)
        s = _mm("bqhe,bkhe->bhqk", q, k, precision) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = _mm("bhqk,bkhe->bqhe", a, v, precision)
        x = x + _mm("bqhe,hed->bqd", o, p["attn"]["out"]["kernel"],
                    precision) + p["attn"]["out"]["bias"]
        y = _mm("bld,df->blf", _ln(x, p["ln2"]), p["mlp"]["up"]["kernel"],
                precision) + p["mlp"]["up"]["bias"]
        y = jax.nn.gelu(y, approximate=True)
        x = x + _mm("blf,fd->bld", y, p["mlp"]["down"]["kernel"],
                    precision) + p["mlp"]["down"]["bias"]
    x = _ln(x, params["ln_f"])
    return _mm("bld,dv->blv", x, params["lm_head"]["kernel"],
               precision) + params["lm_head"]["bias"]


@functools.partial(jax.jit, static_argnames=("precision",))
def served_token_gaps(params, seqs, precision: str = "f32"):
    logits = logits_fn(params, seqs, precision)[:, :-1]
    best = jnp.max(logits, -1)
    nxt = jnp.take_along_axis(logits, seqs[:, 1:, None], -1)[..., 0]
    return best - nxt, jnp.argmax(logits, -1).astype(jnp.int32)


@jax.jit
def gaps_of(params, seqs, chosen):
    logits = logits_fn(params, seqs)[:, :-1]
    c = jnp.take_along_axis(logits, chosen[..., None], -1)[..., 0]
    return jnp.max(logits, -1) - c


def reference_positions(sizes: Dict[str, int], longest: int) -> int:
    return sizes["n_positions"]
