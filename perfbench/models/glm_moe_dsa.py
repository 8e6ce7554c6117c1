"""GLM-5.2 (``model_type: glm_moe_dsa``) as the benchmark knows it: the
sizes it reads from a configuration, its weights from ``--seed``, its
plain reference, and the counts its per-layer readers need. It imports
nothing of the program.

**The architecture** (zai-org/GLM-5.2 ``config.json``; the layer
equations follow the public DeepSeek-V3 / V3.2 references of the same
mechanisms). Pre-norm residual blocks, RMSNorm, no biases.

- MLA: ``c_q = rms(x W_qa)``, ``q = c_q W_qb`` -> heads x (nope + rope);
  ``[c_kv, k_r] = x W_kva``, ``c_kv = rms(c_kv)``; interleaved RoPE on
  ``q_rope`` and on the single ``k_r``; ``[k_nope, v] = c_kv W_kvb``;
  scores ``(q_nope.k_nope + q_rope.k_r) / sqrt(nope + rope)``, softmax
  over the SELECTED causal positions only, output ``concat(o) W_o``.
  Computed here in the plain expanded (non-absorbed) form.
- DSA: a ``full`` layer scores ``I[t, s] = sum_j w[t, j] relu(q^I[t, j]
  . k^I[s])`` with ``q^I = c_q W^I_q``, ``k^I = LayerNorm(x W^I_k)``,
  ``w = x W^I_w * heads^-1/2 * dim^-1/2`` and keeps the ``index_topk``
  causal positions with the largest ``I`` (all while fewer exist; exact
  ``top_k``); a ``shared`` layer reuses the nearest full layer's.
- MLP: dense SwiGLU, or sigmoid-routed experts: pick by ``s + b``,
  weigh by ``s`` normalised over the picked, times
  ``routed_scaling_factor``; plus the shared expert. The reference is
  given THE SAME SHARE as the program (the ids of the experts held; the
  router's width is the published one): it loops over the held experts
  and leaves out what absent experts would add, as the chip does.

**Departures from the source**, all under ``assumed`` in the
configuration: the indexer's LayerNorm epsilon (1e-6), its RoPE on the
FIRST ``qk_rope_head_dim`` numbers of each index head, its scales, and
no Hadamard rotation or fp8 quantisation of the index vectors (an
orthogonal rotation leaves every dot product unchanged; fp8 is a
deployment's precision choice) are DeepSeek-V3.2's, not in
``config.json``. The multi-token-prediction module is left out (it does
not enter the main model's logits).

**Weights.** Made on the device in one jitted call from the key; the
program's tree (bfloat16 leaves, the router's bias float32). The
reference reads the same bfloat16 values and upcasts each matrix where
it is used, so no float32 copy of the model ever exists.

**The plain reference.** float32 ``jax.numpy``, ``highest`` precision,
one sequence at a time, its attention, index scores, MLPs and head in
blocks of positions so that 14.8k positions fit beside the weights.
``precision`` selects the control, as in ``models/gpt2.py``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

# The source's keys this file reads (a configuration, or its
# ``rehearsal.sizes``, holds them all).
INT_KEYS = ("vocab_size", "hidden_size", "num_attention_heads",
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "index_n_heads",
            "index_head_dim", "index_topk", "intermediate_size",
            "moe_intermediate_size", "num_experts_per_tok",
            "n_shared_experts", "num_hidden_layers", "n_routed_experts",
            "max_position_embeddings")
INDEX_LN_EPS = 1e-6


def sizes(src: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {k: int(src[k]) for k in INT_KEYS}
    lo = int(src.get("first_layer_held", 0))
    n = out["num_hidden_layers"]
    out["layers"] = tuple(
        (src["mlp_layer_types"][lo + i], src["indexer_types"][lo + i])
        for i in range(n))
    out["router_experts"] = int(src.get("n_routed_experts_published",
                                        out["n_routed_experts"]))
    out["experts_held"] = tuple(int(e) for e in src.get(
        "experts_held", range(out["n_routed_experts"])))
    out["routed_scaling_factor"] = float(src["routed_scaling_factor"])
    out["rms_norm_eps"] = float(src["rms_norm_eps"])
    out["rope_theta"] = float(src["rope_parameters"]["rope_theta"])
    out["n_positions"] = out["max_position_embeddings"]
    return out


# -- weights ----------------------------------------------------------------

STD = 0.02


def leaf_shapes(s: Dict[str, Any]) -> List[Tuple[Tuple[str, ...], tuple,
                                                 float, Any]]:
    """(path, shape, centre, dtype) of every leaf of the program's
    tree."""
    bf, f32 = jnp.bfloat16, jnp.float32
    D, H = s["hidden_size"], s["num_attention_heads"]
    rq, rkv = s["q_lora_rank"], s["kv_lora_rank"]
    dn, dr, dv = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                  s["v_head_dim"])
    nh, dh = s["index_n_heads"], s["index_head_dim"]
    E, F = len(s["experts_held"]), s["moe_intermediate_size"]
    Fs = F * s["n_shared_experts"]
    out = [(("tok_emb",), (s["vocab_size"], D), 0.0, bf),
           (("final_norm", "scale"), (D,), 1.0, bf),
           (("lm_head", "kernel"), (D, s["vocab_size"]), 0.0, bf)]
    for i, (mlp, indexer) in enumerate(s["layers"]):
        lay = f"layer_{i}"
        a = (lay, "attn")
        out += [((lay, "attn_norm", "scale"), (D,), 1.0, bf),
                ((lay, "mlp_norm", "scale"), (D,), 1.0, bf),
                (a + ("q_a", "kernel"), (D, rq), 0.0, bf),
                (a + ("q_a_norm", "scale"), (rq,), 1.0, bf),
                (a + ("q_b", "kernel"), (rq, H, dn + dr), 0.0, bf),
                (a + ("kv_a", "kernel"), (D, rkv + dr), 0.0, bf),
                (a + ("kv_a_norm", "scale"), (rkv,), 1.0, bf),
                (a + ("kv_b", "kernel"), (rkv, H, dn + dv), 0.0, bf),
                (a + ("o", "kernel"), (H, dv, D), 0.0, bf)]
        if indexer == "full":
            ix = a + ("indexer",)
            out += [(ix + ("wq_b", "kernel"), (rq, nh, dh), 0.0, bf),
                    (ix + ("wk", "kernel"), (D, dh), 0.0, bf),
                    (ix + ("k_norm", "scale"), (dh,), 1.0, bf),
                    (ix + ("k_norm", "bias"), (dh,), 0.0, bf),
                    (ix + ("weights_proj", "kernel"), (D, nh), 0.0, bf)]
        if mlp == "dense":
            Fd = s["intermediate_size"]
            out += [((lay, "mlp", "gate", "kernel"), (D, Fd), 0.0, bf),
                    ((lay, "mlp", "up", "kernel"), (D, Fd), 0.0, bf),
                    ((lay, "mlp", "down", "kernel"), (Fd, D), 0.0, bf)]
        else:
            m = (lay, "moe")
            out += [(m + ("router", "kernel"), (D, s["router_experts"]),
                     0.0, bf),
                    (m + ("router_bias",), (s["router_experts"],), 0.0, f32),
                    (m + ("experts_gate", "kernel"), (E, D, F), 0.0, bf),
                    (m + ("experts_up", "kernel"), (E, D, F), 0.0, bf),
                    (m + ("experts_down", "kernel"), (E, F, D), 0.0, bf)]
            if Fs:
                out += [(m + ("shared_gate", "kernel"), (D, Fs), 0.0, bf),
                        (m + ("shared_up", "kernel"), (D, Fs), 0.0, bf),
                        (m + ("shared_down", "kernel"), (Fs, D), 0.0, bf)]
    return out


_BOUND: Dict[Any, Dict[str, Any]] = {}


def _shape_key(params) -> Any:
    return tuple((jax.tree_util.keystr(p), tuple(x.shape)) for p, x in
                 jax.tree_util.tree_leaves_with_path(params))


def _put(tree: Dict[str, Any], path, leaf) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = leaf


def make_params(key: jax.Array, sizes: Dict[str, Any],
                stacked: bool = False) -> Dict[str, Any]:
    """The whole tree (trace this under jit), every leaf N(centre, 0.02)
    rounded to the dtype the program stores. The layers differ in kind,
    so the reference reads the program's own layout: ``stacked`` changes
    nothing."""
    out: Dict[str, Any] = {}
    for i, (path, shape, centre, dtype) in enumerate(leaf_shapes(sizes)):
        leaf = centre + STD * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
        _put(out, path, leaf.astype(dtype))
    _BOUND[_shape_key(out)] = dict(sizes)
    return out


def param_count(sizes: Dict[str, Any]) -> int:
    n = 0
    for _, shape, _, _ in leaf_shapes(sizes):
        k = 1
        for d in shape:
            k *= d
        n += k
    return n


# -- the plain reference ----------------------------------------------------

PRECISIONS = ("f32", "bf16", "fp8")
HI = jax.lax.Precision.HIGHEST


def _round_operand(x, precision: str):
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        return (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    raise ValueError(f"precision {precision!r}; have {PRECISIONS}")


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _round_operand(a.astype(jnp.float32), precision),
                      _round_operand(b.astype(jnp.float32), precision),
                      precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _layer_norm(x, scale, bias, eps=INDEX_LN_EPS):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32))


def _rope(x, pos, theta):
    """Interleaved RoPE: pair i is (x[2i], x[2i+1]). x [L, d] or
    [L, H, d]; pos [L]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]      # [L, d/2]
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _block(n: int, target: int) -> int:
    if n <= target:
        return n
    for b in range(target, 0, -1):
        if n % b == 0:
            return b
    return 1


def _blocks(fn, L: int, target: int):
    """``fn(start, size)`` over consecutive blocks of positions; the
    results concatenated along axis 0."""
    b = _block(L, target)
    out = jax.lax.map(lambda i: fn(i * b, b), jnp.arange(L // b))
    return jax.tree_util.tree_map(
        lambda a: a.reshape((L,) + a.shape[2:]), out)


def _swiglu(x, gate, up, down, precision):
    h = jax.nn.silu(_mm("ld,df->lf", x, gate, precision)) \
        * _mm("ld,df->lf", x, up, precision)
    return _mm("lf,fd->ld", h, down, precision)


def router(x, w_g, bias, s: Dict[str, Any], precision: str):
    """(ids [L, k], weights [L, k]): picked by ``s + b``, weighted by
    ``s`` over the picked, scaled."""
    score = jax.nn.sigmoid(_mm("ld,de->le", x, w_g, precision))
    _, ids = jax.lax.top_k(score + bias[None, :], s["num_experts_per_tok"])
    w = jnp.take_along_axis(score, ids, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return ids, w * s["routed_scaling_factor"]


def moe_layer(x, p, s: Dict[str, Any], precision: str = "f32",
              shared: bool = True):
    """x [L, D] -> the held experts' part (a loop over them, every
    expert over every token, weighted by the router's weight or 0) plus,
    with ``shared``, the shared expert."""
    ids, w = router(x, p["router"]["kernel"], p["router_bias"], s, precision)
    y = jnp.zeros_like(x)
    for j, e in enumerate(s["experts_held"]):
        w_e = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)      # [L]
        y = y + w_e[:, None] * _swiglu(
            x, p["experts_gate"]["kernel"][j], p["experts_up"]["kernel"][j],
            p["experts_down"]["kernel"][j], precision)
    if shared and "shared_gate" in p:
        y = y + _swiglu(x, p["shared_gate"]["kernel"],
                        p["shared_up"]["kernel"],
                        p["shared_down"]["kernel"], precision)
    return y


def index_scores(c_q, x, pos, p, s: Dict[str, Any], precision: str):
    """The indexer's (q^I [L, nh, dh], k^I [L, dh], w [L, nh])."""
    dr, nh, dh = s["qk_rope_head_dim"], s["index_n_heads"], \
        s["index_head_dim"]
    q = _mm("lr,rhd->lhd", c_q, p["wq_b"]["kernel"], precision)
    q = jnp.concatenate([_rope(q[..., :dr], pos, s["rope_theta"]),
                         q[..., dr:]], -1)
    k = _layer_norm(_mm("ld,de->le", x, p["wk"]["kernel"], precision),
                    p["k_norm"]["scale"], p["k_norm"]["bias"])
    k = jnp.concatenate([_rope(k[:, :dr], pos, s["rope_theta"]),
                         k[:, dr:]], -1)
    w = _mm("ld,dh->lh", x, p["weights_proj"]["kernel"], precision) \
        * (nh ** -0.5 * dh ** -0.5)
    return q, k, w


def selection(qI, kI, w, topk: int, precision: str):
    """keep [L, L] bool: the exact top-``topk`` causal positions of
    every query (all causal ones while fewer exist)."""
    L = qI.shape[0]
    cols = jnp.arange(L)

    def block(lo, n):
        q = jax.lax.dynamic_slice_in_dim(qI, lo, n)
        wb = jax.lax.dynamic_slice_in_dim(w, lo, n)
        sc = jax.nn.relu(_mm("qhd,sd->hqs", q, kI, precision))
        I = jnp.einsum("hqs,qh->qs", sc, wb, precision=HI)
        causal = cols[None, :] <= (lo + jnp.arange(n))[:, None]
        if L <= topk:
            return causal
        _, idx = jax.lax.top_k(jnp.where(causal, I, -jnp.inf), topk)
        picked = jnp.zeros((n, L), bool).at[
            jnp.arange(n)[:, None], idx].set(True)
        return picked & causal

    return _blocks(block, L, 128)


def attention(x, pos, p, s: Dict[str, Any], keep, full: bool,
              precision: str):
    """MLA over the selection. Returns (output [L, D], keep)."""
    dn, dr = s["qk_nope_head_dim"], s["qk_rope_head_dim"]
    rkv, eps, theta = s["kv_lora_rank"], s["rms_norm_eps"], s["rope_theta"]
    c_q = _rms(_mm("ld,dr->lr", x, p["q_a"]["kernel"], precision),
               p["q_a_norm"]["scale"], eps)
    kv_a = _mm("ld,de->le", x, p["kv_a"]["kernel"], precision)
    c_kv = _rms(kv_a[:, :rkv], p["kv_a_norm"]["scale"], eps)
    k_r = _rope(kv_a[:, rkv:], pos, theta)                       # [L, dr]
    kv = _mm("lr,rhe->lhe", c_kv, p["kv_b"]["kernel"], precision)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    if full:
        keep = selection(*index_scores(c_q, x, pos, p["indexer"], s,
                                       precision), s["index_topk"],
                         precision)
    L = x.shape[0]
    scale = (dn + dr) ** -0.5

    def block(lo, n):
        cq = jax.lax.dynamic_slice_in_dim(c_q, lo, n)
        q = _mm("lr,rhe->lhe", cq, p["q_b"]["kernel"], precision)
        q_rope = _rope(q[..., dn:], lo + jnp.arange(n), theta)
        sc = (_mm("qhe,khe->hqk", q[..., :dn], k_nope, precision)
              + _mm("qhe,ke->hqk", q_rope, k_r, precision)) * scale
        kp = jax.lax.dynamic_slice_in_dim(keep, lo, n)
        sc = jnp.where(kp[None], sc, -jnp.inf)
        a = jax.nn.softmax(sc, axis=-1)
        o = _mm("hqk,khv->qhv", a, v, precision)
        return _mm("qhv,hvd->qd", o, p["o"]["kernel"], precision)

    return _blocks(block, L, 128), keep


def forward_with_selections(params, tokens, sizes: Dict[str, Any],
                            precision: str = "f32",
                            forced: Optional[Dict[int, Any]] = None):
    """tokens [L] -> (final-normed features [L, D], {full layer: its keep
    mask}). ``forced`` {full layer: keep [L, L]} makes those layers attend
    over the GIVEN sets instead of their own
    (``tools/selection_overlap.py``)."""
    s = sizes
    L = tokens.shape[0]
    pos = jnp.arange(L)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    keep, keeps = None, {}
    for i, (mlp, indexer) in enumerate(s["layers"]):
        p = params[f"layer_{i}"]
        y = _rms(x, p["attn_norm"]["scale"], s["rms_norm_eps"])
        if forced and i in forced:
            keep = forced[i]
        y, keep = attention(y, pos, p["attn"], s, keep,
                            indexer == "full" and not (forced and i in forced),
                            precision)
        if indexer == "full":
            keeps[i] = keep
        x = x + y
        y = _rms(x, p["mlp_norm"]["scale"], s["rms_norm_eps"])
        if mlp == "dense":
            m = p["mlp"]
            f = lambda lo, n: _swiglu(
                jax.lax.dynamic_slice_in_dim(y, lo, n), m["gate"]["kernel"],
                m["up"]["kernel"], m["down"]["kernel"], precision)
        else:
            f = lambda lo, n: moe_layer(
                jax.lax.dynamic_slice_in_dim(y, lo, n), p["moe"], s,
                precision)
        x = x + _blocks(f, L, 1024)
    return _rms(x, params["final_norm"]["scale"], s["rms_norm_eps"]), keeps


def forward_features(params, tokens, sizes: Dict[str, Any],
                     precision: str = "f32"):
    """tokens [L] -> the final-normed features [L, D] of one sequence."""
    return forward_with_selections(params, tokens, sizes, precision)[0]


def logits_fn(params, tokens, sizes, precision: str = "f32"):
    """tokens [B, L] -> logits [B, L, V] float32 (small sizes: the
    tests; the runners go through the blocked functions below)."""
    return jax.lax.map(
        lambda t: _mm("ld,dv->lv", forward_features(params, t, sizes,
                                                    precision),
                      params["lm_head"]["kernel"], precision), tokens)


def _head_blocks(params, feats, fn, precision):
    """``fn(logits block [n, V], start, n)`` over blocks of positions."""
    return _blocks(
        lambda lo, n: fn(_mm("ld,dv->lv",
                             jax.lax.dynamic_slice_in_dim(feats, lo, n),
                             params["lm_head"]["kernel"], precision), lo, n),
        feats.shape[0], 512)


# The runners call the next two functions with the weights and the
# sequences only (``harness/serve_runner.py``). What the reference needs
# beyond the weights' shapes (the layer kinds, WHICH experts are held,
# top-k sizes, scales) is the ``sizes`` the weights were made from:
# ``make_params`` records them under the tree's shapes.
def _bound_sizes(params) -> Dict[str, Any]:
    try:
        return _BOUND[_shape_key(params)]
    except KeyError:
        raise ValueError(
            "these weights were not made by this file's make_params in "
            "this process (the reference needs the sizes they were made "
            "from)") from None


def served_token_gaps(params, seqs, precision: str = "f32"):
    """seqs [B, L] (prompt, served tokens, padding). For every position
    t the reference predicts seqs[t+1]: (gap, top) [B, L-1], gap how far
    the reference's logit of the token that follows lies below its best,
    top its own argmax (with ``precision`` below f32: what that
    precision would have served; score it with :func:`gaps_of`)."""
    return _served(params, seqs, _freeze(_bound_sizes(params)), precision)


def gaps_of(params, seqs, chosen):
    """The f32 reference's gap of ``chosen`` [B, L-1] at every position
    given the context ``seqs[:, :t+1]``."""
    return _gaps_of(params, seqs, chosen, _freeze(_bound_sizes(params)))


def _freeze(sizes: Dict[str, Any]):
    return tuple(sorted(sizes.items()))


@functools.partial(jax.jit, static_argnames=("frozen", "precision"))
def _served(params, seqs, frozen, precision):
    sizes = dict(frozen)

    def one(seq):
        feats = forward_features(params, seq, sizes, precision)
        nxt = jnp.concatenate([seq[1:], seq[:1]])

        def score(logits, lo, n):
            want = jax.lax.dynamic_slice_in_dim(nxt, lo, n)
            best = jnp.max(logits, -1)
            got = jnp.take_along_axis(logits, want[:, None], -1)[:, 0]
            return best - got, jnp.argmax(logits, -1).astype(jnp.int32)

        gap, top = _head_blocks(params, feats, score, precision)
        return gap[:-1], top[:-1]

    return jax.lax.map(one, seqs)


@functools.partial(jax.jit, static_argnames=("frozen",))
def _gaps_of(params, seqs, chosen, frozen):
    sizes = dict(frozen)

    def one(args):
        seq, ch = args
        feats = forward_features(params, seq, sizes, "f32")
        ch = jnp.concatenate([ch, ch[:1]])

        def score(logits, lo, n):
            c = jax.lax.dynamic_slice_in_dim(ch, lo, n)
            return jnp.max(logits, -1) - jnp.take_along_axis(
                logits, c[:, None], -1)[:, 0]

        return _head_blocks(params, feats, score, "f32")[:-1]

    return jax.lax.map(one, (seqs, chosen))


def reference_positions(sizes: Dict[str, Any], longest: int) -> int:
    """The length the serve runner pads a sampled sequence to: the next
    multiple of 256 at or above the sample's longest (the reference
    blocks its own forward pass; a 16k pad would cost a third more than
    the longest request needs)."""
    return min(-(-longest // 256) * 256, max(sizes["n_positions"], longest))


# -- counts -----------------------------------------------------------------

def n_full_layers(sizes: Dict[str, Any]) -> int:
    return sum(1 for _, ix in sizes["layers"] if ix == "full")


def cache_bytes_per_token(sizes: Dict[str, Any], bytes_per_el: int = 2
                          ) -> Dict[str, int]:
    """What one token leaves in the cache, by kind of leaf: the numbers
    the mathematics needs (``kv_lora_rank + qk_rope_head_dim`` a layer).
    The program stores each latent row in whole 128-lane tiles (640 for
    576), as the TPU's tiled memory would pad it anyway."""
    return {"latent": len(sizes["layers"]) * bytes_per_el
            * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]),
            "index_keys": n_full_layers(sizes) * bytes_per_el
            * sizes["index_head_dim"]}


def decode_step_bytes(param_bytes: int, sizes: Dict[str, Any], slots: float,
                      kv_bytes_per_el: int = 2, *,
                      experts_hit: Optional[float] = None,
                      keys_kept: Optional[float] = None,
                      keys_available: Optional[float] = None) -> float:
    """Bytes one decode step with ``slots`` LIVE rows must read. Every
    parameter as stored, once, except the embedding table (one row a live
    row) and the routed experts: a grouped matmul skips an expert no pair
    reached, so of the held experts only ``experts_hit`` (distinct held
    experts reached, summed over the expert layers) are read. Of the
    cache, ``keys_kept`` selected latent rows in every layer and
    ``keys_available`` index keys in every full layer.

    The three counts are the PROGRAM's own, a step on average
    (``serve_summary``: ``moe_experts_hit``, ``select_keys_kept``,
    ``select_keys_available`` over ``decode_steps``). Where one is not
    given it is what ``slots`` live rows would need at the least: each
    row's ``num_experts_per_tok`` picks uniform over the router's width
    (``held x (1 - (1 - k/E)^slots)`` held experts reached a layer),
    ``index_topk`` rows kept a slot, and no index key beyond those."""
    D, per = sizes["hidden_size"], cache_bytes_per_token(sizes,
                                                         kv_bytes_per_el)
    held, moe_layers = len(sizes["experts_held"]), sum(
        1 for mlp, _ in sizes["layers"] if mlp == "sparse")
    one_expert = 3 * D * sizes["moe_intermediate_size"] * 2
    if experts_hit is None:
        miss = 1.0 - sizes["num_experts_per_tok"] / sizes["router_experts"]
        experts_hit = moe_layers * held * (1.0 - miss ** slots)
    if keys_kept is None:
        keys_kept = slots * sizes["index_topk"]
    if keys_available is None:
        keys_available = keys_kept
    emb = sizes["vocab_size"] * D * 2
    return (param_bytes - emb + slots * D * 2
            - (moe_layers * held - experts_hit) * one_expert
            + keys_kept * per["latent"]
            + keys_available * per["index_keys"])


def latent_attend_cost(sizes: Dict[str, Any], slots: int, keys: int,
                       bytes_per_el: int = 2) -> tuple:
    """(operations, bytes) of one call of the latent attend kernel over
    already gathered rows: scores and weighted sum of ``keys`` rows of
    width ``kv_lora_rank (+ rope)`` for every head of every slot."""
    H, r, dr = (sizes["num_attention_heads"], sizes["kv_lora_rank"],
                sizes["qk_rope_head_dim"])
    ops = 2.0 * slots * H * keys * ((r + dr) + r)
    byts = bytes_per_el * slots * (keys * (r + dr) + H * (r + dr)) \
        + 4 * slots * H * r
    return ops, byts


def index_scores_cost(sizes: Dict[str, Any], slots: int, positions: int,
                      bytes_per_el: int = 2) -> tuple:
    """(operations, bytes) of one call of the decode index-score kernel:
    every index head of one query a slot against ``positions`` cached
    index keys."""
    nh, dh = sizes["index_n_heads"], sizes["index_head_dim"]
    ops = 2.0 * slots * nh * dh * positions
    byts = bytes_per_el * slots * (positions * dh + nh * dh) \
        + 4 * slots * positions
    return ops, byts
