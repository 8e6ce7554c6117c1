"""A.X-K1 (``model_type: axk1``, DeepSeek-V3's key names) as the benchmark
knows it: the sizes it reads from a configuration, its weights from
``--seed``, its plain reference, and the counts its per-layer readers
need. It imports nothing of the program and nothing of the other model
files: the reference below is written from the equations, on its own.

**The architecture** (skt/A.X-K1 ``config.json``; the layer equations are
DeepSeek-V3's, whose keys these are). Pre-norm residual blocks, RMSNorm,
no biases.

- MLA: ``c_q = rms(x W_qa)``, ``q = c_q W_qb`` -> heads x (nope + rope);
  ``[c_kv, k_r] = x W_kva``, ``c_kv = rms(c_kv)``; RoPE on ``q_rope`` and
  on the single ``k_r``; ``[k_nope, v] = c_kv W_kvb``; scores ``scale
  (q_nope . k_nope + q_rope . k_r)`` over EVERY causal position (no
  selection), output ``concat(o) W_o``. Computed here in the plain
  expanded (non-absorbed, uncached) form.
- YaRN (``rope_scaling``): ``f_i = theta^(-2i/d)``; ``cd(n) = d ln(L0 /
  (2 pi n)) / (2 ln theta)``; ``low = max(floor(cd(beta_fast)), 0)``,
  ``high = min(ceil(cd(beta_slow)), d - 1)``; ``ramp_i = clip((i - low) /
  (high - low), 0, 1)``; the frequency used is ``f_i (1 - ramp_i) + (f_i /
  s) ramp_i``. ``m(s, a) = 0.1 a ln s + 1``; cosine and sine times
  ``m(s, mscale) / m(s, mscale_all_dim)``; the softmax scale is ``(nope +
  rope)^-1/2 m(s, mscale_all_dim)^2``.
- MLP: dense SwiGLU, or group-limited sigmoid routing: ``s = sigmoid(x
  W_g)`` over all published experts, ``s' = s + b``; the experts are
  ``n_group`` groups of consecutive ids, a group scores the sum of its 2
  largest ``s'``, the ``topk_group`` best groups are kept and every
  ``s'`` outside them is out; the ``num_experts_per_tok`` largest ``s'``
  left are picked, weighted ``s / sum(s picked) * routed_scaling_factor``;
  plus the shared expert. The reference is given THE SAME SHARE as the
  program (the ids of the experts held; the router's width and groups are
  the published ones): it loops over the held experts and leaves out what
  absent experts would add, as the chip does.

**Departures from the published description**, all under ``assumed`` in
the configuration: interleaved RoPE pairs ``(x[2i], x[2i+1])`` (the config
has no ``rope_interleave``; DeepSeek-V3's reference rotates such pairs);
``topk_method: "none"`` beside ``n_group`` 8 read as the family's
group-limited selection with the top-2-sum group score and a float32
correction bias that picks and does not weigh; positions outside the kept
groups are set to ``-inf`` (not 0) before the pick; the held share of the
experts and of the vocabulary; 5 of the 61 layers.

**Weights.** Made on the device in one jitted call from the key; the
program's tree (bfloat16 leaves, the router's bias float32 and seeded
non-zero, so that what is picked by and what is weighted by differ). The
reference reads the same bfloat16 values and upcasts each matrix where it
is used, so no float32 copy of the model ever exists.

**The plain reference.** float32 ``jax.numpy``, ``highest`` precision, one
sequence at a time; attention in blocks of queries against all keys under
a causal mask made per block (nothing ``[L, L]`` exists), MLPs and head in
blocks of positions, so that 9,728 positions fit beside the weights.
``precision`` selects the control: the same mathematics with every
matmul's operands rounded to that precision first.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

INT_KEYS = ("vocab_size", "hidden_size", "num_attention_heads",
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "intermediate_size",
            "moe_intermediate_size", "num_experts_per_tok",
            "n_shared_experts", "num_hidden_layers", "n_routed_experts",
            "max_position_embeddings", "n_group", "topk_group")
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")


def sizes(src: Dict[str, Any]) -> Dict[str, Any]:
    """What this file reads of a configuration (or of its
    ``rehearsal.sizes``): every value hashable, so that the dict can be a
    static argument."""
    out: Dict[str, Any] = {k: int(src[k]) for k in INT_KEYS}
    lo = int(src.get("first_layer_held", 0))
    dense, freq = (int(src["first_k_dense_replace"]),
                   int(src.get("moe_layer_freq", 1)))
    out["layers"] = tuple(
        "sparse" if i >= dense and i % freq == 0 else "dense"
        for i in range(lo, lo + out["num_hidden_layers"]))
    out["router_experts"] = int(src.get("n_routed_experts_published",
                                        out["n_routed_experts"]))
    out["experts_held"] = tuple(int(e) for e in src.get(
        "experts_held", range(out["n_routed_experts"])))
    out["routed_scaling_factor"] = float(src["routed_scaling_factor"])
    out["rms_norm_eps"] = float(src["rms_norm_eps"])
    out["rope_theta"] = float(src["rope_theta"])
    yarn = src.get("rope_scaling") or {}
    if yarn and yarn.get("type") != "yarn":
        raise ValueError(f"rope_scaling type {yarn.get('type')!r}")
    out["yarn"] = tuple(float(yarn[k]) for k in YARN_KEYS) if yarn else ()
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
    E, F = len(s["experts_held"]), s["moe_intermediate_size"]
    Fs = F * s["n_shared_experts"]
    out = [(("tok_emb",), (s["vocab_size"], D), 0.0, bf),
           (("final_norm", "scale"), (D,), 1.0, bf),
           (("lm_head", "kernel"), (D, s["vocab_size"]), 0.0, bf)]
    for i, mlp in enumerate(s["layers"]):
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


# What the reference needs beyond the weights' shapes (which experts are
# held, the groups, YaRN's numbers) is the ``sizes`` the weights were made
# from: ``make_params`` records them under the tree's shapes, because the
# runners call the reference with the weights and the sequences only.
_BOUND: Dict[Any, Dict[str, Any]] = {}


def _shape_key(params) -> Any:
    return tuple((jax.tree_util.keystr(p), tuple(x.shape)) for p, x in
                 jax.tree_util.tree_leaves_with_path(params))


def make_params(key: jax.Array, sizes: Dict[str, Any],
                stacked: bool = False) -> Dict[str, Any]:
    """The whole tree (trace this under jit), every leaf N(centre, 0.02)
    rounded to the dtype the program stores; the router's bias therefore
    non-zero. The layers differ in kind, so the reference reads the
    program's own layout: ``stacked`` changes nothing."""
    out: Dict[str, Any] = {}
    for i, (path, shape, centre, dtype) in enumerate(leaf_shapes(sizes)):
        leaf = centre + STD * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf.astype(dtype)
    _BOUND[_shape_key(out)] = dict(sizes)
    return out


def param_count(sizes: Dict[str, Any]) -> int:
    return sum(math.prod(shape) for _, shape, _, _ in leaf_shapes(sizes))


# -- the plain reference ----------------------------------------------------

PRECISIONS = ("f32", "bf16", "fp8")
HI = jax.lax.Precision.HIGHEST


def _rounded(x, precision: str):
    x = x.astype(jnp.float32)
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
    return jnp.einsum(spec, _rounded(a, precision), _rounded(b, precision),
                      precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def yarn_mscale(factor: float, a: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * a * math.log(factor) + 1.0


def yarn_range(s: Dict[str, Any], d: int) -> Tuple[int, int]:
    """(low, high) of the ramp, in pair indices."""
    _, l0, fast, slow, _, _ = s["yarn"]

    def cd(n):
        return d * math.log(l0 / (2 * math.pi * n)) \
            / (2 * math.log(s["rope_theta"]))
    return max(math.floor(cd(fast)), 0), min(math.ceil(cd(slow)), d - 1)


def rope_frequencies(s: Dict[str, Any], d: int):
    """The d/2 angular frequencies, YaRN's blend where the sizes have
    one."""
    f = s["rope_theta"] ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if not s["yarn"]:
        return f
    low, high = yarn_range(s, d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / s["yarn"][0]) * ramp


def rope_magnitude(s: Dict[str, Any]) -> float:
    if not s["yarn"]:
        return 1.0
    factor, _, _, _, mscale, all_dim = s["yarn"]
    return yarn_mscale(factor, mscale) / yarn_mscale(factor, all_dim)


def softmax_scale(s: Dict[str, Any]) -> float:
    scale = (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]) ** -0.5
    if s["yarn"]:
        factor, _, _, _, _, all_dim = s["yarn"]
        if all_dim:
            scale *= yarn_mscale(factor, all_dim) ** 2
    return scale


def _rope(x, pos, s: Dict[str, Any]):
    """Interleaved RoPE: pair i is (x[2i], x[2i+1]). x [L, d] or
    [L, H, d]; pos [L]."""
    ang = pos.astype(jnp.float32)[:, None] \
        * rope_frequencies(s, x.shape[-1])[None, :]              # [L, d/2]
    if x.ndim == 3:
        ang = ang[:, None, :]
    m = rope_magnitude(s)
    cos, sin = m * jnp.cos(ang), m * jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _divisor(n: int, target: int) -> int:
    for b in range(min(n, target), 0, -1):
        if n % b == 0:
            return b
    return 1


def _in_blocks(fn, L: int, target: int):
    """``fn(start, size)`` over consecutive blocks of positions; the
    results concatenated along axis 0."""
    b = _divisor(L, target)
    out = jax.lax.map(lambda i: fn(i * b, b), jnp.arange(L // b))
    return jax.tree_util.tree_map(
        lambda a: a.reshape((L,) + a.shape[2:]), out)


def _swiglu(x, gate, up, down, precision):
    h = jax.nn.silu(_mm("ld,df->lf", x, gate, precision)) \
        * _mm("ld,df->lf", x, up, precision)
    return _mm("lf,fd->ld", h, down, precision)


def router(x, w_g, bias, s: Dict[str, Any], precision: str):
    """(ids [L, k], weights [L, k]) by group-limited selection: picked by
    ``s + b`` inside the kept groups, weighted by ``s`` over the picked,
    scaled."""
    score = jax.nn.sigmoid(_mm("ld,de->le", x, w_g, precision))
    choice = score + bias[None, :]
    G, keep = s["n_group"], s["topk_group"]
    if G > 1:
        per = choice.reshape(choice.shape[0], G, -1)
        best2 = jnp.sort(per, axis=-1)[..., -2:].sum(-1)          # [L, G]
        # a group is kept if fewer than `keep` groups score above it
        # (ties to the lower id, as a stable descending sort gives)
        order = jnp.argsort(-best2, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        choice = jnp.where((rank < keep)[:, :, None], per,
                           -jnp.inf).reshape(choice.shape)
    _, ids = jax.lax.top_k(choice, s["num_experts_per_tok"])
    w = jnp.take_along_axis(score, ids, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return ids, w * s["routed_scaling_factor"]


def moe_layer(x, p, s: Dict[str, Any], precision: str = "f32",
              shared: bool = True):
    """x [L, D] -> the held experts' part (a loop over them, every expert
    over every token, weighted by the router's weight or 0) plus, with
    ``shared``, the shared expert."""
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


def attention(x, pos, p, s: Dict[str, Any], precision: str):
    """Dense causal MLA of one sequence, expanded form: x [L, D] ->
    [L, D]."""
    dn, rkv, eps = (s["qk_nope_head_dim"], s["kv_lora_rank"],
                    s["rms_norm_eps"])
    c_q = _rms(_mm("ld,dr->lr", x, p["q_a"]["kernel"], precision),
               p["q_a_norm"]["scale"], eps)
    kv_a = _mm("ld,de->le", x, p["kv_a"]["kernel"], precision)
    c_kv = _rms(kv_a[:, :rkv], p["kv_a_norm"]["scale"], eps)
    k_r = _rope(kv_a[:, rkv:], pos, s)                           # [L, dr]
    kv = _mm("lr,rhe->lhe", c_kv, p["kv_b"]["kernel"], precision)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    L = x.shape[0]
    scale = softmax_scale(s)

    def block(lo, n):
        at = lo + jnp.arange(n)
        q = _mm("lr,rhe->lhe", jax.lax.dynamic_slice_in_dim(c_q, lo, n),
                p["q_b"]["kernel"], precision)
        q_rope = _rope(q[..., dn:], at, s)
        sc = (_mm("qhe,khe->hqk", q[..., :dn], k_nope, precision)
              + _mm("qhe,ke->hqk", q_rope, k_r, precision)) * scale
        sc = jnp.where(jnp.arange(L)[None, None, :] <= at[None, :, None],
                       sc, -jnp.inf)
        o = _mm("hqk,khv->qhv", jax.nn.softmax(sc, axis=-1), v, precision)
        return _mm("qhv,hvd->qd", o, p["o"]["kernel"], precision)

    return _in_blocks(block, L, 128)


def forward_features(params, tokens, sizes: Dict[str, Any],
                     precision: str = "f32"):
    """tokens [L] -> the final-normed features [L, D] of one sequence."""
    s = sizes
    L = tokens.shape[0]
    pos = jnp.arange(L)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for i, mlp in enumerate(s["layers"]):
        p = params[f"layer_{i}"]
        x = x + attention(_rms(x, p["attn_norm"]["scale"],
                               s["rms_norm_eps"]), pos, p["attn"], s,
                          precision)
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
        x = x + _in_blocks(f, L, 1024)
    return _rms(x, params["final_norm"]["scale"], s["rms_norm_eps"])


def logits_fn(params, tokens, sizes, precision: str = "f32"):
    """tokens [B, L] -> logits [B, L, V] float32 (small sizes: the
    tests; the runners go through the blocked functions below)."""
    return jax.lax.map(
        lambda t: _mm("ld,dv->lv", forward_features(params, t, sizes,
                                                    precision),
                      params["lm_head"]["kernel"], precision), tokens)


def _head_blocks(params, feats, fn, precision):
    """``fn(logits block [n, V], start, n)`` over blocks of positions."""
    return _in_blocks(
        lambda lo, n: fn(_mm("ld,dv->lv",
                             jax.lax.dynamic_slice_in_dim(feats, lo, n),
                             params["lm_head"]["kernel"], precision), lo, n),
        feats.shape[0], 512)


def _bound_sizes(params):
    try:
        return tuple(sorted(_BOUND[_shape_key(params)].items()))
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
    return _served(params, seqs, _bound_sizes(params), precision)


def gaps_of(params, seqs, chosen):
    """The f32 reference's gap of ``chosen`` [B, L-1] at every position
    given the context ``seqs[:, :t+1]``."""
    return _gaps_of(params, seqs, chosen, _bound_sizes(params))


@functools.partial(jax.jit, static_argnames=("frozen", "precision"))
def _served(params, seqs, frozen, precision):
    sizes = dict(frozen)

    def one(seq):
        feats = forward_features(params, seq, sizes, precision)
        nxt = jnp.roll(seq, -1)

        def score(logits, lo, n):
            want = jax.lax.dynamic_slice_in_dim(nxt, lo, n)
            got = jnp.take_along_axis(logits, want[:, None], -1)[:, 0]
            return (jnp.max(logits, -1) - got,
                    jnp.argmax(logits, -1).astype(jnp.int32))

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
    multiple of 256 at or above the sample's longest (the reference blocks
    its own forward pass; padding to the whole 10,240 would cost a tenth
    more than the longest request needs)."""
    return min(-(-longest // 256) * 256, max(sizes["n_positions"], longest))


# -- counts -----------------------------------------------------------------

def cache_bytes_per_token(sizes: Dict[str, Any], bytes_per_el: int = 2
                          ) -> Dict[str, int]:
    """What one token leaves in the cache: one kind of leaf,
    ``kv_lora_rank + qk_rope_head_dim`` numbers a layer, as the
    mathematics needs them. The program stores each row in whole 128-lane
    tiles (:func:`cache_bytes_per_token_stored`)."""
    return {"latent": len(sizes["layers"]) * bytes_per_el
            * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])}


def cache_bytes_per_token_stored(sizes: Dict[str, Any],
                                 bytes_per_el: int = 2) -> int:
    row = -(-(sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]) // 128) * 128
    return len(sizes["layers"]) * bytes_per_el * row


def decode_step_bytes(param_bytes: int, sizes: Dict[str, Any], slots: float,
                      kv_bytes_per_el: int = 2, *,
                      experts_hit=None, keys_kept=None,
                      keys_available=None) -> float:
    """Bytes one decode step with ``slots`` LIVE rows must read (the
    signature ``serve.decode_bw_share.live`` calls). Every parameter as
    stored, once, except the embedding table (one row a live row) and the
    routed experts: a grouped matmul skips an expert no pair reached, so
    of the held experts only ``experts_hit`` (distinct held experts
    reached, summed over the expert layers) are read. Of the cache,
    ``keys_kept`` latent rows in every layer: for this model every
    position the live rows attend (``keys_available`` is the same number
    and adds nothing: there are no index keys).

    The counts are the PROGRAM's own, a step on average
    (``serve_summary``). Where one is not given it is what ``slots`` live
    rows would need: each row's ``num_experts_per_tok`` picks uniform over
    the router's width (``held x (1 - (1 - k/E)^slots)`` held experts
    reached a layer), and every row at its full depth."""
    del keys_available
    D = sizes["hidden_size"]
    held = len(sizes["experts_held"])
    moe_layers = sum(1 for mlp in sizes["layers"] if mlp == "sparse")
    one_expert = 3 * D * sizes["moe_intermediate_size"] * 2
    if experts_hit is None:
        miss = 1.0 - sizes["num_experts_per_tok"] / sizes["router_experts"]
        experts_hit = moe_layers * held * (1.0 - miss ** slots)
    if keys_kept is None:
        keys_kept = slots * sizes["n_positions"]
    emb = sizes["vocab_size"] * D * 2
    return (param_bytes - emb + slots * D * 2
            - (moe_layers * held - experts_hit) * one_expert
            + keys_kept * cache_bytes_per_token(sizes,
                                                kv_bytes_per_el)["latent"])


def dense_attend_cost(sizes: Dict[str, Any], positions: float,
                      bytes_per_el: int = 2) -> tuple:
    """(operations, bytes) of ONE layer's dense latent attend over
    ``positions`` cached positions in all (the live rows' depths summed):
    every head's score against ``kv_lora_rank + rope`` numbers and its
    weighted sum of ``kv_lora_rank``, and each position's numbers read
    once. The queries and the result (a few KB a row) are left out."""
    H, r, dr = (sizes["num_attention_heads"], sizes["kv_lora_rank"],
                sizes["qk_rope_head_dim"])
    return (2.0 * H * positions * ((r + dr) + r),
            float(bytes_per_el) * positions * (r + dr))
