"""MiniCPM-SALA (``model_type: minicpm_sala``) as the benchmark knows it:
the sizes it reads from a configuration, its weights from ``--seed``, its
plain reference, and the counts its per-layer readers need. It imports
nothing of the program and nothing of the other model files: the reference
below is written from the equations, on its own.

**The architecture** (openbmb/MiniCPM-SALA ``config.json``). Pre-norm
residual blocks under MiniCPM's muP scalings, RMSNorm, no biases, an untied
head, a dense SwiGLU MLP in every layer, and one of two mixers a layer
(``mixer_types``):

- Model. ``x0 = scale_emb E[tok]``; ``h = x + r Mixer(rms(x))``, ``x' = h
  + r MLP(rms(h))`` with ``r = scale_depth / sqrt(PUBLISHED layers)``
  whatever the depth held; logits ``= W_head (rms(x_L) / (hidden_size /
  dim_model_base))``.
- ``lightning-attn``. ``q, k, v = W u`` as heads x 128; RMSNorm over each
  head of ``q`` and ``k`` (one learned 128-scale each); RoPE on both, in
  halves (``rotate_half``); a FIXED decay a head, ``lambda_h = exp(-s_h)``,
  ``s_h = 2^(-8 h / H)``, ``h = 1..H``; ``S_t = lambda_h S_{t-1} + k_t^T
  v_t`` (float32, ``S_{-1} = 0``), ``o_t = 128^-1/2 q_t S_t``, no softmax;
  RMSNorm over each head's ``o_t`` (a learned scale a channel), times
  ``sigmoid(W_g u)``, then ``W_o``. Computed HERE token by token (a
  ``lax.scan`` over positions), never in chunks.
- ``minicpm4`` (InfLLM-v2). 32 query heads over 2 key-value heads, no
  rotation, the same per-head RMSNorm of ``q`` and ``k``. A query whose
  context (itself included) is at most ``dense_len`` long attends all of
  it. A longer one chooses blocks, a key-value group at a time: pooled
  keys ``Kc_j = mean(K[stride j : stride j + kernel_size])`` of every
  COMPLETE window; ``p_h = softmax_j(q_h . Kc_j / sqrt(128))`` over the
  windows that end at or before the query, summed over the group's 16
  heads; a block of ``block_size`` positions scores the maximum over the
  windows that overlap it; block 0 (``init_blocks``) and the query's own
  block with the ``window_size / block_size - 1`` before it are forced;
  the ``topk`` best blocks are kept (forced ones first, ties to the lower
  block; a block no window reached is never kept unless forced); causal
  softmax attention at ``128^-1/2`` over the kept blocks' positions.
  Then ``sigmoid(W_g u)`` on the output and ``W_o``.

**Departures from the public code that this builder knows of**, all under
``assumed`` in the configuration: the catalog's ``config`` has no
``sparse_config``, so MiniCPM4's published one is taken; the decay slopes
are Lightning Attention's ALiBi-like ones, the same in every layer; the
forced local window is counted in whole blocks ending at the query's own
(32 blocks: between 1,985 and 2,048 positions).

**Weights.** Made on the device in one jitted call from the key, in the
program's tree (bfloat16 leaves). The reference reads the same bfloat16
values and upcasts each matrix where it is used, so no float32 copy of the
model ever exists.

**The plain reference.** float32 ``jax.numpy``, ``highest`` precision, one
sequence at a time; the sparse layers in blocks of queries against all keys
(nothing ``[L, L]`` exists), MLPs and head in blocks of positions, so that
25,600 positions fit beside the weights. ``precision`` selects the
control: the same mathematics with every product's operands rounded to
that precision first.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

INT_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "lightning_nh", "lightning_nkv", "lightning_head_dim",
            "num_hidden_layers", "max_position_embeddings",
            "dim_model_base")
SPARSE_KEYS = ("kernel_size", "kernel_stride", "init_blocks", "block_size",
               "window_size", "topk", "dense_len")
MIXERS = ("lightning-attn", "minicpm4")


def sizes(src: Dict[str, Any]) -> Dict[str, Any]:
    """What this file reads of a configuration (or of its
    ``rehearsal.sizes``): every value hashable, so that the dict can be a
    static argument."""
    out: Dict[str, Any] = {k: int(src[k]) for k in INT_KEYS}
    lo = int(src.get("first_layer_held", 0))
    kinds = list(src["mixer_types"])[lo:lo + out["num_hidden_layers"]]
    if len(kinds) != out["num_hidden_layers"] or set(kinds) - set(MIXERS):
        raise ValueError(f"mixer_types[{lo}:{lo}+{out['num_hidden_layers']}]"
                         f" = {kinds}")
    if out["lightning_nkv"] != out["lightning_nh"]:
        raise ValueError("a lightning layer has one key and value a head")
    if src.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)":
        raise ValueError(f"lightning_scale {src['lightning_scale']!r}")
    for flag in ("qk_norm", "lightning_use_rope", "use_output_gate",
                 "use_output_norm", "attn_use_output_gate"):
        if not src.get(flag, True):
            raise ValueError(f"{flag} false is not written down here")
    if src.get("attn_use_rope", False):
        raise ValueError("the sparse layers take no rotation")
    out["mixers"] = tuple(kinds)
    out["published_layers"] = int(src.get("num_hidden_layers_published",
                                          out["num_hidden_layers"]))
    for k in ("rms_norm_eps", "rope_theta", "scale_emb", "scale_depth"):
        out[k] = float(src[k])
    out["sparse"] = tuple(int(src["sparse_config"][k]) for k in SPARSE_KEYS)
    out["n_positions"] = out["max_position_embeddings"]
    return out


def sparse_of(s: Dict[str, Any]) -> Dict[str, int]:
    return dict(zip(SPARSE_KEYS, s["sparse"]))


# -- weights ----------------------------------------------------------------

STD = 0.02


def leaf_shapes(s: Dict[str, Any]) -> List[Tuple[Tuple[str, ...], tuple,
                                                 float, Any]]:
    """(path, shape, centre, dtype) of every leaf of the program's
    tree."""
    bf = jnp.bfloat16
    D, F = s["hidden_size"], s["intermediate_size"]
    H, G, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                s["head_dim"])
    Hl, dl = s["lightning_nh"], s["lightning_head_dim"]
    out = [(("tok_emb",), (s["vocab_size"], D), 0.0, bf),
           (("final_norm", "scale"), (D,), 1.0, bf),
           (("lm_head", "kernel"), (D, s["vocab_size"]), 0.0, bf)]
    for i, kind in enumerate(s["mixers"]):
        lay = f"layer_{i}"
        m = (lay, "mixer")
        out += [((lay, "attn_norm", "scale"), (D,), 1.0, bf),
                ((lay, "mlp_norm", "scale"), (D,), 1.0, bf),
                ((lay, "mlp", "gate", "kernel"), (D, F), 0.0, bf),
                ((lay, "mlp", "up", "kernel"), (D, F), 0.0, bf),
                ((lay, "mlp", "down", "kernel"), (F, D), 0.0, bf)]
        if kind == "lightning-attn":
            out += [(m + (n, "kernel"), (D, Hl, dl), 0.0, bf)
                    for n in ("q", "k", "v", "g")]
            out += [(m + ("o", "kernel"), (Hl, dl, D), 0.0, bf),
                    (m + ("q_norm", "scale"), (dl,), 1.0, bf),
                    (m + ("k_norm", "scale"), (dl,), 1.0, bf),
                    (m + ("o_norm", "scale"), (Hl * dl,), 1.0, bf)]
        else:
            out += [(m + ("q", "kernel"), (D, H, dh), 0.0, bf),
                    (m + ("k", "kernel"), (D, G, dh), 0.0, bf),
                    (m + ("v", "kernel"), (D, G, dh), 0.0, bf),
                    (m + ("g", "kernel"), (D, H, dh), 0.0, bf),
                    (m + ("o", "kernel"), (H, dh, D), 0.0, bf),
                    (m + ("q_norm", "scale"), (dh,), 1.0, bf),
                    (m + ("k_norm", "scale"), (dh,), 1.0, bf)]
    return out


# What the reference needs beyond the weights' shapes (the mixer list, the
# selection's numbers, the muP scalings) is the ``sizes`` the weights were
# made from: ``make_params`` records them under the tree's shapes, because
# the runners call the reference with the weights and the sequences only.
_BOUND: Dict[Any, Dict[str, Any]] = {}


def _shape_key(params) -> Any:
    return tuple((jax.tree_util.keystr(p), tuple(x.shape)) for p, x in
                 jax.tree_util.tree_leaves_with_path(params))


def make_params(key: jax.Array, sizes: Dict[str, Any],
                stacked: bool = False) -> Dict[str, Any]:
    """The whole tree (trace this under jit), every leaf N(centre, 0.02)
    rounded to bfloat16. The layers differ in kind, so the reference reads
    the program's own layout: ``stacked`` changes nothing."""
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


def residual_scale(s: Dict[str, Any]) -> float:
    return s["scale_depth"] / math.sqrt(s["published_layers"])


def decay_slopes(n_heads: int):
    """``s_h = 2^(-8 h / H)``, ``h = 1..H``: head h forgets at
    ``exp(-s_h)`` a token."""
    return jnp.asarray([2.0 ** (-8.0 * h / n_heads)
                        for h in range(1, n_heads + 1)], jnp.float32)


def _rope_halves(x, pos, theta: float):
    """RoPE as ``x cos + rotate_half(x) sin``: pair i is ``(x[i], x[i +
    d/2])``. x [L, H, d]; pos [L]."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * freq          # [L,1,d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


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


def _swiglu(x, p, precision):
    h = jax.nn.silu(_mm("ld,df->lf", x, p["gate"]["kernel"], precision)) \
        * _mm("ld,df->lf", x, p["up"]["kernel"], precision)
    return _mm("lf,fd->ld", h, p["down"]["kernel"], precision)


def lightning_mixer(u, pos, p, s: Dict[str, Any], precision: str):
    """The decayed outer-product recurrence of one sequence, token by
    token: u [L, D] -> [L, D]."""
    eps, dl = s["rms_norm_eps"], s["lightning_head_dim"]
    q = _rms(_mm("ld,dhe->lhe", u, p["q"]["kernel"], precision),
             p["q_norm"]["scale"], eps)
    k = _rms(_mm("ld,dhe->lhe", u, p["k"]["kernel"], precision),
             p["k_norm"]["scale"], eps)
    v = _mm("ld,dhe->lhe", u, p["v"]["kernel"], precision)
    q = _rounded(_rope_halves(q, pos, s["rope_theta"]), precision)
    k = _rounded(_rope_halves(k, pos, s["rope_theta"]), precision)
    v = _rounded(v, precision)
    lam = jnp.exp(-decay_slopes(s["lightning_nh"]))[:, None, None]

    def token(S, qkv):
        qt, kt, vt = qkv                                         # [H, d]
        S = lam * S + kt[:, :, None] * vt[:, None, :]
        return S, jnp.einsum("hd,hde->he", qt, S, precision=HI)

    _, o = jax.lax.scan(
        token, jnp.zeros((s["lightning_nh"], dl, dl), jnp.float32),
        (q, k, v))
    o = _rms(o * dl ** -0.5, p["o_norm"]["scale"].reshape(
        s["lightning_nh"], dl), eps)
    o = o * jax.nn.sigmoid(_mm("ld,dhe->lhe", u, p["g"]["kernel"],
                               precision))
    return _mm("lhe,hed->ld", o, p["o"]["kernel"], precision)


def windows_of_block(sp: Dict[str, int]) -> Tuple[int, int, int]:
    """A block m of ``block_size`` positions is overlapped by the pooled
    windows ``per * m + first .. per * m + first + count - 1`` (those that
    exist): (per, first, count). MiniCPM4's numbers give (4, -1, 5)."""
    bs, ks, st = sp["block_size"], sp["kernel_size"], sp["kernel_stride"]
    if bs % st or ks % st:
        raise ValueError("block_size and kernel_size are multiples of "
                         "kernel_stride")
    first = -((ks - 1) // st)
    return bs // st, first, (bs - 1) // st - first + 1


def pooled_keys(k, sp: Dict[str, int]):
    """k [L, G, d] -> the means of the complete windows [n_w, G, d]."""
    L = k.shape[0]
    n_w = max((L - sp["kernel_size"]) // sp["kernel_stride"] + 1, 0)
    at = sp["kernel_stride"] * jnp.arange(n_w)[:, None] \
        + jnp.arange(sp["kernel_size"])[None, :]
    return jnp.mean(k[at], axis=1)


def kept_blocks(q, kc, at, sp: Dict[str, int], n_blocks: int,
                precision: str):
    """Which blocks each query keeps, a key-value group at a time. q [n,
    H, d] the queries at positions ``at`` [n]; kc [n_w, G, d] -> bool [n,
    G, n_blocks]. A query whose context is at most ``dense_len`` keeps
    every causal block."""
    n, H, d = q.shape
    n_w, G, _ = kc.shape
    bs = sp["block_size"]
    mine = at // bs                                   # the query's block
    causal = jnp.arange(n_blocks)[None, :] <= mine[:, None]     # [n, nb]
    if n_w == 0:
        return jnp.broadcast_to(causal[:, None, :], (n, G, n_blocks))
    # windows that END at or before the query
    have = (at - (sp["kernel_size"] - 1)) // sp["kernel_stride"] + 1
    seen = jnp.arange(n_w)[None, :] < have[:, None]             # [n, n_w]
    sc = _mm("nghd,jgd->nghj", q.reshape(n, G, H // G, d), kc,
             precision) * d ** -0.5
    sc = jnp.where(seen[:, None, None, :], sc, -jnp.inf)
    top = jnp.max(sc, -1, keepdims=True)
    e = jnp.where(seen[:, None, None, :],
                  jnp.exp(sc - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    prob = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
    grp = jnp.sum(prob, axis=2)                                 # [n,G,n_w]
    per, first, count = windows_of_block(sp)
    w = per * jnp.arange(n_blocks)[:, None] + first \
        + jnp.arange(count)[None, :]                            # [nb, 5]
    inside = (w >= 0) & (w < n_w)
    wc = jnp.clip(w, 0, n_w - 1)
    reach = inside[None, :, :] & seen[:, wc]                    # [n,nb,5]
    score = jnp.max(jnp.where(reach[:, None], grp[:, :, wc], -jnp.inf),
                    axis=-1)                                    # [n,G,nb]
    forced = (jnp.arange(n_blocks)[None, :] < sp["init_blocks"]) | (
        jnp.arange(n_blocks)[None, :]
        > mine[:, None] - sp["window_size"] // bs)
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    score = jnp.where(causal[:, None, :], score, -jnp.inf)
    vals, idx = jax.lax.top_k(score, min(sp["topk"], n_blocks))
    picked = jnp.any((idx[..., None] == jnp.arange(n_blocks))
                     & (vals[..., None] > -jnp.inf), axis=-2)
    dense = (at + 1 <= sp["dense_len"])[:, None, None]
    return jnp.where(dense, causal[:, None, :], picked)


SPARSE_QUERY_BLOCK = 64


def sparse_mixer(u, p, s: Dict[str, Any], precision: str):
    """InfLLM-v2 attention of one sequence: u [L, D] -> [L, D]."""
    sp = sparse_of(s)
    eps, d = s["rms_norm_eps"], s["head_dim"]
    H, G = s["num_attention_heads"], s["num_key_value_heads"]
    L = u.shape[0]
    bs = sp["block_size"]
    n_blocks = -(-L // bs)
    k = _rms(_mm("ld,dge->lge", u, p["k"]["kernel"], precision),
             p["k_norm"]["scale"], eps)
    v = _mm("ld,dge->lge", u, p["v"]["kernel"], precision)
    kc = pooled_keys(k, sp)
    key_block = jnp.arange(L) // bs

    def block(lo, n):
        at = lo + jnp.arange(n)
        ub = jax.lax.dynamic_slice_in_dim(u, lo, n)
        q = _rms(_mm("ld,dhe->lhe", ub, p["q"]["kernel"], precision),
                 p["q_norm"]["scale"], eps)
        keep = kept_blocks(q, kc, at, sp, n_blocks, precision)  # [n,G,nb]
        mask = keep[:, :, key_block] \
            & (jnp.arange(L)[None, None, :] <= at[:, None, None])
        sc = _mm("nghd,sgd->ngsh", q.reshape(n, G, H // G, d), k,
                 precision) * d ** -0.5
        sc = jnp.where(mask[..., None], sc, -jnp.inf)
        o = _mm("ngsh,sgd->nghd", jax.nn.softmax(sc, axis=2), v, precision)
        o = o.reshape(n, H, d) * jax.nn.sigmoid(
            _mm("ld,dhe->lhe", ub, p["g"]["kernel"], precision))
        return _mm("lhe,hed->ld", o, p["o"]["kernel"], precision)

    return _in_blocks(block, L, SPARSE_QUERY_BLOCK)


def forward_features(params, tokens, sizes: Dict[str, Any],
                     precision: str = "f32"):
    """tokens [L] -> the final-normed, muP-scaled features [L, D] of one
    sequence."""
    s = sizes
    L = tokens.shape[0]
    pos = jnp.arange(L)
    r, eps = residual_scale(s), s["rms_norm_eps"]
    x = s["scale_emb"] * params["tok_emb"][tokens].astype(jnp.float32)
    for i, kind in enumerate(s["mixers"]):
        p = params[f"layer_{i}"]
        u = _rms(x, p["attn_norm"]["scale"], eps)
        if kind == "lightning-attn":
            y = lightning_mixer(u, pos, p["mixer"], s, precision)
        else:
            y = sparse_mixer(u, p["mixer"], s, precision)
        x = x + r * y
        u = _rms(x, p["mlp_norm"]["scale"], eps)
        x = x + r * _in_blocks(
            lambda lo, n: _swiglu(jax.lax.dynamic_slice_in_dim(u, lo, n),
                                  p["mlp"], precision), L, 1024)
    return _rms(x, params["final_norm"]["scale"], eps) \
        / (s["hidden_size"] / s["dim_model_base"])


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
    its own forward pass; what lies past a request's end is causal-masked
    from it and only costs time)."""
    return min(-(-longest // 256) * 256, max(sizes["n_positions"], longest))


# -- counts -----------------------------------------------------------------

def layer_counts(sizes: Dict[str, Any]) -> Tuple[int, int]:
    """(lightning layers, sparse layers) held."""
    n_lin = sum(1 for m in sizes["mixers"] if m == "lightning-attn")
    return n_lin, len(sizes["mixers"]) - n_lin


def state_bytes_per_slot(sizes: Dict[str, Any]) -> int:
    """The float32 recurrent state a slot holds, whatever its depth."""
    return layer_counts(sizes)[0] * sizes["lightning_nh"] \
        * sizes["lightning_head_dim"] ** 2 * 4


def cache_bytes_per_token(sizes: Dict[str, Any], bytes_per_el: int = 2
                          ) -> Dict[str, float]:
    """What one token leaves in the position-indexed leaves: K and V of
    the sparse layers' key-value heads, and its share of a pooled key
    (one a ``kernel_stride`` positions)."""
    _, n_sp = layer_counts(sizes)
    row = sizes["num_key_value_heads"] * sizes["head_dim"] * bytes_per_el
    return {"kv": n_sp * 2 * row,
            "pooled_keys": n_sp * row / sparse_of(sizes)["kernel_stride"]}


def state_step_cost(sizes: Dict[str, Any], rows: float) -> tuple:
    """(operations, bytes from HBM) of ONE lightning layer's decode step
    over ``rows`` live rows: each row's state decayed and added to (2 a
    number), the outer product and the read-out (2 each), the state read
    once and written once. q, k, v and o (a few KB a row) are left
    out."""
    n = sizes["lightning_nh"] * sizes["lightning_head_dim"] ** 2
    return 6.0 * n * rows, 8.0 * n * rows


#: Tokens a chunk of the chunked form whose cost is counted below (the
#: program's: a longer chunk needs more operations, a shorter one fewer).
SCAN_CHUNK = 256


def chunk_scan_cost(sizes: Dict[str, Any], length: int, chunk: int
                    ) -> tuple:
    """(operations, bytes from HBM) of ONE lightning layer's chunked scan
    over ``length`` positions in chunks of ``chunk``: a chunk's ``Q K^T``
    and its product with ``V`` (``2 C d`` each a token a head), the read
    of the carried state and its update (``2 d d`` each); q, k, v read
    once in bfloat16, o written once in float32 (the norm that follows
    takes it unrounded), the final state written in float32."""
    H, d = sizes["lightning_nh"], sizes["lightning_head_dim"]
    ops = 2.0 * length * H * (2 * chunk * d + 2 * d * d)
    return ops, length * H * d * (3 * 2 + 4) + H * d * d * 4


def block_scores_cost(sizes: Dict[str, Any], windows: float) -> tuple:
    """(operations, bytes from HBM) of ONE sparse layer's pooled-key scores
    of a decode step: every query head against ``windows`` pooled keys in
    all (the live rows' complete windows, summed over rows; both groups
    counted by the factor of heads), each pooled key read once."""
    H, G, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
               sizes["head_dim"])
    return 2.0 * H * windows * d, 2.0 * G * windows * d


def block_attend_cost(sizes: Dict[str, Any], positions: float) -> tuple:
    """(operations, bytes from HBM) of ONE sparse layer's attend of a
    decode step over ``positions`` kept positions in all (a key-value
    group's, summed over the live rows): every head's score and weighted
    sum, and each position's K and V of both groups read once."""
    H, G, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
               sizes["head_dim"])
    return 4.0 * H * positions * d, 2.0 * 2 * G * positions * d


def decode_step_bytes(param_bytes: int, sizes: Dict[str, Any], slots: float,
                      kv_bytes_per_el: int = 2, *, keys_kept=None,
                      keys_available=None, experts_hit=None) -> float:
    """Bytes one decode step with ``slots`` LIVE rows must read and write
    (the signature ``serve.decode_bw_share.live`` calls). Every parameter
    as stored, once, except the embedding table (one row a live row); each
    live row's recurrent states read and written; in every sparse layer
    the K and V of the ``keys_kept`` positions the live rows attend (a
    group's count; both groups read) and the pooled keys to each row's
    depth (``keys_available`` positions, one pooled key a
    ``kernel_stride``).

    The counts are the PROGRAM's own, a step on average
    (``serve_summary``). Where one is not given it is what ``slots`` live
    rows at full depth would need: ``topk`` whole blocks kept a row."""
    del experts_hit
    sp = sparse_of(sizes)
    if keys_available is None:
        keys_available = slots * sizes["n_positions"]
    if keys_kept is None:
        keys_kept = slots * min(sp["topk"] * sp["block_size"],
                                sizes["n_positions"])
    emb = sizes["vocab_size"] * sizes["hidden_size"] * 2
    per_token = cache_bytes_per_token(sizes, kv_bytes_per_el)
    return (param_bytes - emb + slots * sizes["hidden_size"] * 2
            + 2.0 * slots * state_bytes_per_slot(sizes)
            + keys_kept * per_token["kv"]
            + keys_available * per_token["pooled_keys"])
