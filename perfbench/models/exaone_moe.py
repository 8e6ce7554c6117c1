"""K-EXAONE-236B-A23B (``model_type: exaone_moe``) as the benchmark knows
it: the sizes it reads from a configuration, its weights from ``--seed``,
its plain reference, and the counts its per-layer readers need. It imports
nothing of the program and nothing of the other model files: the reference
below is written from the equations, on its own.

**The equations** (LGAI-EXAONE/K-EXAONE-236B-A23B ``config.json``; the
family's convention is EXAONE 4.0's, arXiv:2507.11407, whose hybrid
attention this model keeps). Norms are RMSNorm with a learned scale, eps
``rms_norm_eps`` 1e-5; no bias anywhere; the embedding and the head are two
matrices. Layer ``i`` has an attention kind ``layer_types[i]``
(``sliding_attention`` or ``full_attention``; the published pattern is
three window layers to one full layer, ``LLLG``) and a feed-forward kind
``mlp_layer_types[i]`` (``dense`` for the first ``first_k_dense_replace``
layers, ``sparse`` after):

    h = x + Attn_i(rmsnorm_1(x))     y = h + FF_i(rmsnorm_2(h))
    after the last layer: rmsnorm, then the head

(pre-norm: ``assumed``, the config has no key for where the norms stand).

- **Attention** (``H = num_attention_heads`` query heads over ``G =
  num_key_value_heads`` key-value heads of ``d = head_dim``): ``q = u W_q``
  [D, H d], ``k = u W_k``, ``v = u W_v`` [D, G d]; ``q <-
  rmsnorm_head(q)``, ``k <- rmsnorm_head(k)``: RMSNorm over the ``d``
  numbers of each head, one learned ``d``-scale for ``q`` and one for
  ``k``, shared by the heads (QK-norm, ``assumed``); on a
  ``sliding_attention`` layer ONLY, ``q`` and ``k`` rotated by position:
  pair ``i`` is ``(x[i], x[i + d/2])``, angle ``position x theta^(-2i/d)``,
  ``theta = rope_parameters.rope_theta`` 1,000,000, every dimension; a
  ``full_attention`` layer applies no position signal at all (``assumed``:
  the family's "RoPE on the window layers only"). Softmax of ``q k^T /
  sqrt(d)`` in float32 over the keys a query may see: ``j <= i`` on a full
  layer, ``i - sliding_window < j <= i`` on a window layer (the window
  counts the query's own position); ``H / G`` query heads a key-value head;
  out ``W_o`` [H d, D]. No sink, no gate, no soft cap.
- **``dense``**: ``W_d (silu(u W_g) * (u W_u))``, ``intermediate_size`` wide.
- **``sparse``**: ``s = sigmoid(u W_r)`` in float32 over ALL published
  experts; PICK the ``num_experts_per_tok`` largest ``s + b`` (``n_group``
  1, ``topk_group`` 1: no group limit; ``b`` the router's float32
  correction bias, which picks and does not weigh; ties to the lower
  index); WEIGH by ``s`` of the picked, normalised to sum 1, times
  ``routed_scaling_factor``. Expert ``e``: ``W_d,e (silu(u W_g,e) * (u
  W_u,e))``, ``moe_intermediate_size`` wide. One shared expert of the same
  shape on every token, added with weight 1 and no gate of its own. Out
  ``routed + shared``. Given a SHARE (``experts_held``) the routed sum runs
  over the held experts only, under the weights of all the picked: what the
  absent experts would add is left out, as the program leaves it out.
- The next-token-prediction layer (``num_nextn_predict_layers``) is not
  served: the published forward pass for the next token does not read it.

**The weights.** Every matrix N(0, ``weight_std``) (0.02) from the run's
seed, rounded to bfloat16; norm scales N(1, 0.02), the two ``d``-scales
of QK-norm N(``qk_norm_scale``, 0.02) (a configuration's own key, 1 where
it has none: the softmax scores' deviation is its square); the router's
correction bias float32, N(0,
``ROUTER_BIAS_STD``): seeded non-zero, so that picking by ``s + b`` and
weighing by ``s`` are told apart. The reference reads the same bfloat16
values and upcasts each matrix where it is used.

**The plain reference.** float32 ``jax.numpy``, ``highest`` precision, one
sequence at a time, no ring, no cache, no batching: attention in blocks of
queries against ALL keys under a triangle or a band, experts one at a time
under a mask over blocks of positions. ``precision`` selects the control:
the same mathematics with every product's operands rounded to that
precision first. ``break_`` selects a control that breaks one MECHANISM of
the reference (``CONTROLS``), which ``correct`` must see.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

INT_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "sliding_window",
            "num_experts", "num_experts_per_tok", "num_hidden_layers",
            "max_position_embeddings")
FLOAT_KEYS = ("rms_norm_eps", "routed_scaling_factor")
ATTENTION_KINDS = ("sliding_attention", "full_attention")
MLP_KINDS = ("dense", "sparse")


def sizes(src: Dict[str, Any]) -> Dict[str, Any]:
    """What this file reads of a configuration (or of its
    ``rehearsal.sizes``): every value hashable, so that the dict can be a
    static argument."""
    out: Dict[str, Any] = {k: int(src[k]) for k in INT_KEYS}
    out.update({k: float(src[k]) for k in FLOAT_KEYS})
    n, lo = out["num_hidden_layers"], int(src.get("first_layer_held", 0))
    kinds = []
    for key, have in (("layer_types", ATTENTION_KINDS),
                      ("mlp_layer_types", MLP_KINDS)):
        mine = tuple(src[key][lo:lo + n])
        if len(mine) != n or set(mine) - set(have):
            raise ValueError(f"{key}[{lo}:{lo}+{n}] = {mine!r}")
        kinds.append(mine)
    for key, want in (("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("n_group", 1), ("topk_group", 1),
                      ("norm_topk_prob", True), ("num_shared_experts", 1),
                      ("scoring_func", "sigmoid"), ("attention_bias", False),
                      ("num_nextn_predict_layers", 0)):
        if src.get(key, want) != want:
            raise ValueError(f"{key} = {src[key]!r}: the equations above "
                             f"are written down for {want!r}")
    rope = dict(src["rope_parameters"])
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_parameters {rope}: rope_type default")
    out["rope_theta"] = float(rope["rope_theta"])
    out["layers"] = tuple(zip(*kinds))
    # the spread of the seeded matrices (``make_params``): a rehearsal's
    # tiny widths take a wider one, so that its projections come out as
    # large as the published widths'
    out["weight_std"] = float(src.get("weight_std", STD))
    # the middle of the two seeded QK-norm scales: the scores' deviation
    # is its square (q and k leave the norm at unit rms a number), and at
    # 1 a softmax over thousands of keys is so flat that the full layer
    # adds next to nothing and ``correct`` cannot see how it attends
    out["qk_norm_scale"] = float(src.get("qk_norm_scale", 1.0))
    out["router_experts"] = int(src.get("num_experts_published",
                                        out["num_experts"]))
    held = tuple(int(e) for e in src.get(
        "experts_held", range(out["num_experts"])))
    if len(held) != out["num_experts"]:
        raise ValueError("experts_held names num_experts experts")
    out["experts_held"] = held
    out["n_positions"] = out["max_position_embeddings"]
    return out


def layer_counts(sizes: Dict[str, Any]) -> Tuple[int, int]:
    """(full-attention layers, window layers) held."""
    return (sum(a == "full_attention" for a, _ in sizes["layers"]),
            sum(a == "sliding_attention" for a, _ in sizes["layers"]))


def expert_layers(sizes: Dict[str, Any]) -> int:
    return sum(ff == "sparse" for _, ff in sizes["layers"])


# -- weights ----------------------------------------------------------------

STD = 0.02
ROUTER_BIAS_STD = 0.05


def leaf_shapes(s: Dict[str, Any]) -> List[Tuple[Tuple[str, ...], tuple,
                                                 Any]]:
    """(path, shape, how it is made) of every leaf of the program's tree,
    bfloat16 but the router's float32 correction bias. How: "matrix" is
    N(0, ``weight_std``), "norm" a norm's scale N(1, 0.02), "qk_norm" a
    QK-norm's N(``qk_norm_scale``, 0.02), "bias" the correction bias N(0,
    ``ROUTER_BIAS_STD``) in float32."""
    D, F, Fe = (s["hidden_size"], s["intermediate_size"],
                s["moe_intermediate_size"])
    H, G, d = (s["num_attention_heads"], s["num_key_value_heads"],
               s["head_dim"])
    E, R = len(s["experts_held"]), s["router_experts"]
    out: list = [(("tok_emb",), (s["vocab_size"], D), "matrix"),
                 (("lm_head", "kernel"), (D, s["vocab_size"]), "matrix"),
                 (("final_norm", "scale"), (D,), "norm")]
    for i, (_, ff) in enumerate(s["layers"]):
        lay = f"layer_{i}"
        m, e = (lay, "mixer"), (lay, "moe")
        out += [((lay, "attn_norm", "scale"), (D,), "norm"),
                ((lay, "mlp_norm", "scale"), (D,), "norm"),
                (m + ("q", "kernel"), (D, H, d), "matrix"),
                (m + ("k", "kernel"), (D, G, d), "matrix"),
                (m + ("v", "kernel"), (D, G, d), "matrix"),
                (m + ("o", "kernel"), (H, d, D), "matrix"),
                (m + ("q_norm", "scale"), (d,), "qk_norm"),
                (m + ("k_norm", "scale"), (d,), "qk_norm")]
        if ff == "dense":
            out += [((lay, "mlp", n, "kernel"), shape, "matrix")
                    for n, shape in (("gate", (D, F)), ("up", (D, F)),
                                     ("down", (F, D)))]
        else:
            out += [(e + ("router", "kernel"), (D, R), "matrix"),
                    (e + ("router_bias",), (R,), "bias"),
                    (e + ("experts_gate", "kernel"), (E, D, Fe), "matrix"),
                    (e + ("experts_up", "kernel"), (E, D, Fe), "matrix"),
                    (e + ("experts_down", "kernel"), (E, Fe, D), "matrix"),
                    (e + ("shared_gate", "kernel"), (D, Fe), "matrix"),
                    (e + ("shared_up", "kernel"), (D, Fe), "matrix"),
                    (e + ("shared_down", "kernel"), (Fe, D), "matrix")]
    return out


# What the reference needs beyond the weights' shapes (the layer lists, the
# experts held, the router's constants) is the ``sizes`` the weights were
# made from: ``make_params`` records them under the tree's shapes, because
# the runners call the reference with the weights and the sequences only.
_BOUND: Dict[Any, Dict[str, Any]] = {}


def _shape_key(params) -> Any:
    return tuple((jax.tree_util.keystr(p), tuple(x.shape)) for p, x in
                 jax.tree_util.tree_leaves_with_path(params))


def make_params(key: jax.Array, sizes: Dict[str, Any],
                stacked: bool = False) -> Dict[str, Any]:
    """The whole tree (trace this under jit), rounded to bfloat16. The
    layers differ in kind, so the reference reads the program's own
    layout: ``stacked`` changes nothing."""
    out: Dict[str, Any] = {}
    for i, (path, shape, how) in enumerate(leaf_shapes(sizes)):
        k = jax.random.fold_in(key, i)
        dtype = jnp.bfloat16
        if how == "bias":
            leaf = ROUTER_BIAS_STD * jax.random.normal(k, shape, jnp.float32)
            dtype = jnp.float32
        elif how in ("norm", "qk_norm"):
            leaf = (sizes["qk_norm_scale"] if how == "qk_norm" else 1.0) \
                + STD * jax.random.normal(k, shape, jnp.float32)
        else:
            assert how == "matrix", how
            leaf = sizes["weight_std"] * jax.random.normal(
                k, shape, jnp.float32)
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf.astype(dtype)
    _BOUND[_shape_key(out)] = dict(sizes)
    return out


def param_count(sizes: Dict[str, Any]) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_shapes(sizes))


def param_bytes(sizes: Dict[str, Any]) -> int:
    """bfloat16 but the correction bias, which is float32."""
    return sum(math.prod(shape) * (4 if how == "bias" else 2)
               for _, shape, how in leaf_shapes(sizes))


# -- the plain reference ----------------------------------------------------

PRECISIONS = ("f32", "bf16", "fp8")
#: Mechanisms a control may break in the REFERENCE (``correct`` compares
#: the served tokens with the sound reference, so what a broken reference
#: would have served stands for a program that broke the same mechanism):
#: the window ignored (window layers attend the whole depth), the rotation
#: applied on the full layers too, the routed part left out.
CONTROLS = ("window_ignored", "rope_everywhere", "no_routed")
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


def rotate(x, positions, theta: float):
    """x [n, heads, d], positions [n]: pair ``i`` is ``(x[i], x[i + d/2])``,
    turned by ``positions x theta^(-2i/d)``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


ATTEND_QUERY_BLOCK = 128


def attention(u, p, s: Dict[str, Any], kind: str, precision: str,
              break_: str = ""):
    """Grouped-query attention of one sequence, the whole score matrix of
    a block of queries against ALL keys under a triangle (a full layer) or
    a band (a window layer): u [L, D] -> [L, D]."""
    L = u.shape[0]
    H, G, d = s["num_attention_heads"], s["num_key_value_heads"], \
        s["head_dim"]
    eps = s["rms_norm_eps"]
    window = kind == "sliding_attention"
    turned = window or break_ == "rope_everywhere"
    banded = window and break_ != "window_ignored"
    at_all = jnp.arange(L)
    k = _rms(_mm("ld,dge->lge", u, p["k"]["kernel"], precision),
             p["k_norm"]["scale"], eps)
    if turned:
        k = rotate(k, at_all, s["rope_theta"])
    v = _mm("ld,dge->lge", u, p["v"]["kernel"], precision)

    def block(lo, n):
        at = lo + jnp.arange(n)
        ub = jax.lax.dynamic_slice_in_dim(u, lo, n)
        q = _rms(_mm("ld,dhe->lhe", ub, p["q"]["kernel"], precision),
                 p["q_norm"]["scale"], eps)
        if turned:
            q = rotate(q, at, s["rope_theta"])
        sc = _mm("nghd,sgd->ngsh", q.reshape(n, G, H // G, d), k,
                 precision) / math.sqrt(d)
        mask = at_all[None, :] <= at[:, None]                    # [n, L]
        if banded:
            mask = mask & (at_all[None, :] > at[:, None]
                           - s["sliding_window"])
        sc = jnp.where(mask[:, None, :, None], sc, -jnp.inf)
        o = _mm("ngsh,sgd->nghd", jax.nn.softmax(sc, axis=2), v, precision)
        return _mm("lhe,hed->ld", o.reshape(n, H, d), p["o"]["kernel"],
                   precision)

    return _in_blocks(block, L, ATTEND_QUERY_BLOCK)


def route(u, w_r, bias, s: Dict[str, Any]):
    """(ids [L, k], weights [L, k]): sigmoid scores over every published
    expert, the ``k`` largest ``s + b`` picked (ties to the lower index),
    weighed by ``s`` alone, normalised, scaled. The router's product is
    float32 whatever the control's precision: the program states so."""
    sc = jax.nn.sigmoid(jnp.einsum("ld,de->le", u, w_r.astype(jnp.float32),
                                   precision=HI))
    _, ids = jax.lax.top_k(sc + bias.astype(jnp.float32)[None, :],
                           s["num_experts_per_tok"])
    w = jnp.take_along_axis(sc, ids, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return ids, w * s["routed_scaling_factor"]


def gated(u, gate, up, down, precision: str):
    return _mm("lf,fd->ld", jax.nn.silu(_mm("ld,df->lf", u, gate, precision))
               * _mm("ld,df->lf", u, up, precision), down, precision)


def expert_layer(u, p, s: Dict[str, Any], precision: str,
                 held=None, shared: bool = True, routed: bool = True):
    """Routed(u) over the experts ``held`` (ids; None: the configuration's
    own share) plus Shared(u) (``shared`` False leaves it out: another
    chip's part of a layer counts the shared expert once): u [L, D] -> [L,
    D]. The routed weights stored are those of ``s['experts_held']`` in
    that order; every expert is a plain product over all the block's
    positions, weighed by 0 where it was not picked."""
    mine = s["experts_held"]
    held = mine if held is None else held
    ids, w = route(u, p["router"]["kernel"], p["router_bias"], s)
    # weight of expert e for each token: 0 where it is not picked
    per = jnp.sum(jnp.where(ids[..., None] == jnp.asarray(held)[None, None],
                            w[..., None], 0.0), axis=1)          # [L, held]
    at = jnp.asarray([mine.index(e) for e in held])

    def one(args):
        j, we = args
        return we[:, None] * gated(
            u, p["experts_gate"]["kernel"][j], p["experts_up"]["kernel"][j],
            p["experts_down"]["kernel"][j], precision)

    y = jnp.zeros_like(u)
    if routed:
        y = jnp.sum(jax.lax.map(one, (at, per.T)), axis=0)
    if shared:
        y = y + gated(u, p["shared_gate"]["kernel"],
                      p["shared_up"]["kernel"], p["shared_down"]["kernel"],
                      precision)
    return y


def forward_features(params, tokens, sizes: Dict[str, Any],
                     precision: str = "f32", break_: str = ""):
    """tokens [L] -> the final-normed features [L, D] of one sequence."""
    s = sizes
    L = tokens.shape[0]
    eps = s["rms_norm_eps"]
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for i, (kind, ff) in enumerate(s["layers"]):
        p = params[f"layer_{i}"]
        x = x + attention(_rms(x, p["attn_norm"]["scale"], eps), p["mixer"],
                          s, kind, precision, break_)
        u = _rms(x, p["mlp_norm"]["scale"], eps)
        if ff == "dense":
            m = p["mlp"]
            y = _in_blocks(
                lambda lo, n: gated(
                    jax.lax.dynamic_slice_in_dim(u, lo, n),
                    m["gate"]["kernel"], m["up"]["kernel"],
                    m["down"]["kernel"], precision), L, 512)
        else:
            y = _in_blocks(
                lambda lo, n: expert_layer(
                    jax.lax.dynamic_slice_in_dim(u, lo, n), p["moe"], s,
                    precision, routed=break_ != "no_routed"), L, 512)
        x = x + y
    return _rms(x, params["final_norm"]["scale"], eps)


def logits_fn(params, tokens, sizes, precision: str = "f32",
              break_: str = ""):
    """tokens [B, L] -> logits [B, L, V] float32 (small sizes: the
    tests; the runners go through the blocked functions below)."""
    return jax.lax.map(
        lambda t: _mm("ld,dv->lv", forward_features(params, t, sizes,
                                                    precision, break_),
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
    top its own argmax. ``precision`` below f32 (``PRECISIONS``) or the
    name of a broken mechanism (``CONTROLS``) gives in ``top`` what that
    reference would have served; score it with :func:`gaps_of`."""
    break_ = precision if precision in CONTROLS else ""
    return _served(params, seqs, _bound_sizes(params),
                   "f32" if break_ else precision, break_)


def gaps_of(params, seqs, chosen):
    """The f32 reference's gap of ``chosen`` [B, L-1] at every position
    given the context ``seqs[:, :t+1]``."""
    return _gaps_of(params, seqs, chosen, _bound_sizes(params))


@functools.partial(jax.jit, static_argnames=("frozen", "precision", "break_"))
def _served(params, seqs, frozen, precision, break_=""):
    sizes = dict(frozen)

    def one(seq):
        feats = forward_features(params, seq, sizes, precision, break_)
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
    its own forward pass: a block of 128 queries against 14,336 keys is 64
    heads x 7 MB of float32 scores; what lies past a request's end is
    causal from it and only costs time)."""
    return min(-(-longest // 256) * 256, max(sizes["n_positions"], longest))


# -- counts -----------------------------------------------------------------

def kv_bytes_per_position(sizes: Dict[str, Any], bytes_per_el: int = 2
                          ) -> int:
    """K and V of ONE attention layer at one position."""
    return 2 * sizes["num_key_value_heads"] * sizes["head_dim"] \
        * bytes_per_el


def cache_bytes_per_slot(sizes: Dict[str, Any]) -> Dict[str, int]:
    """What a slot holds, by the program's leaf names: ``kv`` the full
    layers' rows to ``n_positions``, ``kv_ring`` the window layers' rings
    of ``sliding_window`` rows."""
    full, ring = layer_counts(sizes)
    row = kv_bytes_per_position(sizes)
    return {"kv": full * sizes["n_positions"] * row,
            "kv_ring": ring * sizes["sliding_window"] * row}


def expert_bytes(sizes: Dict[str, Any]) -> int:
    """bfloat16 bytes of ONE routed expert: gate, up and down."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"] * 2


def expert_step_cost(sizes: Dict[str, Any], held_pairs: float,
                     experts_hit: float) -> tuple:
    """(operations, bytes from HBM) of the held experts' grouped matmuls
    of ONE decode step, all routed layers (the signature
    ``moe_gmm_roofline`` calls): ``held_pairs`` (token, expert) pairs
    landed on ``experts_hit`` held experts (both summed over the layers).
    A pair is three products (``3 x 2 D F`` operations); an expert a pair
    reached is read whole, once; a pair's row comes in bfloat16 and its
    result goes out float32. The ``[pairs, F]`` rows between the products
    are not priced: a kernel could keep them on the chip."""
    D, F = sizes["hidden_size"], sizes["moe_intermediate_size"]
    return (6.0 * D * F * held_pairs,
            experts_hit * expert_bytes(sizes) + held_pairs * D * (2 + 4))


def gqa_attend_cost(sizes: Dict[str, Any], positions: float) -> tuple:
    """(operations, bytes from HBM) of ONE full layer's decode attend over
    ``positions`` cached positions in all (the live rows' depths summed):
    every query head's score and weighted sum (``2 x 2 d`` operations a
    head a position); K and V of a position read once for all the heads
    that share them. The queries and outputs (a few KB a row) are left
    out."""
    H, d = sizes["num_attention_heads"], sizes["head_dim"]
    return (4.0 * H * d * positions,
            positions * kv_bytes_per_position(sizes))


def decode_step_bytes(param_bytes: int, sizes: Dict[str, Any], slots: float,
                      kv_bytes_per_el: int = 2, *, keys_kept=None,
                      keys_available=None, experts_hit=None) -> float:
    """Bytes one decode step with ``slots`` LIVE rows must read and write
    (the signature the ``serve.decode_bw_share`` readers call). Every
    parameter as stored, once, except: of the embedding only the live
    rows' own rows (the head is a matrix of its own), and of the routed
    experts only the ``experts_hit`` a step reached (summed over the
    layers; None: all held). K and V of the ``keys_kept`` positions the
    live rows attend, over all attention layers (None: ``slots`` rows at
    full depth on the full layers and a whole window on the rings)."""
    del keys_available
    D = sizes["hidden_size"]
    full, ring = layer_counts(sizes)
    held = len(sizes["experts_held"]) * expert_layers(sizes)
    if experts_hit is None:
        experts_hit = held
    if keys_kept is None:
        keys_kept = slots * (full * sizes["n_positions"]
                             + ring * sizes["sliding_window"])
    return (param_bytes - (held - experts_hit) * expert_bytes(sizes)
            - (sizes["vocab_size"] - slots) * D * 2
            + keys_kept * kv_bytes_per_position(sizes, kv_bytes_per_el))
