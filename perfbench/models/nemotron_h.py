"""NVIDIA-Nemotron-3-Super-120B-A12B (``model_type: nemotron_h``) as the
benchmark knows it: the sizes it reads from a configuration, its weights
from ``--seed``, its plain reference, and the counts its per-layer readers
need. It imports nothing of the program and nothing of the other model
files: the reference below is written from the equations, on its own.

**The equations** (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
``config.json``). Norms are RMSNorm with a learned scale, eps
``layer_norm_epsilon``; no bias but the convolution's; the embedding and
the head are two matrices. ``hybrid_override_pattern`` spells a layer's
kind, ``M``, ``*`` or ``E``, and a layer is ONE part:

    x <- x + mixer_kind(rmsnorm(x))          after the last: rmsnorm, head

- ``M`` (Mamba-2; ``H`` heads of ``P`` channels, ``d_inner = H P``, a
  state of ``N`` numbers a channel, ``G = n_groups`` groups of ``B`` and
  ``C``, a convolution of ``K`` taps with bias). ``[z | xBC | dt] = u
  W_in`` (widths ``H P | H P + 2 G N | H``); ``xBC_t = silu(b + sum_{j<K}
  w_j xBC_{t-K+1+j})`` depthwise and causal, zeros before the sequence;
  split ``x_t [H, P]``, ``B_t [G, N]``, ``C_t [G, N]``; ``dt_t =
  softplus(dt_t + dt_bias)`` a head, not clamped; ``A_h = -exp(A_log_h)``;
  for head ``h`` in group ``g = h // (H / G)``, in float32 from ``S_{-1} =
  0``: ``S_t = exp(dt_t A_h) S_{t-1} + (dt_t x_t) B_{g,t}^T``, ``y_t = S_t
  C_{g,t} + D_h x_t``; ``y <- rmsnorm_group(y silu(z))``, the norm over
  each of the ``G`` groups of ``H P / G`` channels under one learned ``H
  P``-scale; out ``y W_out``. Computed HERE token by token (a ``lax.scan``
  over positions), never in chunks.
- ``*`` (attention). ``q = u W_q`` (``num_attention_heads`` x
  ``head_dim``), ``k, v = u W_k, u W_v`` (``num_key_value_heads`` x
  ``head_dim``), NO rotation and no position signal at all, causal softmax
  of ``q k^T / sqrt(head_dim)``, a key-value head shared by
  ``num_attention_heads / num_key_value_heads`` query heads, out ``W_o``.
- ``E`` (LatentMoE). ``s = sigmoid(u W_r)`` in float32 over ALL published
  experts; PICK the ``num_experts_per_tok`` largest ``s + b`` (``b`` the
  correction bias; ``n_group`` 1: no group limit; ties to the lower
  index); WEIGH by ``s`` of the picked, normalised to sum 1, times
  ``routed_scaling_factor``. ``l = u W_down_latent``; expert e is the
  UNGATED ``f_e(l) = relu(l W_up^e)^2 W_down^e`` in the latent; ``routed =
  (sum over picked e of w_e f_e(l)) W_up_latent``; the shared expert reads
  ``u`` itself: ``relu(u W_su)^2 W_sd``; out ``routed + shared``. No norm
  inside the latent. The reference is given THE SAME SHARE as the program
  (the ids of the experts held; the router's width is the published one):
  it computes every held expert as a plain matmul under a mask and leaves
  out what absent experts would add, as the chip does.
- The next-token-prediction layers (``num_nextn_predict_layers``) are not
  part of the forward pass that serves a token and are not here.

**Departures and readings this builder knows of**, all under ``assumed``
in the configuration: no position signal in the attention layers
(``rope_theta`` and ``partial_rotary_factor`` unused); no norm inside the
latent; ``intermediate_size`` (a dense MLP's width) unused, the pattern has
no ``-`` layer; the convolution's weight held ``[taps, channels]``;
``A_log``, ``dt_bias`` and ``D`` initialised as Mamba-2 publishes (from
``time_step_min``, ``time_step_max``, ``time_step_floor``); a float32
residual stream (``residual_in_fp32`` false in the source); the held share
of the experts and of the vocabulary; 11 of the 88 layers.

**Weights.** Made on the device in one jitted call from the key, in the
program's tree (bfloat16 leaves, the router's correction bias float32 and
zero). The reference reads the same bfloat16 values and upcasts each matrix
where it is used, so no float32 copy of the model ever exists.

**The plain reference.** float32 ``jax.numpy``, ``highest`` precision, one
sequence at a time; attention in blocks of queries against all keys,
experts one at a time over blocks of positions. ``precision`` selects the
control: the same mathematics with every product's operands rounded to that
precision first.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

INT_KEYS = ("vocab_size", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "mamba_num_heads",
            "mamba_head_dim", "ssm_state_size", "conv_kernel", "n_groups",
            "expand", "moe_intermediate_size", "moe_latent_size",
            "moe_shared_expert_intermediate_size", "n_routed_experts",
            "num_experts_per_tok", "num_hidden_layers",
            "max_position_embeddings")
FLOAT_KEYS = ("layer_norm_epsilon", "routed_scaling_factor",
              "time_step_min", "time_step_max", "time_step_floor")
KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def sizes(src: Dict[str, Any]) -> Dict[str, Any]:
    """What this file reads of a configuration (or of its
    ``rehearsal.sizes``): every value hashable, so that the dict can be a
    static argument."""
    out: Dict[str, Any] = {k: int(src[k]) for k in INT_KEYS}
    out.update({k: float(src[k]) for k in FLOAT_KEYS})
    lo = int(src.get("first_layer_held", 0))
    letters = src["hybrid_override_pattern"][lo:lo + out["num_hidden_layers"]]
    if len(letters) != out["num_hidden_layers"] or set(letters) - set(KINDS):
        raise ValueError(f"hybrid_override_pattern[{lo}:{lo}+"
                         f"{out['num_hidden_layers']}] = {letters!r}")
    for key, want in (("tie_word_embeddings", False),
                      ("mlp_hidden_act", "relu2"), ("n_group", 1),
                      ("topk_group", 1), ("norm_topk_prob", True),
                      ("n_shared_experts", 1), ("use_conv_bias", True),
                      ("num_nextn_predict_layers", 0)):
        if src.get(key, want) != want:
            raise ValueError(f"{key} = {src[key]!r}: the equations above "
                             f"are written down for {want!r}")
    if out["mamba_num_heads"] * out["mamba_head_dim"] != \
            out["expand"] * out["hidden_size"]:
        raise ValueError("mamba_num_heads x mamba_head_dim is not expand x "
                         "hidden_size")
    if out["mamba_num_heads"] % out["n_groups"]:
        raise ValueError("n_groups does not divide mamba_num_heads")
    out["layers"] = tuple(KINDS[c] for c in letters)
    # the spread of the seeded matrices (``make_params``): a rehearsal's
    # tiny widths take a wider one, so that its projections come out as
    # large as the published widths' (sqrt(4096) x 0.02)
    out["weight_std"] = float(src.get("weight_std", STD))
    out["router_experts"] = int(src.get("n_routed_experts_published",
                                        out["n_routed_experts"]))
    held = tuple(int(e) for e in src.get(
        "experts_held", range(out["n_routed_experts"])))
    if len(held) != out["n_routed_experts"]:
        raise ValueError("experts_held names n_routed_experts experts")
    out["experts_held"] = held
    out["n_positions"] = out["max_position_embeddings"]
    return out


def inner_width(s: Dict[str, Any]) -> int:
    return s["mamba_num_heads"] * s["mamba_head_dim"]


def conv_width(s: Dict[str, Any]) -> int:
    return inner_width(s) + 2 * s["n_groups"] * s["ssm_state_size"]


# -- weights ----------------------------------------------------------------

STD = 0.02


def leaf_shapes(s: Dict[str, Any]) -> List[Tuple[Tuple[str, ...], tuple,
                                                 Any]]:
    """(path, shape, how it is made) of every leaf of the program's tree,
    bfloat16 but the router's float32 correction bias. How: "matrix" is
    N(0, ``weight_std``) (0.02 unless the sizes say otherwise), "norm" a
    norm's scale N(1, 0.02), "zeros" the correction bias, and Mamba-2's
    published initialisation for the rest: "A_log" is ``log U(1, 16)``;
    "dt_bias" the inverse softplus of ``dt = exp(U(log time_step_min, log
    time_step_max))`` clamped below at ``time_step_floor``; "ones" is 1
    (``D``); "conv" is ``U(-1/2, 1/2)`` (a depthwise convolution of 4 taps
    under PyTorch's default: with N(0, 0.02) taps ``S C`` is 4e-4 of the
    skip ``D x`` and ``correct`` would not see the state at all,
    perfbench/models/granitemoehybrid.py, PR 41)."""
    D, F, Fs, Dl = (s["hidden_size"], s["moe_intermediate_size"],
                    s["moe_shared_expert_intermediate_size"],
                    s["moe_latent_size"])
    H, Gk, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    Hm, K = s["mamba_num_heads"], s["conv_kernel"]
    inner, width = inner_width(s), conv_width(s)
    E, R = len(s["experts_held"]), s["router_experts"]
    out: list = [(("tok_emb",), (s["vocab_size"], D), "matrix"),
                 (("lm_head", "kernel"), (D, s["vocab_size"]), "matrix"),
                 (("final_norm", "scale"), (D,), "norm")]
    for i, kind in enumerate(s["layers"]):
        lay = f"layer_{i}"
        m, e = (lay, "mixer"), (lay, "moe")
        out.append(((lay, "norm", "scale"), (D,), "norm"))
        if kind == "mamba":
            out += [(m + ("in_proj", "kernel"), (D, inner + width + Hm),
                     "matrix"),
                    (m + ("conv1d", "kernel"), (K, width), "conv"),
                    (m + ("conv1d_bias", "value"), (width,), "conv"),
                    (m + ("dt_bias", "value"), (Hm,), "dt_bias"),
                    (m + ("A_log", "value"), (Hm,), "A_log"),
                    (m + ("D", "value"), (Hm,), "ones"),
                    (m + ("norm", "scale"), (inner,), "norm"),
                    (m + ("out_proj", "kernel"), (inner, D), "matrix")]
        elif kind == "attention":
            out += [(m + ("q", "kernel"), (D, H, dh), "matrix"),
                    (m + ("k", "kernel"), (D, Gk, dh), "matrix"),
                    (m + ("v", "kernel"), (D, Gk, dh), "matrix"),
                    (m + ("o", "kernel"), (H, dh, D), "matrix")]
        else:
            out += [(e + ("router", "kernel"), (D, R), "matrix"),
                    (e + ("router_bias",), (R,), "zeros"),
                    (e + ("latent_down", "kernel"), (D, Dl), "matrix"),
                    (e + ("latent_up", "kernel"), (Dl, D), "matrix"),
                    (e + ("experts_up", "kernel"), (E, Dl, F), "matrix"),
                    (e + ("experts_down", "kernel"), (E, F, Dl), "matrix"),
                    (e + ("shared_up", "kernel"), (D, Fs), "matrix"),
                    (e + ("shared_down", "kernel"), (Fs, D), "matrix")]
    return out


# What the reference needs beyond the weights' shapes (the layer list, the
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
        if how == "ones":
            leaf = jnp.ones(shape, jnp.float32)
        elif how == "zeros":
            leaf, dtype = jnp.zeros(shape, jnp.float32), jnp.float32
        elif how == "A_log":
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                              1.0, 16.0))
        elif how == "dt_bias":
            lo, hi = (math.log(sizes["time_step_min"]),
                      math.log(sizes["time_step_max"]))
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, lo, hi)), sizes["time_step_floor"])
            leaf = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
        elif how == "conv":
            leaf = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
        elif how == "norm":
            leaf = 1.0 + STD * jax.random.normal(k, shape, jnp.float32)
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
    return sum(math.prod(shape) * (4 if how == "zeros" else 2)
               for _, shape, how in leaf_shapes(sizes))


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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


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


def mamba_mixer(u, p, s: Dict[str, Any], precision: str):
    """The selective state recurrence of one sequence, token by token: u
    [L, D] -> [L, D]."""
    L = u.shape[0]
    H, P, N, K, G = (s["mamba_num_heads"], s["mamba_head_dim"],
                     s["ssm_state_size"], s["conv_kernel"], s["n_groups"])
    inner, width = inner_width(s), conv_width(s)
    proj = _mm("ld,de->le", u, p["in_proj"]["kernel"], precision)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + width],
                  proj[:, inner + width:])
    xbc = _rounded(xbc, precision)
    w = p["conv1d"]["kernel"].astype(jnp.float32)                  # [K, C]
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    act = p["conv1d_bias"]["value"].astype(jnp.float32) + sum(
        w[j] * padded[j:j + L] for j in range(K))
    act = _rounded(jax.nn.silu(act), precision)
    x = act[:, :inner].reshape(L, H, P)
    bm = act[:, inner:inner + G * N].reshape(L, G, N)
    cm = act[:, inner + G * N:].reshape(L, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"]["value"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"]["value"].astype(jnp.float32))

    def token(S, xs):                                            # S [H,P,N]
        xt, bt, ct, dtt = xs
        # head h reads the B and C of group h // (H / G)
        bh, ch = (jnp.repeat(bt, H // G, axis=0),
                  jnp.repeat(ct, H // G, axis=0))                # [H, N]
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, ch, precision=HI)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (x, bm, cm, dt))
    y = y + p["D"]["value"].astype(jnp.float32)[:, None] * x
    y = _rms((y.reshape(L, inner) * jax.nn.silu(z)).reshape(
        L, G, inner // G), p["norm"]["scale"].reshape(G, inner // G),
        s["layer_norm_epsilon"]).reshape(L, inner)
    return _mm("le,ed->ld", y, p["out_proj"]["kernel"], precision)


ATTEND_QUERY_BLOCK = 256


def attention_mixer(u, p, s: Dict[str, Any], precision: str):
    """Grouped-query attention of one sequence without positions: u [L,
    D] -> [L, D]."""
    L = u.shape[0]
    H, G, d = s["num_attention_heads"], s["num_key_value_heads"], \
        s["head_dim"]
    k = _mm("ld,dge->lge", u, p["k"]["kernel"], precision)
    v = _mm("ld,dge->lge", u, p["v"]["kernel"], precision)

    def block(lo, n):
        at = lo + jnp.arange(n)
        ub = jax.lax.dynamic_slice_in_dim(u, lo, n)
        q = _mm("ld,dhe->lhe", ub, p["q"]["kernel"], precision)
        sc = _mm("nghd,sgd->ngsh", q.reshape(n, G, H // G, d), k,
                 precision) / math.sqrt(d)
        mask = jnp.arange(L)[None, :] <= at[:, None]             # [n, L]
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


def shared_expert(u, p, precision: str):
    return _mm("lf,fd->ld", _relu2(_mm("ld,df->lf", u,
                                       p["shared_up"]["kernel"], precision)),
               p["shared_down"]["kernel"], precision)


def expert_layer(u, p, s: Dict[str, Any], precision: str,
                 held=None, shared: bool = True):
    """Routed(u) over the experts ``held`` (ids; None: the configuration's
    own share) plus Shared(u) (``shared`` False leaves it out: another
    chip's part of a layer counts the shared expert once): u [L, D] -> [L,
    D]. The routed weights stored are those of ``s['experts_held']`` in
    that order. The latent's two projections are whole on every chip and
    linear, so each share's routed part goes through them on its own."""
    mine = s["experts_held"]
    held = mine if held is None else held
    ids, w = route(u, p["router"]["kernel"], p["router_bias"], s)
    # weight of expert e for each token: 0 where it is not picked
    per = jnp.sum(jnp.where(ids[..., None] == jnp.asarray(held)[None, None],
                            w[..., None], 0.0), axis=1)          # [L, held]
    at = jnp.asarray([mine.index(e) for e in held])
    lat = _mm("ld,de->le", u, p["latent_down"]["kernel"], precision)

    def one(args):
        j, we = args
        h = _relu2(_mm("le,ef->lf", lat, p["experts_up"]["kernel"][j],
                       precision))
        return we[:, None] * _mm("lf,fe->le", h,
                                 p["experts_down"]["kernel"][j], precision)

    y = jnp.sum(jax.lax.map(one, (at, per.T)), axis=0)           # [L, Dl]
    y = _mm("le,ed->ld", y, p["latent_up"]["kernel"], precision)
    if shared:
        y = y + shared_expert(u, p, precision)
    return y


def forward_features(params, tokens, sizes: Dict[str, Any],
                     precision: str = "f32"):
    """tokens [L] -> the final-normed features [L, D] of one sequence."""
    s = sizes
    L = tokens.shape[0]
    eps = s["layer_norm_epsilon"]
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for i, kind in enumerate(s["layers"]):
        p = params[f"layer_{i}"]
        u = _rms(x, p["norm"]["scale"], eps)
        if kind == "mamba":
            y = mamba_mixer(u, p["mixer"], s, precision)
        elif kind == "attention":
            y = attention_mixer(u, p["mixer"], s, precision)
        else:
            y = _in_blocks(
                lambda lo, n: expert_layer(
                    jax.lax.dynamic_slice_in_dim(u, lo, n), p["moe"], s,
                    precision), L, 512)
        x = x + y
    return _rms(x, params["final_norm"]["scale"], eps)


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
    its own forward pass; what lies past a request's end is causal from it
    and only costs time)."""
    return min(-(-longest // 256) * 256, max(sizes["n_positions"], longest))


# -- counts -----------------------------------------------------------------

def layer_counts(sizes: Dict[str, Any]) -> Tuple[int, int]:
    """(state-space layers, attention layers) held; the rest are expert
    layers (:func:`expert_layers`), which keep nothing a slot."""
    return (sum(1 for k in sizes["layers"] if k == "mamba"),
            sum(1 for k in sizes["layers"] if k == "attention"))


def expert_layers(sizes: Dict[str, Any]) -> int:
    return sum(1 for k in sizes["layers"] if k == "moe")


def state_numbers(sizes: Dict[str, Any]) -> int:
    """float32 numbers of ONE layer's state a row."""
    return inner_width(sizes) * sizes["ssm_state_size"]


def state_bytes_per_slot(sizes: Dict[str, Any]) -> int:
    """The float32 state a slot holds, whatever its depth."""
    return layer_counts(sizes)[0] * state_numbers(sizes) * 4


def conv_bytes_per_slot(sizes: Dict[str, Any], rows: int = 0) -> int:
    """The convolution's inputs a slot holds in bfloat16: ``rows`` a layer
    (0: the program's ring of ``conv_kernel``)."""
    return layer_counts(sizes)[0] * (rows or sizes["conv_kernel"]) \
        * conv_width(sizes) * 2


def cache_bytes_per_token(sizes: Dict[str, Any], bytes_per_el: int = 2
                          ) -> Dict[str, float]:
    """What one token leaves in the position-indexed leaves: K and V of the
    attention layers' key-value heads."""
    return {"kv": layer_counts(sizes)[1] * 2 * sizes["num_key_value_heads"]
            * sizes["head_dim"] * bytes_per_el}


def state_step_cost(sizes: Dict[str, Any], rows: float) -> tuple:
    """(operations, bytes from HBM) of ONE state-space layer's decode step
    over ``rows`` LIVE rows: each number of a row's state decayed and added
    to (2 a number), the outer product and the read-out (2 each); the
    state read once and written once. x, dt, B, C and y (a few KB a row)
    are left out."""
    n = state_numbers(sizes)
    return 6.0 * n * rows, 8.0 * n * rows


#: Tokens a chunk of the chunked form whose cost is counted below: the
#: program's (``ops/state_space.py::SCAN_CHUNK``). The source's
#: ``chunk_size`` 128 is the tiling of its own kernel, not mathematics.
SCAN_CHUNK = 256


def chunk_scan_cost(sizes: Dict[str, Any], length: int, chunk: int
                    ) -> tuple:
    """(operations, bytes from HBM) of ONE state-space layer's chunked scan
    over ``length`` positions in chunks of ``chunk``. A token a head: the
    chunk's decayed scores times ``dt x`` (``2 C P``), the read of the
    carried state (``2 N P``) and its update (``2 N P``); ``C B^T`` is
    shared by a group's heads (``2 C N`` a token a GROUP, once). x, B and
    C read once in bfloat16, the log-decays and ``dt`` in float32 (12 B a
    token a head), y written once in float32 (the gated norm that follows
    takes it unrounded), the final state written in float32. Nothing a
    kernel keeps in VMEM (the carried state between chunks) is priced as
    traffic."""
    H, P, N, G = (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
                  sizes["ssm_state_size"], sizes["n_groups"])
    ops = 2.0 * length * (H * (chunk * P + 2 * N * P) + G * chunk * N)
    byts = length * (H * P * (2 + 4) + 2 * G * N * 2 + H * 12) \
        + H * P * N * 4
    return ops, byts


def expert_bytes(sizes: Dict[str, Any]) -> int:
    """bfloat16 bytes of ONE routed expert: up and down in the latent."""
    return 2 * sizes["moe_latent_size"] * sizes["moe_intermediate_size"] * 2


def expert_step_cost(sizes: Dict[str, Any], held_pairs: float,
                     experts_hit: float) -> tuple:
    """(operations, bytes from HBM) of the held experts' grouped matmuls
    of ONE decode step, all expert layers: ``held_pairs`` (token, expert)
    pairs landed on ``experts_hit`` held experts (both summed over the
    layers). A pair is two products in the latent (``2 x 2 Dl F``
    operations); an expert a pair reached is read whole, once (an expert
    reached by one pair is read as much as one reached by ten); a pair's
    row comes in bfloat16 and its result goes out float32. The ``[pairs,
    F]`` rows between the two products are not priced: a kernel could keep
    them on the chip."""
    Dl, F = sizes["moe_latent_size"], sizes["moe_intermediate_size"]
    return (4.0 * Dl * F * held_pairs,
            experts_hit * expert_bytes(sizes) + held_pairs * Dl * (2 + 4))


def decode_step_bytes(param_bytes: int, sizes: Dict[str, Any], slots: float,
                      kv_bytes_per_el: int = 2, *, keys_kept=None,
                      keys_available=None, experts_hit=None) -> float:
    """Bytes one decode step with ``slots`` LIVE rows must read and write
    (the signature ``serve.decode_bw_share.ssm`` calls). Every parameter
    as stored, once, except: of the embedding only the live rows' own
    rows (the head is a matrix of its own), and of the routed experts
    only the ``experts_hit`` a step reached (summed over the layers;
    None: all held). Each live row's states read and written and its
    convolution ring read; in every attention layer K and V of the
    ``keys_kept`` positions the live rows attend (None: ``slots`` rows at
    full depth)."""
    del keys_available
    D = sizes["hidden_size"]
    held = len(sizes["experts_held"]) * expert_layers(sizes)
    if experts_hit is None:
        experts_hit = held
    if keys_kept is None:
        keys_kept = slots * sizes["n_positions"]
    return (param_bytes - (held - experts_hit) * expert_bytes(sizes)
            - (sizes["vocab_size"] - slots) * D * 2
            + 2.0 * slots * state_bytes_per_slot(sizes)
            + slots * conv_bytes_per_slot(sizes)
            + keys_kept * cache_bytes_per_token(sizes, kv_bytes_per_el)["kv"])
