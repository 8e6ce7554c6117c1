"""granite-4.0-h-small (``model_type: granitemoehybrid``) as the benchmark
knows it: the sizes it reads from a configuration, its weights from
``--seed``, its plain reference, and the counts its per-layer readers need.
It imports nothing of the program and nothing of the other model files: the
reference below is written from the equations, on its own.

**The architecture** (ibm-granite/granite-4.0-h-small ``config.json``).
Pre-norm residual blocks, RMSNorm, no bias but the convolution's, the
embedding tied to the head, four muP-style multipliers, one of two mixers a
layer (``layer_types``) and a routed expert layer beside a shared expert in
every layer:

- Model. ``x0 = embedding_multiplier E[tok]``; ``h = x + r Mixer(rms(x))``,
  ``u = rms(h)``, ``x' = h + r (Routed(u) + Shared(u))`` with ``r =
  residual_multiplier``; logits ``= E rms(x_L) / logits_scaling``.
- ``mamba`` (Mamba-2). ``[z | xBC | dt] = W_in u`` (widths ``H P | H P + 2
  N | H``); ``xBC_t = silu(b + sum_{j=0..3} w_j xBC_{t-3+j})`` depthwise,
  zeros before the sequence; split into ``x_t [H, P]``, ``B_t [N]``, ``C_t
  [N]`` (one group, shared by every head); ``dt_t = softplus(dt_t +
  dt_bias)`` a head; ``A_h = -exp(A_log_h)``; ``S_t = exp(dt_t A_h) S_{t-1}
  + dt_t x_t B_t^T`` a head (float32, ``S_{-1} = 0``); ``y_t = S_t C_t +
  D_h x_t``; ``y = rms(y silu(z)) g`` over all ``H P`` channels; ``W_out``.
  Computed HERE token by token (a ``lax.scan`` over positions), never in
  chunks.
- ``attention``. Query heads over fewer key-value heads, no rotation and no
  position signal at all; scores times ``attention_multiplier``; causal
  softmax; ``W_o``.
- Routed experts. ``l = W_r u`` (all published experts); the
  ``num_experts_per_tok`` largest logits (ties to the lower index); weights
  ``softmax`` over those logits alone; expert e is ``W_out^e (silu(W_gate^e
  u) * (W_up^e u))``. The reference is given THE SAME SHARE as the program
  (the ids of the experts held; the router's width is the published one):
  it loops over the held experts and leaves out what absent experts would
  add, as the chip does. Shared: the same form, weight 1, every token.

**Departures and readings this builder knows of**, all under ``assumed`` in
the configuration: ``intermediate_size`` read as one expert's width; the
published fused ``input_linear`` held as ``gate`` and ``up``; the
convolution's weight held ``[taps, channels]``; ``A_log``, ``dt_bias`` and
``D`` initialised as Mamba-2 publishes (not N(0, 0.02): every decay would be
the same number); ``time_step_limit`` unbounded; the held share of the
experts and of the vocabulary; 10 of the 40 layers.

**Weights.** Made on the device in one jitted call from the key, in the
program's tree (bfloat16 leaves). The reference reads the same bfloat16
values and upcasts each matrix where it is used, so no float32 copy of the
model ever exists.

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

INT_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "shared_intermediate_size", "num_attention_heads",
            "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "mamba_d_conv", "mamba_n_groups",
            "mamba_expand", "num_experts_per_tok", "num_hidden_layers",
            "num_local_experts", "max_position_embeddings")
FLOAT_KEYS = ("rms_norm_eps", "embedding_multiplier", "residual_multiplier",
              "attention_multiplier", "logits_scaling")
KINDS = ("mamba", "attention")


def sizes(src: Dict[str, Any]) -> Dict[str, Any]:
    """What this file reads of a configuration (or of its
    ``rehearsal.sizes``): every value hashable, so that the dict can be a
    static argument."""
    out: Dict[str, Any] = {k: int(src[k]) for k in INT_KEYS}
    out.update({k: float(src[k]) for k in FLOAT_KEYS})
    lo = int(src.get("first_layer_held", 0))
    kinds = list(src["layer_types"])[lo:lo + out["num_hidden_layers"]]
    if len(kinds) != out["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError(f"layer_types[{lo}:{lo}+{out['num_hidden_layers']}]"
                         f" = {kinds}")
    if out["mamba_n_groups"] != 1:
        raise ValueError("one group of B and C is written down here")
    if src.get("position_embedding_type", "nope") != "nope":
        raise ValueError("the attention layers take no positions")
    if not src.get("tie_word_embeddings", True):
        raise ValueError("the embedding is the head")
    if out["mamba_n_heads"] * out["mamba_d_head"] != \
            out["mamba_expand"] * out["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    out["layers"] = tuple(kinds)
    # the spread of the seeded matrices (``make_params``): a rehearsal's
    # tiny widths take a wider one, so that its projections come out as
    # large as the published widths' (sqrt(4096) x 0.02) and the state
    # weighs in its outputs as it does there
    out["weight_std"] = float(src.get("weight_std", STD))
    out["router_experts"] = int(src.get("num_local_experts_published",
                                        out["num_local_experts"]))
    held = tuple(int(e) for e in src.get(
        "experts_held", range(out["num_local_experts"])))
    if len(held) != out["num_local_experts"]:
        raise ValueError("experts_held names num_local_experts experts")
    out["experts_held"] = held
    out["head_dim"] = out["hidden_size"] // out["num_attention_heads"]
    out["n_positions"] = out["max_position_embeddings"]
    return out


def inner_width(s: Dict[str, Any]) -> int:
    return s["mamba_n_heads"] * s["mamba_d_head"]


def conv_width(s: Dict[str, Any]) -> int:
    return inner_width(s) + 2 * s["mamba_d_state"]


# -- weights ----------------------------------------------------------------

STD = 0.02
#: The embedding-and-head's rows are N(0, STD / 8). With N(0, 0.02) rows, the
#: tied head and ``embedding_multiplier`` 12, a token's OWN logit (12 |E|^2
#: over ``logits_scaling`` and the stream's rms: about 0.9 at the published
#: widths) beats the largest of the other 50,175 (about 0.34) at every
#: position: the model repeats its input whatever the layers compute, and
#: every served token has gap 0 at any precision (my CPU rehearsals and my
#: first chip run, PR 41: 3,034 of 3,034). The own-to-largest-other ratio
#: is about 180 sigma / rms; sigma 0.0025 puts it near 1/3.
EMB_STD = STD / 8


def leaf_shapes(s: Dict[str, Any]) -> List[Tuple[Tuple[str, ...], tuple,
                                                 Any]]:
    """(path, shape, how it is made) of every leaf of the program's tree,
    all bfloat16. How: "matrix" is N(0, ``weight_std``) (0.02 unless the
    sizes say otherwise), "norm" a norm's scale N(1, 0.02); "emb" is
    N(0, EMB_STD); "A_log" is ``log U(1, 16)``; "dt_bias" the inverse
    softplus of ``U(0.001, 0.1)``; "ones" is 1; "conv" is ``U(-1/2, 1/2)``
    (a depthwise convolution of 4 taps under PyTorch's default, which
    Mamba-2's published code keeps: with N(0, 0.02) taps x, B and C come out
    near 0.03 and ``S C`` is 4e-4 of the skip ``D x``: a state folded twice
    moved no logit by more than 1e-7, so ``correct`` would not see the
    state at all; my CPU reading, PR 41)."""
    D, F, Fs = (s["hidden_size"], s["intermediate_size"],
                s["shared_intermediate_size"])
    H, G, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                s["head_dim"])
    Hm, K = s["mamba_n_heads"], s["mamba_d_conv"]
    inner, width = inner_width(s), conv_width(s)
    E, R = len(s["experts_held"]), s["router_experts"]
    out: list = [(("tok_emb",), (s["vocab_size"], D), "emb"),
                 (("final_norm", "scale"), (D,), "norm")]
    for i, kind in enumerate(s["layers"]):
        lay = f"layer_{i}"
        m, e = (lay, "mixer"), (lay, "moe")
        out += [((lay, "mixer_norm", "scale"), (D,), "norm"),
                ((lay, "moe_norm", "scale"), (D,), "norm"),
                (e + ("router", "kernel"), (D, R), "matrix"),
                (e + ("experts_gate", "kernel"), (E, D, F), "matrix"),
                (e + ("experts_up", "kernel"), (E, D, F), "matrix"),
                (e + ("experts_down", "kernel"), (E, F, D), "matrix"),
                (e + ("shared_gate", "kernel"), (D, Fs), "matrix"),
                (e + ("shared_up", "kernel"), (D, Fs), "matrix"),
                (e + ("shared_down", "kernel"), (Fs, D), "matrix")]
        if kind == "mamba":
            out += [(m + ("in_proj", "kernel"), (D, inner + width + Hm), "matrix"),
                    (m + ("conv1d", "kernel"), (K, width), "conv"),
                    (m + ("conv1d_bias", "value"), (width,), "conv"),
                    (m + ("dt_bias", "value"), (Hm,), "dt_bias"),
                    (m + ("A_log", "value"), (Hm,), "A_log"),
                    (m + ("D", "value"), (Hm,), "ones"),
                    (m + ("norm", "scale"), (inner,), "norm"),
                    (m + ("out_proj", "kernel"), (inner, D), "matrix")]
        else:
            out += [(m + ("q", "kernel"), (D, H, dh), "matrix"),
                    (m + ("k", "kernel"), (D, G, dh), "matrix"),
                    (m + ("v", "kernel"), (D, G, dh), "matrix"),
                    (m + ("o", "kernel"), (H, dh, D), "matrix")]
    return out


# What the reference needs beyond the weights' shapes (the layer list, the
# experts held, the multipliers) is the ``sizes`` the weights were made
# from: ``make_params`` records them under the tree's shapes, because the
# runners call the reference with the weights and the sequences only.
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
        if how == "ones":
            leaf = jnp.ones(shape, jnp.float32)
        elif how == "A_log":
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                              1.0, 16.0))
        elif how == "dt_bias":
            dt = jax.random.uniform(k, shape, jnp.float32, 0.001, 0.1)
            leaf = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
        elif how == "conv":
            leaf = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
        elif how == "emb":
            leaf = EMB_STD * jax.random.normal(k, shape, jnp.float32)
        elif how == "norm":
            leaf = 1.0 + STD * jax.random.normal(k, shape, jnp.float32)
        else:
            assert how == "matrix", how
            leaf = sizes["weight_std"] * jax.random.normal(
                k, shape, jnp.float32)
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf.astype(jnp.bfloat16)
    _BOUND[_shape_key(out)] = dict(sizes)
    return out


def param_count(sizes: Dict[str, Any]) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_shapes(sizes))


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
    H, P, N, K = (s["mamba_n_heads"], s["mamba_d_head"], s["mamba_d_state"],
                  s["mamba_d_conv"])
    inner, width = inner_width(s), conv_width(s)
    proj = _mm("ld,de->le", u, p["in_proj"]["kernel"], precision)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + width],
                  proj[:, inner + width:])
    xbc = _rounded(xbc, precision)
    w = p["conv1d"]["kernel"].astype(jnp.float32)                  # [K, C]
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    act = p["conv1d_bias"]["value"].astype(jnp.float32) + sum(
        w[j] * padded[j:j + L] for j in range(K))
    act = jax.nn.silu(act)
    act = _rounded(act, precision)
    x = act[:, :inner].reshape(L, H, P)
    bm, cm = act[:, inner:inner + N], act[:, inner + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"]["value"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"]["value"].astype(jnp.float32))

    def token(S, xs):                                            # S [H,P,N]
        xt, bt, ct, dtt = xs
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return S, jnp.einsum("hpn,n->hp", S, ct, precision=HI)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (x, bm, cm, dt))
    y = y + p["D"]["value"].astype(jnp.float32)[:, None] * x
    y = _rms(y.reshape(L, inner) * jax.nn.silu(z), p["norm"]["scale"],
             s["rms_norm_eps"])
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
                 precision) * s["attention_multiplier"]
        mask = jnp.arange(L)[None, :] <= at[:, None]             # [n, L]
        sc = jnp.where(mask[:, None, :, None], sc, -jnp.inf)
        o = _mm("ngsh,sgd->nghd", jax.nn.softmax(sc, axis=2), v, precision)
        return _mm("lhe,hed->ld", o.reshape(n, H, d), p["o"]["kernel"],
                   precision)

    return _in_blocks(block, L, ATTEND_QUERY_BLOCK)


def route(u, w_r, k: int):
    """(ids [L, k], weights [L, k]): the ``k`` largest of the logits over
    every published expert (ties to the lower index), softmax over them.
    The router's product is float32 whatever the control's precision: the
    program states so."""
    logits = jnp.einsum("ld,de->le", u, w_r.astype(jnp.float32),
                        precision=HI)
    top, ids = jax.lax.top_k(logits, k)
    return ids, jax.nn.softmax(top, axis=-1)


def _gated(x, gate, up, down, precision):
    h = jax.nn.silu(_mm("ld,df->lf", x, gate, precision)) \
        * _mm("ld,df->lf", x, up, precision)
    return _mm("lf,fd->ld", h, down, precision)


def expert_layer(u, p, s: Dict[str, Any], precision: str,
                 held=None, shared: bool = True):
    """Routed(u) over the experts ``held`` (ids; None: the configuration's
    own share) plus Shared(u) (``shared`` False leaves it out: the other
    chip's part of a layer counts the shared expert once): u [L, D] -> [L,
    D]. The routed weights stored are those of ``s['experts_held']`` in
    that order."""
    mine = s["experts_held"]
    held = mine if held is None else held
    ids, w = route(u, p["router"]["kernel"], s["num_experts_per_tok"])
    # weight of expert e for each token: 0 where it is not picked
    per = jnp.sum(jnp.where(ids[..., None] == jnp.asarray(held)[None, None],
                            w[..., None], 0.0), axis=1)          # [L, held]
    at = jnp.asarray([mine.index(e) for e in held])

    def one(args):
        j, we = args
        return we[:, None] * _gated(u, p["experts_gate"]["kernel"][j],
                                    p["experts_up"]["kernel"][j],
                                    p["experts_down"]["kernel"][j],
                                    precision)

    y = jnp.sum(jax.lax.map(one, (at, per.T)), axis=0)
    if shared:
        y = y + _gated(u, p["shared_gate"]["kernel"],
                       p["shared_up"]["kernel"], p["shared_down"]["kernel"],
                       precision)
    return y


def forward_features(params, tokens, sizes: Dict[str, Any],
                     precision: str = "f32"):
    """tokens [L] -> the final-normed features over ``logits_scaling`` [L,
    D] of one sequence."""
    s = sizes
    L = tokens.shape[0]
    r, eps = s["residual_multiplier"], s["rms_norm_eps"]
    x = s["embedding_multiplier"] * params["tok_emb"][tokens].astype(
        jnp.float32)
    for i, kind in enumerate(s["layers"]):
        p = params[f"layer_{i}"]
        u = _rms(x, p["mixer_norm"]["scale"], eps)
        if kind == "mamba":
            y = mamba_mixer(u, p["mixer"], s, precision)
        else:
            y = attention_mixer(u, p["mixer"], s, precision)
        x = x + r * y
        u = _rms(x, p["moe_norm"]["scale"], eps)
        x = x + r * _in_blocks(
            lambda lo, n: expert_layer(
                jax.lax.dynamic_slice_in_dim(u, lo, n), p["moe"], s,
                precision), L, 512)
    return _rms(x, params["final_norm"]["scale"], eps) / s["logits_scaling"]


def logits_fn(params, tokens, sizes, precision: str = "f32"):
    """tokens [B, L] -> logits [B, L, V] float32 (small sizes: the
    tests; the runners go through the blocked functions below)."""
    return jax.lax.map(
        lambda t: _mm("ld,vd->lv", forward_features(params, t, sizes,
                                                    precision),
                      params["tok_emb"], precision), tokens)


def _head_blocks(params, feats, fn, precision):
    """``fn(logits block [n, V], start, n)`` over blocks of positions."""
    return _in_blocks(
        lambda lo, n: fn(_mm("ld,vd->lv",
                             jax.lax.dynamic_slice_in_dim(feats, lo, n),
                             params["tok_emb"], precision), lo, n),
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
    """(state-space layers, attention layers) held."""
    n_ssm = sum(1 for k in sizes["layers"] if k == "mamba")
    return n_ssm, len(sizes["layers"]) - n_ssm


def state_numbers(sizes: Dict[str, Any]) -> int:
    """float32 numbers of ONE layer's state a row."""
    return inner_width(sizes) * sizes["mamba_d_state"]


def state_bytes_per_slot(sizes: Dict[str, Any]) -> int:
    """The float32 state a slot holds, whatever its depth."""
    return layer_counts(sizes)[0] * state_numbers(sizes) * 4


def conv_bytes_per_slot(sizes: Dict[str, Any], rows: int = 0) -> int:
    """The convolution's inputs a slot holds in bfloat16: ``rows`` a layer
    (0: the program's ring of ``mamba_d_conv``)."""
    return layer_counts(sizes)[0] * (rows or sizes["mamba_d_conv"]) \
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


#: Tokens a chunk of the chunked form whose cost is counted below (the
#: program's and the source's ``mamba_chunk_size``).
SCAN_CHUNK = 256


def chunk_scan_cost(sizes: Dict[str, Any], length: int, chunk: int
                    ) -> tuple:
    """(operations, bytes from HBM) of ONE state-space layer's chunked scan
    over ``length`` positions in chunks of ``chunk``. A token a head: the
    chunk's decayed scores times ``dt x`` (``2 C P``), the read of the
    carried state (``2 N P``) and its update (``2 N P``); ``C B^T`` is
    shared by the heads (``2 C N`` a token, once). x, B and C read once in
    bfloat16, the log-decays and ``dt`` in float32 (12 B a token a head),
    y written once in float32 (the gated norm that follows takes it
    unrounded), the final state written in float32. Nothing a kernel keeps
    in VMEM (the carried state between chunks) is priced as traffic."""
    H, P, N = (sizes["mamba_n_heads"], sizes["mamba_d_head"],
               sizes["mamba_d_state"])
    ops = 2.0 * length * (H * (chunk * P + 2 * N * P) + chunk * N)
    byts = length * (H * P * (2 + 4) + 2 * N * 2 + H * 12) + H * P * N * 4
    return ops, byts


def decode_step_bytes(param_bytes: int, sizes: Dict[str, Any], slots: float,
                      kv_bytes_per_el: int = 2, *, keys_kept=None,
                      keys_available=None, experts_hit=None) -> float:
    """Bytes one decode step with ``slots`` LIVE rows must read and write
    (the signature ``serve.decode_bw_share.live`` calls). Every parameter
    as stored, once, except: the embedding-and-head's table is read once
    as the head, and of the routed experts only the ``experts_hit`` a step
    reached (summed over the layers; None: all held). Each live row's
    states read and written and its convolution ring read; in every
    attention layer K and V of the ``keys_kept`` positions the live rows
    attend (None: ``slots`` rows at full depth)."""
    del keys_available
    n_ssm, _ = layer_counts(sizes)
    D, F = sizes["hidden_size"], sizes["intermediate_size"]
    held = len(sizes["experts_held"]) * len(sizes["layers"])
    if experts_hit is None:
        experts_hit = held
    if keys_kept is None:
        keys_kept = slots * sizes["n_positions"]
    one_expert = 3 * D * F * 2
    return (param_bytes - (held - experts_hit) * one_expert
            + 2.0 * slots * state_bytes_per_slot(sizes)
            + slots * conv_bytes_per_slot(sizes)
            + keys_kept * cache_bytes_per_token(sizes, kv_bytes_per_el)["kv"])
