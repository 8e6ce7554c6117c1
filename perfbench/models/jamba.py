"""AI21-Jamba2-3B (``model_type: jamba``) as the benchmark knows it: the
sizes it reads from a configuration, its weights from ``--seed``, its plain
reference, and the counts its per-layer readers need. It imports nothing of
the program and nothing of the other model files: the reference below is
written from the equations, on its own.

**The architecture** (ai21labs/AI21-Jamba2-3B ``config.json``; layer
equations from Jamba, arXiv:2403.19887, and the ``jamba`` model of the
``transformers`` library, whose key names the config uses). ``D`` hidden;
RMSNorm with a learned scale, eps ``rms_norm_eps``; no bias but the
convolution's and ``dt``'s; no position signal anywhere (the family has
none). Layer ``i`` is an attention layer iff ``i mod attn_layer_period ==
attn_layer_offset`` and a Mamba layer otherwise; ``num_experts`` is 1, so
every layer's feed-forward is the dense one.

- Layer. ``h = x + Mixer_i(rms_in(x))``, ``y = h + FF(rms_ff(h))``; after
  the last layer ``rms_final``, then logits ``= y E^T`` with ``E`` the
  embedding table (``tie_word_embeddings``).
- Mamba mixer (``d_inner = mamba_expand D``, ``N = mamba_d_state``, ``R =
  mamba_dt_rank``, 4 taps): ``[x | z] = u W_in`` ``[D, 2 d_inner]``; ``x_t
  <- silu(b_conv + sum_{j=0..3} w_j x_{t-3+j})``, depthwise and causal,
  zeros before the sequence; ``[r | B | C] = x W_x`` ``[d_inner, R + 2
  N]``; ``r <- rms(r)``, ``B <- rms(B)``, ``C <- rms(C)``, each with its own
  learned scale (``R``, ``N``, ``N``) and the same eps (the family's
  ``dt_layernorm``, ``b_layernorm``, ``c_layernorm``); ``dt = softplus(r
  W_dt + b_dt)`` ``[R, d_inner]``, float32; ``A = -exp(A_log)`` ``[d_inner,
  N]`` (held ``[N, d_inner]``), float32. Recurrence a channel ``c`` and
  state number ``n``, ``h_0 = 0``: ``h_t[c, n] = exp(dt_t[c] A[c, n])
  h_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]``; ``y_t[c] = sum_n h_t[c, n]
  C_t[n] + D_skip[c] x_t[c]``; out ``(y * silu(z)) W_out`` ``[d_inner,
  D]``. Computed HERE token by token (a ``lax.scan`` over positions with the
  state ``[d_inner, N]``), never in chunks.
- Attention mixer (``H`` query heads over ``G`` key-value heads, ``d = D /
  H``): ``q = u W_q``, ``k = u W_k``, ``v = u W_v``; causal softmax of ``q
  k^T / sqrt(d)`` in float32, a group's heads over its one K and V; ``W_o``.
  No rotation, no norm a head, no window.
- Feed-forward. ``W_d (silu(u W_g) * (u W_u))``.

**Readings this builder made**, all under ``assumed`` in the configuration:
pre-norm placement; the three inner RMSNorms; ``dt_rank`` read from the
config; softplus applied to ``r W_dt + b_dt``; ``D_skip`` a learned vector;
``A_log`` held transposed; the convolution's weight held ``[taps,
channels]``; the seeded ``A_log``, ``b_dt``, taps and ``D_skip``.

**Weights.** Made on the device in one jitted call from the key, in the
program's tree (bfloat16 matrices; float32 ``A_log``, ``D``, ``dt_bias`` and
norm scales). The reference reads the same values and upcasts each matrix
where it is used, so no float32 copy of the model ever exists.

**The plain reference.** float32 ``jax.numpy``, ``highest`` precision, one
sequence at a time; attention in blocks of queries against all keys, so
that 10,240 positions fit. ``precision`` selects a control: the same
mathematics with every product's operands rounded to that precision first
(``PRECISIONS``), or one mechanism of the reference left out (``CONTROLS``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

INT_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "mamba_d_state",
            "mamba_d_conv", "mamba_dt_rank", "mamba_expand",
            "num_hidden_layers", "attn_layer_period", "attn_layer_offset",
            "max_position_embeddings")
KINDS = ("mamba", "attention")


def sizes(src: Dict[str, Any]) -> Dict[str, Any]:
    """What this file reads of a configuration (or of its
    ``rehearsal.sizes``): every value hashable, so that the dict can be a
    static argument."""
    out: Dict[str, Any] = {k: int(src[k]) for k in INT_KEYS}
    out["rms_norm_eps"] = float(src["rms_norm_eps"])
    if int(src.get("num_experts", 1)) != 1:
        raise ValueError("a dense feed-forward in every layer is written "
                         "down here (num_experts 1)")
    if not src.get("tie_word_embeddings", True):
        raise ValueError("the embedding is the head")
    if out["hidden_size"] % out["num_attention_heads"] or \
            out["num_attention_heads"] % out["num_key_value_heads"]:
        raise ValueError("hidden_size divides into the query heads and "
                         "they into the key-value heads")
    period, offset = out["attn_layer_period"], out["attn_layer_offset"]
    out["layers"] = tuple("attention" if i % period == offset else "mamba"
                          for i in range(out["num_hidden_layers"]))
    # the spread of the seeded matrices (``make_params``): a rehearsal's
    # tiny widths take a wider one, so that its projections come out as
    # large as the published widths' (sqrt(2560) x 0.02)
    out["weight_std"] = float(src.get("weight_std", STD))
    out["head_dim"] = out["hidden_size"] // out["num_attention_heads"]
    out["n_positions"] = out["max_position_embeddings"]
    return out


def inner_width(s: Dict[str, Any]) -> int:
    return s["mamba_expand"] * s["hidden_size"]


# -- weights ----------------------------------------------------------------

STD = 0.02


def leaf_shapes(s: Dict[str, Any]) -> List[Tuple[Tuple[str, ...], tuple,
                                                 str]]:
    """(path, shape, how it is made) of every leaf of the program's tree.
    How: "matrix" is N(0, ``weight_std``) (0.02 unless the sizes say
    otherwise), bfloat16; "norm" a norm's scale N(1, 0.02), float32;
    "A_log" is ``log(n + 1)`` for state number ``n`` of every channel
    (Mamba's S4D-real initialisation), float32; "dt_bias" the inverse
    softplus of a ``dt`` log-uniform in [0.001, 0.1], float32 (with a zero
    bias ``dt`` is near 0.7 and every state forgets in a few tokens:
    ``correct`` would not see the state); "ones" is 1, float32 (``D``);
    "conv" is ``U(-1/2, 1/2)``, bfloat16 (a depthwise convolution of 4 taps
    under PyTorch's default, which Mamba's published code keeps; with N(0,
    0.02) taps ``x`` comes out near 0.03 and the state weighs nothing
    beside the rest: perfbench/models/granitemoehybrid.py's reading,
    PR 41)."""
    D, F = s["hidden_size"], s["intermediate_size"]
    H, G, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                s["head_dim"])
    N, K, R = s["mamba_d_state"], s["mamba_d_conv"], s["mamba_dt_rank"]
    inner = inner_width(s)
    out: list = [(("tok_emb",), (s["vocab_size"], D), "matrix"),
                 (("final_norm", "scale"), (D,), "norm")]
    for i, kind in enumerate(s["layers"]):
        lay = f"layer_{i}"
        m, f = (lay, "mixer"), (lay, "mlp")
        out += [((lay, "mixer_norm", "scale"), (D,), "norm"),
                ((lay, "mlp_norm", "scale"), (D,), "norm"),
                (f + ("gate", "kernel"), (D, F), "matrix"),
                (f + ("up", "kernel"), (D, F), "matrix"),
                (f + ("down", "kernel"), (F, D), "matrix")]
        if kind == "mamba":
            out += [(m + ("in_proj", "kernel"), (D, 2 * inner), "matrix"),
                    (m + ("conv1d", "kernel"), (K, inner), "conv"),
                    (m + ("conv1d_bias", "value"), (inner,), "conv"),
                    (m + ("x_proj", "kernel"), (inner, R + 2 * N), "matrix"),
                    (m + ("dt_norm", "scale"), (R,), "norm"),
                    (m + ("b_norm", "scale"), (N,), "norm"),
                    (m + ("c_norm", "scale"), (N,), "norm"),
                    (m + ("dt_proj", "kernel"), (R, inner), "matrix"),
                    (m + ("dt_bias", "value"), (inner,), "dt_bias"),
                    (m + ("A_log",), (N, inner), "A_log"),
                    (m + ("D", "value"), (inner,), "ones"),
                    (m + ("out_proj", "kernel"), (inner, D), "matrix")]
        else:
            out += [(m + ("q", "kernel"), (D, H, dh), "matrix"),
                    (m + ("k", "kernel"), (D, G, dh), "matrix"),
                    (m + ("v", "kernel"), (D, G, dh), "matrix"),
                    (m + ("o", "kernel"), (H, dh, D), "matrix")]
    return out


#: How a leaf is made -> the dtype it is stored in.
FLOAT32_LEAVES = ("norm", "A_log", "dt_bias", "ones")

# What the reference needs beyond the weights' shapes (the layer list) is
# the ``sizes`` the weights were made from: ``make_params`` records them
# under the tree's shapes, because the runners call the reference with the
# weights and the sequences only.
_BOUND: Dict[Any, Dict[str, Any]] = {}


def _shape_key(params) -> Any:
    return tuple((jax.tree_util.keystr(p), tuple(x.shape)) for p, x in
                 jax.tree_util.tree_leaves_with_path(params))


def make_params(key: jax.Array, sizes: Dict[str, Any],
                stacked: bool = False) -> Dict[str, Any]:
    """The whole tree (trace this under jit); the tied table is made once.
    The layers differ in kind, so the reference reads the program's own
    layout: ``stacked`` changes nothing."""
    out: Dict[str, Any] = {}
    for i, (path, shape, how) in enumerate(leaf_shapes(sizes)):
        k = jax.random.fold_in(key, i)
        if how == "ones":
            leaf = jnp.ones(shape, jnp.float32)
        elif how == "A_log":
            leaf = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
        elif how == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(0.001), math.log(0.1)))
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
        node[path[-1]] = leaf.astype(
            jnp.float32 if how in FLOAT32_LEAVES else jnp.bfloat16)
    _BOUND[_shape_key(out)] = dict(sizes)
    return out


def param_count(sizes: Dict[str, Any]) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_shapes(sizes))


def param_bytes(sizes: Dict[str, Any]) -> int:
    return sum(math.prod(shape) * (4 if how in FLOAT32_LEAVES else 2)
               for _, shape, how in leaf_shapes(sizes))


# -- the plain reference ----------------------------------------------------

PRECISIONS = ("f32", "bf16", "fp8")
#: Mechanisms a control may break in the REFERENCE (``correct`` compares
#: the served tokens with the sound reference, so what a broken reference
#: would have served stands for a program that broke the same mechanism):
#: the inner norms on ``B`` and ``C`` left out, the attention layers left
#: out (their mixer adds nothing).
CONTROLS = ("no_bc_norm", "no_attention")
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


def selective_scan(x, dt, A, bm, cm, h0=None):
    """The recurrence of one sequence, token by token: x, dt [L, C]; A
    [C, N]; bm, cm [L, N]; h0 [C, N] (None: zeros) -> (``sum_n h_t C_t``
    [L, C], the last state [C, N])."""
    if h0 is None:
        h0 = jnp.zeros(A.shape, jnp.float32)

    def token(h, xs):
        xt, dtt, bt, ct = xs
        h = jnp.exp(dtt[:, None] * A) * h \
            + (dtt * xt)[:, None] * bt[None, :]
        return h, jnp.einsum("cn,n->c", h, ct, precision=HI)

    # (unrolled: a turn of the loop is a few vector operations, and a
    # loop of 10,240 turns a layer costs the chip more in turns than in
    # arithmetic; the arithmetic and its order are the same)
    h, y = jax.lax.scan(token, h0, (x, dt, bm, cm), unroll=16)
    return y, h


def mamba_mixer(u, p, s: Dict[str, Any], precision: str, break_: str = ""):
    """The selective scan of one sequence: u [L, D] -> [L, D]."""
    L = u.shape[0]
    N, K, R = s["mamba_d_state"], s["mamba_d_conv"], s["mamba_dt_rank"]
    inner, eps = inner_width(s), s["rms_norm_eps"]
    proj = _mm("ld,de->le", u, p["in_proj"]["kernel"], precision)
    x, z = _rounded(proj[:, :inner], precision), proj[:, inner:]
    w = p["conv1d"]["kernel"].astype(jnp.float32)                  # [K, C]
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    x = jax.nn.silu(p["conv1d_bias"]["value"].astype(jnp.float32) + sum(
        w[j] * padded[j:j + L] for j in range(K)))
    x = _rounded(x, precision)
    rbc = _mm("le,ef->lf", x, p["x_proj"]["kernel"], precision)
    r = _rms(rbc[:, :R], p["dt_norm"]["scale"], eps)
    bm, cm = rbc[:, R:R + N], rbc[:, R + N:]
    if break_ != "no_bc_norm":
        bm = _rms(bm, p["b_norm"]["scale"], eps)
        cm = _rms(cm, p["c_norm"]["scale"], eps)
    dt = jax.nn.softplus(_mm("lr,re->le", r, p["dt_proj"]["kernel"],
                             precision) + p["dt_bias"]["value"])
    A = -jnp.exp(p["A_log"]).T                                     # [C, N]
    y, _ = selective_scan(x, dt, A, bm, cm)
    y = y + p["D"]["value"] * x
    return _mm("le,ed->ld", y * jax.nn.silu(z), p["out_proj"]["kernel"],
               precision)


ATTEND_QUERY_BLOCK = 256


def attention_mixer(u, p, s: Dict[str, Any], precision: str):
    """Attention of one sequence without positions, a group's query heads
    over its one K and V: u [L, D] -> [L, D]."""
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
                 precision) * d ** -0.5
        mask = jnp.arange(L)[None, :] <= at[:, None]             # [n, L]
        sc = jnp.where(mask[:, None, :, None], sc, -jnp.inf)
        o = _mm("ngsh,sgd->nghd", jax.nn.softmax(sc, axis=2), v, precision)
        return _mm("lhe,hed->ld", o.reshape(n, H, d), p["o"]["kernel"],
                   precision)

    return _in_blocks(block, L, ATTEND_QUERY_BLOCK)


def feed_forward(u, p, precision: str):
    h = jax.nn.silu(_mm("ld,df->lf", u, p["gate"]["kernel"], precision)) \
        * _mm("ld,df->lf", u, p["up"]["kernel"], precision)
    return _mm("lf,fd->ld", h, p["down"]["kernel"], precision)


def forward_features(params, tokens, sizes: Dict[str, Any],
                     precision: str = "f32", break_: str = ""):
    """tokens [L] -> the final-normed features [L, D] of one sequence."""
    s, eps = sizes, sizes["rms_norm_eps"]
    L = tokens.shape[0]
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for i, kind in enumerate(s["layers"]):
        p = params[f"layer_{i}"]
        u = _rms(x, p["mixer_norm"]["scale"], eps)
        if kind == "mamba":
            x = x + mamba_mixer(u, p["mixer"], s, precision, break_)
        elif break_ != "no_attention":
            x = x + attention_mixer(u, p["mixer"], s, precision)
        u = _rms(x, p["mlp_norm"]["scale"], eps)
        x = x + _in_blocks(
            lambda lo, n: feed_forward(
                jax.lax.dynamic_slice_in_dim(u, lo, n), p["mlp"], precision),
            L, 1024)
    return _rms(x, params["final_norm"]["scale"], eps)


def logits_fn(params, tokens, sizes, precision: str = "f32",
              break_: str = ""):
    """tokens [B, L] -> logits [B, L, V] float32 (small sizes: the
    tests; the runners go through the blocked functions below)."""
    return jax.lax.map(
        lambda t: _mm("ld,vd->lv", forward_features(params, t, sizes,
                                                    precision, break_),
                      params["tok_emb"], precision), tokens)


def _head_blocks(params, feats, fn, precision):
    """``fn(logits block [n, V], start, n)`` over blocks of positions."""
    return _in_blocks(
        lambda lo, n: fn(_mm("ld,vd->lv",
                             jax.lax.dynamic_slice_in_dim(feats, lo, n),
                             params["tok_emb"], precision), lo, n),
        feats.shape[0], 256)


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
    its own forward pass: a block of 256 queries against 10,240 keys is 20
    heads x 10 MB of float32 scores; what lies past a request's end is
    causal from it and only costs time)."""
    return min(-(-longest // 256) * 256, max(sizes["n_positions"], longest))


# -- counts -----------------------------------------------------------------

def layer_counts(sizes: Dict[str, Any]) -> Tuple[int, int]:
    """(state-space layers, attention layers)."""
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
        * inner_width(sizes) * 2


def cache_bytes_per_token(sizes: Dict[str, Any], bytes_per_el: int = 2
                          ) -> Dict[str, float]:
    """What one token leaves in the position-indexed leaves: K and V of the
    attention layers' key-value heads."""
    return {"kv": layer_counts(sizes)[1] * 2 * sizes["num_key_value_heads"]
            * sizes["head_dim"] * bytes_per_el}


def state_step_cost(sizes: Dict[str, Any], rows: float) -> tuple:
    """(operations, bytes from HBM) of ONE state-space layer's decode step
    over ``rows`` LIVE rows: each number of a row's state decayed (the
    product ``dt A``, its exponential and the product with the state: 3),
    added to (the outer product and the sum: 2) and read out (2); the
    state read once and written once. x, dt, B, C and y (a few KB a row)
    are left out."""
    n = state_numbers(sizes)
    return 7.0 * n * rows, 8.0 * n * rows


def s6_scan_cost(sizes: Dict[str, Any], length: int) -> tuple:
    """(operations, bytes from HBM) of ONE state-space layer's scan over
    ``length`` positions: the recurrence's 7 operations a state number a
    position (as :func:`state_step_cost` counts them; one of them an
    exponential) and the skip's 2 a channel; ``x`` read in bfloat16, ``dt``
    and the gate ``z`` in float32, ``B`` and ``C`` in float32, ``y`` written
    in float32, the final state written once. The carried state stays in
    VMEM between chunks and is not priced as traffic."""
    C, N = inner_width(sizes), sizes["mamba_d_state"]
    ops = length * (7.0 * C * N + 2.0 * C)
    byts = length * (C * (2 + 4 + 4 + 4) + 2 * N * 4) + C * N * 4
    return ops, byts


def decode_step_bytes(param_bytes: int, sizes: Dict[str, Any], slots: float,
                      kv_bytes_per_el: int = 2, *, keys_kept=None,
                      keys_available=None, experts_hit=None) -> float:
    """Bytes one decode step with ``slots`` LIVE rows must read and write
    (the signature ``serve.decode_bw_share.ssm`` calls). Every parameter as
    stored, once (the tied table is read once, as the head: the rows a step
    embeds are a few KB); each live row's states read and written and its
    convolution rings read; in every attention layer K and V of the
    ``keys_kept`` positions the live rows attend (None: ``slots`` rows at
    full depth). The model has no routed experts: ``experts_hit`` is not
    used."""
    del keys_available, experts_hit
    if keys_kept is None:
        keys_kept = slots * sizes["n_positions"]
    return (param_bytes
            + 2.0 * slots * state_bytes_per_slot(sizes)
            + slots * conv_bytes_per_slot(sizes)
            + keys_kept * cache_bytes_per_token(sizes, kv_bytes_per_el)["kv"])
