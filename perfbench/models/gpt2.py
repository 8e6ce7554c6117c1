"""GPT-2 as the benchmark knows it: the sizes it reads from a configuration,
its weights from ``--seed``, its plain reference, and the counts its
per-layer readers need. A configuration names this file with
``"model": "gpt2"``; the harness reaches it through ``cell.model`` and a
reader through ``ctx.model`` (perfbench/README.md, "Adding things").

**Weights.** Made on the device in one jitted call. The benchmark owns
them: the program is handed this tree in the layout its model expects, the
plain reference reads the same numbers (``stacked=True`` gives the
per-layer leaves stacked on a leading layer axis, which is how the
reference scans over layers). Nothing the program initialised is used.
Every leaf is random, biases and norms too (std 0.02 around 0, norm
scales around 1), so that a path that dropped a bias or a scale would
show in ``correct``.

**The plain reference.** GPT-2 in straightforward ``jax.numpy``, float32,
matrix multiplications at ``highest`` precision. No kernels, no cache, no
batching tricks. It imports nothing of the program and reads only the
weights above. It follows Radford et al. 2019 (pre-LN blocks, learned
positions, tanh GELU, tied head). Departures, which follow the program
under test: LayerNorm epsilon is 1e-6 (flax's default) where GPT-2 has
1e-5, and there is no dropout (the cells run with dropout 0).

``precision`` selects the control: the same arithmetic with the matmul
operands rounded to a lower precision, which is what a later PR would be
tempted to do. "f32" is the reference proper; "bf16" is the
configuration's stated compute precision; "fp8" (e4m3, per-tensor
scaled) is the nearest precision below it and must come out NOT correct.

**Counts.** Operations and bytes the algorithm needs, from the sizes (the
program has a copy of the first formula in ``observe/mfu.py``; later PRs
may change the program, never this file). A multiply-add counts as two
operations. Recomputed operations do not count.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

# The source's own key names, read from the configuration file or from its
# ``rehearsal.sizes``. Every model returns ``vocab_size`` and
# ``n_positions``; the rest are this model's own.
SIZE_KEYS = ("n_embd", "n_layer", "n_head", "n_inner", "n_positions",
             "vocab_size")


def sizes(src: Dict[str, Any]) -> Dict[str, int]:
    return {k: int(src[k]) for k in SIZE_KEYS}


# -- weights ----------------------------------------------------------------

STD = 0.02
# (path inside a layer, shape as a function of the sizes, centre)
_LAYER_LEAVES = (
    (("ln1", "scale"), lambda d, h, f: (d,), 1.0),
    (("ln1", "bias"), lambda d, h, f: (d,), 0.0),
    (("attn", "qkv", "kernel"), lambda d, h, f: (d, 3, h, d // h), 0.0),
    (("attn", "qkv", "bias"), lambda d, h, f: (3, h, d // h), 0.0),
    (("attn", "out", "kernel"), lambda d, h, f: (h, d // h, d), 0.0),
    (("attn", "out", "bias"), lambda d, h, f: (d,), 0.0),
    (("ln2", "scale"), lambda d, h, f: (d,), 1.0),
    (("ln2", "bias"), lambda d, h, f: (d,), 0.0),
    (("mlp", "up", "kernel"), lambda d, h, f: (d, f), 0.0),
    (("mlp", "up", "bias"), lambda d, h, f: (f,), 0.0),
    (("mlp", "down", "kernel"), lambda d, h, f: (f, d), 0.0),
    (("mlp", "down", "bias"), lambda d, h, f: (d,), 0.0),
)
_TOP_LEAVES = (
    (("tok_emb", "embedding"), lambda s: (s["vocab_size"], s["n_embd"]), 0.0),
    (("pos_emb", "embedding"),
     lambda s: (s["n_positions"], s["n_embd"]), 0.0),
    (("ln_f", "scale"), lambda s: (s["n_embd"],), 1.0),
    (("ln_f", "bias"), lambda s: (s["n_embd"],), 0.0),
)


def _put(tree: Dict[str, Any], path, leaf) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = leaf


def _leaf(key, shape, centre):
    return centre + STD * jax.random.normal(key, shape, jnp.float32)


def make_params(key: jax.Array, sizes: Dict[str, int],
                stacked: bool = False) -> Dict[str, Any]:
    """The whole tree (trace this under jit): the program's layout, or
    with ``stacked`` the reference's."""
    d, h, f = sizes["n_embd"], sizes["n_head"], sizes["n_inner"]
    n = sizes["n_layer"]
    out: Dict[str, Any] = {}
    for i, (path, shape, centre) in enumerate(_TOP_LEAVES):
        _put(out, path, _leaf(jax.random.fold_in(key, i), shape(sizes),
                              centre))
    for j, (path, shape, centre) in enumerate(_LAYER_LEAVES):
        k_leaf = jax.random.fold_in(key, 100 + j)
        shp = shape(d, h, f)
        if stacked:
            _put(out.setdefault("blocks", {}), path, jax.vmap(
                lambda li: _leaf(jax.random.fold_in(k_leaf, li), shp,
                                 centre))(jnp.arange(n)))
        else:
            for li in range(n):
                _put(out.setdefault(f"layer_{li}", {}), path,
                     _leaf(jax.random.fold_in(k_leaf, li), shp, centre))
    return out


def stack_like_reference(tree: Dict[str, Any], n_layer: int
                         ) -> Dict[str, Any]:
    """Program-layout tree -> the reference's stacked layout (used to
    compare a program-side quantity leaf by leaf with the reference's)."""
    out = {k: v for k, v in tree.items() if not k.startswith("layer_")}
    layers = [tree[f"layer_{i}"] for i in range(n_layer)]
    out["blocks"] = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *layers)
    return out


# -- the plain reference ----------------------------------------------------

LN_EPS = 1e-6
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
PRECISIONS = ("f32", "bf16", "fp8")


def _round_operand(x, precision: str):
    """Round a matmul operand to ``precision``. The rounding is straight
    through for the gradient (the cotangent stays float32), so a lower
    precision changes the values the matmuls see, forward and backward,
    and nothing else."""
    if precision == "f32":
        return x
    if precision == "bf16":
        r = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
        r = (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    else:
        raise ValueError(f"precision {precision!r}; have {PRECISIONS}")
    return x + jax.lax.stop_gradient(r - x)


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _round_operand(a, precision),
                      _round_operand(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _ln(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def _block(x, p, precision: str):
    """One pre-LN block on x [B, L, D]."""
    B, L, D = x.shape
    y = _ln(x, p["ln1"])
    qkv = _mm("bld,dthe->blthe", y, p["attn"]["qkv"]["kernel"],
              precision) + p["attn"]["qkv"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]      # [B, L, H, Dh]
    s = _mm("bqhe,bkhe->bhqk", q, k, precision) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhe->bqhe", a, v, precision)
    x = x + _mm("bqhe,hed->bqd", o, p["attn"]["out"]["kernel"],
                precision) + p["attn"]["out"]["bias"]
    y = _ln(x, p["ln2"])
    y = _gelu_tanh(_mm("bld,df->blf", y, p["mlp"]["up"]["kernel"],
                       precision) + p["mlp"]["up"]["bias"])
    return x + _mm("blf,fd->bld", y, p["mlp"]["down"]["kernel"],
                   precision) + p["mlp"]["down"]["bias"]


def logits_fn(params: Dict[str, Any], tokens, precision: str = "f32"):
    """tokens [B, L] int -> logits [B, L, V] float32."""
    L = tokens.shape[1]
    emb = params["tok_emb"]["embedding"]
    x = emb[tokens] + params["pos_emb"]["embedding"][None, :L]
    body = jax.checkpoint(
        lambda h, p: (_block(h, p, precision), None))
    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = _ln(x, params["ln_f"])
    return _mm("bld,vd->blv", x, emb, precision)


def _block_loss_sum(params, tokens, targets, mask, precision):
    logits = logits_fn(params, tokens, precision)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum((logz - picked) * mask)


def loss_fn(params, batch, precision: str = "f32", rows_per_block: int = 2):
    """Mean masked next-token cross-entropy of a {tokens, targets, mask}
    batch, computed in blocks of rows so that it fits beside anything."""
    B = batch["tokens"].shape[0]
    rb = rows_per_block if B % rows_per_block == 0 else 1
    split = lambda a: a.reshape((B // rb, rb) + a.shape[1:])
    f = jax.checkpoint(functools.partial(_block_loss_sum,
                                         precision=precision))
    sums = jax.lax.map(
        lambda b: f(params, b[0], b[1], b[2]),
        (split(batch["tokens"]), split(batch["targets"]),
         split(batch["mask"])))
    return jnp.sum(sums) / jnp.sum(batch["mask"])


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """{leaf path: norm}; a stacked block leaf gives one norm a layer."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.startswith("blocks/"):
            out[name] = jnp.sqrt(jnp.sum(
                jnp.square(leaf), axis=tuple(range(1, leaf.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(leaf)))
    return out


def _by_program_name(norms: Dict[str, Any]) -> Dict[str, float]:
    """The stacked ``blocks/...`` norms, one entry a layer, under the
    program's names (``layer_3/attn/out/bias``), on the host."""
    out = {}
    for name, v in jax.device_get(norms).items():
        if name.startswith("blocks/"):
            for i, x in enumerate(v):
                out[f"layer_{i}/{name[len('blocks/'):]}"] = float(x)
        else:
            out[name] = float(v)
    return out


@functools.partial(jax.jit, static_argnames=("precision",),
                   donate_argnums=(0, 1, 2))
def adam_step(params, mu, nu, t, batch, lr, precision: str = "f32"):
    """One Adam step as optax.adam computes it. Returns the new state,
    the loss and the per-leaf norms of the gradient."""
    loss, g = jax.value_and_grad(loss_fn)(params, batch, precision)
    mu = jax.tree_util.tree_map(
        lambda m, x: ADAM_B1 * m + (1 - ADAM_B1) * x, mu, g)
    nu = jax.tree_util.tree_map(
        lambda v, x: ADAM_B2 * v + (1 - ADAM_B2) * x * x, nu, g)
    c1 = 1 - ADAM_B1 ** t
    c2 = 1 - ADAM_B2 ** t
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        params, mu, nu)
    return params, mu, nu, loss, leaf_norms(g)


def follow_training(make_p0, batches, lr: float, precision: str = "f32"
                    ) -> Dict[str, Any]:
    """Follow the first ``len(batches)`` Adam steps from ``make_p0()``
    (the weights in the reference's layout). Returns losses, the first
    gradient's norms and the norms of the parameters' change over all the
    steps, each a {program leaf name: norm}."""
    params = make_p0()
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu = zeros(), zeros()
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        params, mu, nu, loss, gn = adam_step(
            params, mu, nu, jnp.float32(i + 1), batch, jnp.float32(lr),
            precision=precision)
        losses.append(float(loss))
        if i == 0:
            grad_norms = _by_program_name(gn)
    del mu, nu
    delta = jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, a, b)))(params, make_p0())
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": _by_program_name(delta)}


@functools.partial(jax.jit, static_argnames=("precision",))
def served_token_gaps(params, seqs, precision: str = "f32"):
    """seqs [B, L] (prompt, served tokens, padding). For every position t
    the reference predicts seqs[t+1]: returns (gap, top) [B, L-1] where
    gap is how far the reference's logit of the token that follows lies
    below the reference's best, and top is the reference's own argmax.
    With ``precision`` below f32 ``top`` is what that precision would have
    served; score it with :func:`gaps_of`."""
    logits = logits_fn(params, seqs, precision)[:, :-1]
    best = jnp.max(logits, -1)
    nxt = jnp.take_along_axis(logits, seqs[:, 1:, None], -1)[..., 0]
    return best - nxt, jnp.argmax(logits, -1).astype(jnp.int32)


@jax.jit
def gaps_of(params, seqs, chosen):
    """The f32 reference's gap of ``chosen`` [B, L-1], a token proposed at
    every position given the context ``seqs[:, :t+1]``."""
    logits = logits_fn(params, seqs, "f32")[:, :-1]
    best = jnp.max(logits, -1)
    c = jnp.take_along_axis(logits, chosen[..., None], -1)[..., 0]
    return best - c


def reference_positions(sizes: Dict[str, int], longest: int) -> int:
    """The length the serve runner pads a sampled sequence to: the whole
    context, whatever the sample's longest (one compiled shape a cell)."""
    return sizes["n_positions"]


# -- counts -----------------------------------------------------------------

def matmul_params(sizes: Dict[str, int]) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: qkv, out, up, down per layer, and the (tied) head. Embedding
    lookups, biases and norms are not multiplications."""
    d_model, d_ff = sizes["n_embd"], sizes["n_inner"]
    per_layer = 3 * d_model * d_model + d_model * d_model \
        + 2 * d_model * d_ff
    return sizes["n_layer"] * per_layer + sizes["vocab_size"] * d_model


def train_flops_per_token(sizes: Dict[str, int], seq_len: int) -> float:
    """Forward plus backward, per trained token: 6 per matmul parameter
    (2 forward, 4 backward) plus causal attention. Attention forward is
    QK^T and PV, 2 * 2 * L * d_model per token per layer over the full
    square; the causal half is what the algorithm needs; backward is
    twice the forward."""
    dense = 6.0 * matmul_params(sizes)
    attn_fwd = 4.0 * seq_len * sizes["n_embd"] * sizes["n_layer"] / 2.0
    return dense + 3.0 * attn_fwd


def decode_step_bytes(param_bytes: int, sizes: Dict[str, int], slots: int,
                      kv_bytes_per_el: int = 2) -> float:
    """Bytes one decode step must read: every parameter as stored, once,
    plus the keys and values the program attends over. The dense slot
    engine attends over the whole ``[slots, n_positions]`` cache whatever
    each slot's depth, so that is what is counted (ROADMAP A4)."""
    kv = 2.0 * sizes["n_layer"] * sizes["n_embd"] * kv_bytes_per_el \
        * slots * sizes["n_positions"]
    return param_bytes + kv


def param_count(sizes: Dict[str, int]) -> int:
    """All parameters of a tied GPT-2: embeddings, positions, per-layer
    kernels, biases and norms, final norm."""
    d_model, d_ff = sizes["n_embd"], sizes["n_inner"]
    per_layer = (3 * d_model * d_model + 3 * d_model      # qkv
                 + d_model * d_model + d_model            # out
                 + d_model * d_ff + d_ff                  # up
                 + d_ff * d_model + d_model               # down
                 + 4 * d_model)                           # two norms
    return (sizes["vocab_size"] * d_model
            + sizes["n_positions"] * d_model
            + sizes["n_layer"] * per_layer + 2 * d_model)
