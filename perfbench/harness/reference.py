"""The plain reference: GPT-2 in straightforward ``jax.numpy``, float32,
matrix multiplications at ``highest`` precision. No kernels, no cache, no
batching tricks. It imports nothing of the program and reads only the
benchmark's own weights (``weights.make_params(..., stacked=True)``).

It follows Radford et al. 2019 (pre-LN blocks, learned positions, tanh
GELU, tied head). Departures, which follow the program under test:
LayerNorm epsilon is 1e-6 (flax's default) where GPT-2 has 1e-5, and
there is no dropout (the cells run with dropout 0).

``precision`` selects the control: the same arithmetic with the matmul
operands rounded to a lower precision, which is what a later PR would be
tempted to do. "f32" is the reference proper; "bf16" is the
configuration's stated compute precision; "fp8" (e4m3, per-tensor
scaled) is the nearest precision below it and must come out NOT correct.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

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
    """Follow the first ``len(batches)`` Adam steps from ``make_p0()``.
    Returns losses, the first gradient's per-leaf norms, and the per-leaf
    norms of the parameters' change over all the steps."""
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
            grad_norms = jax.device_get(gn)
    del mu, nu
    delta = jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(
        jnp.subtract, a, b)))(params, make_p0())
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": jax.device_get(delta)}


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
