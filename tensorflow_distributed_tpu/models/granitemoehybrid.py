"""The ``granitemoehybrid`` family (the source's ``model_type``): Mamba-2
STATE-SPACE layers, whose cache is a fixed-size float32 state and a ring of
the convolution's last inputs a slot, beside NoPE grouped-query attention
layers, whose cache is K and V a position; in every layer a softmax-routed
expert layer beside a shared expert; four muP-style multipliers. Served as
ONE CHIP'S SHARE of a deployment: a run of consecutive published layers,
and of each of them the routed experts this chip holds.

A model of this family is a list of layer kinds read from the source's own
``layer_types`` (``num_hidden_layers`` entries from ``first_layer_held``):
the parameters AND the decode cache are built from that list. Hidden ``D``,
RMSNorm eps from the source, no bias but the convolution's:

- **Model.** ``x0 = embedding_multiplier E[tok]``; logits ``E rms(x_L) /
  logits_scaling`` (the embedding is the head). **Block.** ``h = x + r
  Mixer(rms(x))``, ``u = rms(h)``, ``x' = h + r (Routed(u) + Shared(u))``
  with ``r = residual_multiplier``.
- **``mamba``** (Mamba-2; ``H`` heads of ``P`` channels, a state of ``N``
  numbers a channel, ``G`` groups of ``B`` and ``C``: one here, eight in
  models/nemotron_h.py, which runs this mixer too): ``[z | xBC | dt] =
  W_in u``; ``xBC_t = silu(b + sum_j w_j xBC_{t-3+j})`` depthwise, zeros
  before the sequence, split into ``x_t [H, P]``, ``B_t [G, N]``, ``C_t
  [G, N]``; ``dt_t = softplus(dt_t + dt_bias)`` a head, ``A_h =
  -exp(A_log_h)``; for head ``h`` of group ``g = h // (H / G)``: ``S_t =
  exp(dt_t A_h) S_{t-1} + dt_t x_t B_{g,t}^T``, ``y_t = S_t C_{g,t} + D_h
  x_t`` in float32; ``y = rms(y silu(z)) g`` over each group's ``H P / G``
  channels (all ``H P`` here); then ``W_out``.
  What a slot keeps: ``state`` ``[B, N, H P]`` float32 (``S^T``: ``(head,
  channel)`` along the lanes, ops/state_space.py) and ``conv`` ``[B,
  d_conv, H P + 2 G N]``, a ring of the last ``d_conv`` pre-convolution
  rows, row ``position mod d_conv``.
- **``attention``.** Query heads over fewer key-value heads, no rotation and
  no position signal at all (``position_embedding_type: nope``), scores
  times ``attention_multiplier`` (NOT ``head_dim^-1/2``), causal softmax in
  float32, ``W_o``. A slot keeps ``kv`` ``[B, max_len, 2 G d]``.
- **Experts.** ``l = W_r u`` in float32 over ALL published experts; the
  ``num_experts_per_tok`` largest LOGITS (ties to the lower index); weights
  the softmax over THOSE logits (:func:`route`: not the latent family's
  sigmoid, bias-picked, group-limited router). This chip computes the pairs
  whose expert it holds (``experts_held``; ``ops.latent_attention
  .held_experts``, dropless) under the weights of all the picked, and the
  shared expert whole. No exchange and nothing stands in for absent chips.

**A state cannot be rewritten** (models/minicpm_sala.py says why): the
cache carries ``state_pos`` ``[B]``, a step at position ``p`` folds iff ``p
== state_pos`` and reads the state either way, and a prefill leaves the
state AT ``true_len`` (``prefill_true_len``). The ``conv`` ring is indexed
by position and CAN be rewritten, so it needs no stamp.

Parameters are stored bfloat16 and never materialised in float32. Served
only (the scan has no backward here, ROADMAP B2).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflow_distributed_tpu.models.glm_moe_dsa import (
    PARAM_DTYPE, Scale, Weight, _count, _mm, count_held_pairs,
    describe_moe_plan, experts_held_from, held_index, held_share,
    load_source, rms_norm, summarize_moe, swiglu)
from tensorflow_distributed_tpu.models.transformer import rope_rotate
from tensorflow_distributed_tpu.ops import hybrid_attention as hyb_ops
from tensorflow_distributed_tpu.ops import latent_attention as lat_ops
from tensorflow_distributed_tpu.ops import state_space as ops

LAYER_KINDS = ("mamba", "attention")


@dataclasses.dataclass(frozen=True)
class GraniteMoeHybridConfig:
    """Sizes under the SOURCE's key names (``config.json`` of
    ``model_type: granitemoehybrid``), plus what this chip holds."""
    vocab_size: int
    hidden_size: int
    # the width of ONE routed expert (the source has no key of its own)
    intermediate_size: int
    shared_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_n_groups: int
    num_experts_per_tok: int
    rms_norm_eps: float
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    max_position_embeddings: int
    layers: Tuple[str, ...]
    # The router's width: the PUBLISHED number of routed experts.
    router_experts: int
    # Ids (in [0, router_experts)) of the routed experts this chip holds.
    experts_held: Tuple[int, ...]
    compute_dtype: Any = jnp.bfloat16
    causal: bool = True

    @property
    def max_len(self) -> int:
        return self.max_position_embeddings

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_width(self) -> int:
        """Channels of the convolution: ``x`` and every group's ``B``
        and ``C``."""
        return self.mamba_inner + 2 * self.mamba_n_groups \
            * self.mamba_d_state

    @property
    def n_mamba(self) -> int:
        return sum(1 for k in self.layers if k == "mamba")

    @property
    def state_bytes_per_slot(self) -> int:
        return self.n_mamba * self.mamba_inner * self.mamba_d_state * 4

    @property
    def conv_bytes_per_slot(self) -> int:
        return self.n_mamba * self.mamba_d_conv * self.conv_width \
            * jnp.dtype(self.compute_dtype).itemsize


def layer_list(src: Dict[str, Any]) -> Tuple[str, ...]:
    """The kinds of the layers held: ``num_hidden_layers`` entries of the
    source's ``layer_types`` from ``first_layer_held`` (0 when absent)."""
    n, lo = int(src["num_hidden_layers"]), int(src.get("first_layer_held", 0))
    kinds = tuple(src["layer_types"][lo:lo + n])
    if len(kinds) != n or set(kinds) - set(LAYER_KINDS):
        raise ValueError(
            f"layers {lo}..{lo + n - 1} of layer_types "
            f"({len(src['layer_types'])} entries) must each be one of "
            f"{LAYER_KINDS}, got {kinds}")
    return kinds


def config_from_source(src: Dict[str, Any], **overrides
                       ) -> GraniteMoeHybridConfig:
    """A configuration from a dict of the source's ``config.json`` keys.
    ``num_local_experts`` counts the routed experts HELD here and
    ``experts_held`` names them; ``num_local_experts_published`` (the
    router's width) defaults to ``num_local_experts`` for a whole layer.
    What the equations above assume of the source's switches is checked,
    not ignored."""
    want = {"position_embedding_type": "nope",
            "tie_word_embeddings": True, "mamba_conv_bias": True,
            "mamba_proj_bias": False, "attention_bias": False,
            "hidden_act": "silu", "normalization_function": "rmsnorm"}
    differ = {k: src[k] for k, v in want.items() if src.get(k, v) != v}
    if differ:
        raise ValueError(f"granitemoehybrid is written down for {want}; "
                         f"the source says {differ}")
    held_n = int(src["num_local_experts"])
    width = int(src.get("num_local_experts_published", held_n))
    held = experts_held_from(src, held_n, width)
    kw = dict(
        vocab_size=int(src["vocab_size"]),
        hidden_size=int(src["hidden_size"]),
        intermediate_size=int(src["intermediate_size"]),
        shared_intermediate_size=int(src["shared_intermediate_size"]),
        num_attention_heads=int(src["num_attention_heads"]),
        num_key_value_heads=int(src["num_key_value_heads"]),
        mamba_n_heads=int(src["mamba_n_heads"]),
        mamba_d_head=int(src["mamba_d_head"]),
        mamba_d_state=int(src["mamba_d_state"]),
        mamba_d_conv=int(src["mamba_d_conv"]),
        mamba_n_groups=int(src.get("mamba_n_groups", 1)),
        num_experts_per_tok=int(src["num_experts_per_tok"]),
        rms_norm_eps=float(src["rms_norm_eps"]),
        embedding_multiplier=float(src["embedding_multiplier"]),
        residual_multiplier=float(src["residual_multiplier"]),
        attention_multiplier=float(src["attention_multiplier"]),
        logits_scaling=float(src["logits_scaling"]),
        max_position_embeddings=int(src["max_position_embeddings"]),
        layers=layer_list(src), router_experts=width, experts_held=held)
    kw.update(overrides)
    cfg = GraniteMoeHybridConfig(**kw)
    if cfg.mamba_inner != int(src["mamba_expand"]) * cfg.hidden_size:
        raise ValueError(
            f"mamba_n_heads x mamba_d_head = {cfg.mamba_inner} is not "
            f"mamba_expand x hidden_size")
    if cfg.mamba_n_heads % cfg.mamba_n_groups:
        raise ValueError(f"mamba_n_groups {cfg.mamba_n_groups} does not "
                         f"divide mamba_n_heads {cfg.mamba_n_heads}")
    if int(src.get("mamba_chunk_size", ops.SCAN_CHUNK)) != ops.SCAN_CHUNK:
        raise ValueError(f"the scan's chunks are {ops.SCAN_CHUNK} tokens")
    if cfg.num_attention_heads % cfg.num_key_value_heads or \
            cfg.hidden_size % cfg.num_attention_heads:
        raise ValueError("query heads divide into the key-value heads and "
                         "hidden_size into the query heads")
    if not 0 < cfg.num_experts_per_tok <= cfg.router_experts:
        raise ValueError("num_experts_per_tok exceeds the router's width")
    return cfg


# -- what every family with a state-space state shares ------------------------

def stamp_states(module: nn.Module, positions: jax.Array, decode: bool,
                 true_len):
    """The ``state_pos`` stamp of a model whose layers keep a state that
    cannot be rewritten (this family, models/nemotron_h.py, models/jamba
    .py), called from the model's own ``__call__``: positions [B, L] ->
    (``fold`` [B], ``live`` [B]) for a decode step (``L == 1``: a row at
    depth 0 is a free slot, an admitted row is at least one token deep; a
    live row folds its token iff its states do not hold it yet), (None,
    None) otherwise. A prefill stamps ``true_len`` (``L`` when None)."""
    if not decode:
        return None, None
    B, L = positions.shape
    held = module.variable("cache", "state_pos", jnp.zeros, (B,), jnp.int32)
    if L > 1:
        held.value = jnp.broadcast_to(jnp.asarray(
            L if true_len is None else true_len, jnp.int32), (B,))
        return None, None
    pos = positions[:, 0]
    live = pos > 0
    fold = live & (pos == held.value)
    held.value = jnp.where(fold, pos + 1, held.value)
    return fold, live


def count_state_step(module: nn.Module, pos, live, fold, n_ssm: int,
                     n_attention: int, max_len: int) -> None:
    """One decode step's counters of ``n_ssm`` state-space layers beside
    ``n_attention`` attention layers over ``kv`` leaves of ``max_len``
    positions: the live rows; the slot-rows whose state the step moved
    (the state step's loop runs once a live slot a layer), and of those
    the rows that folded their token (the others only read: a step
    computed again); the cached positions the live rows attend, one
    layer's; and what the attention layers' blocks cover over ALL slots
    (the live rows' blocks to their depth)."""
    n_live = jnp.sum(live, dtype=jnp.int32)
    _count(module, "live_rows", n_live)
    _count(module, "state_rows_stepped", n_ssm * n_live)
    _count(module, "state_rows_folded",
           n_ssm * jnp.sum(fold, dtype=jnp.int32))
    _count(module, "keys_attended", jnp.sum(jnp.where(live, pos + 1, 0)))
    _count(module, "positions_visited",
           n_attention * hyb_ops.gqa_attend_visits(pos, max_len))


def summarize_state_step(totals: Dict[str, Any], cfg, n_attention: int
                         ) -> Dict[str, Any]:
    """``serve_summary``'s part from :func:`count_state_step`'s counters
    summed over a run's decode steps, under the names the state-space
    readers know: the live rows, the slot-rows whose state a step moved
    split into those that folded their token and those that only read,
    the bytes of ``state`` and of ``conv`` a slot (``cfg``'s), the cached
    positions the attention layers' live rows attend (``attend_keys``
    one layer's, ``select_keys_kept`` over the layers) beside those the
    attends' blocks covered over all slots
    (``attend_positions_visited``, as exaone_moe counts them)."""
    stepped, folded = (int(totals["state_rows_stepped"]),
                       int(totals["state_rows_folded"]))
    keys = int(totals["keys_attended"])
    return {"decode_live_rows": int(totals["live_rows"]),
            "state_rows_stepped": stepped,
            "state_rows_folded": folded,
            "state_rows_reread": stepped - folded,
            "state_bytes_per_slot": cfg.state_bytes_per_slot,
            "conv_bytes_per_slot": cfg.conv_bytes_per_slot,
            "attend_keys": keys,
            "select_keys_kept": n_attention * keys,
            "attend_positions_visited": int(totals["positions_visited"])}


# -- the mixers ---------------------------------------------------------------

class Vector(nn.Module):
    """One vector parameter a head or a channel (``A_log``, ``dt_bias``,
    ``D``, the convolution's bias), bfloat16 unless the family keeps it in
    another ``dtype`` (models/jamba.py: float32)."""
    dim: int
    dtype: Any = PARAM_DTYPE

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("value", nn.initializers.zeros_init(),
                          (self.dim,), self.dtype)


class MambaMixer(nn.Module):
    """Mamba-2. ``fold`` [B]: the rows of a decode step whose states do
    not hold this token yet. ``cfg``: this family's configuration or
    another's with the same ``mamba_*`` fields (models/nemotron_h.py)."""
    cfg: Any

    @nn.compact
    def __call__(self, u, positions, decode: bool, true_len, fold):
        cfg = self.cfg
        dt_ = cfg.compute_dtype
        B, L, D = u.shape
        H, P, N, K, G = (cfg.mamba_n_heads, cfg.mamba_d_head,
                         cfg.mamba_d_state, cfg.mamba_d_conv,
                         cfg.mamba_n_groups)
        inner, width = cfg.mamba_inner, cfg.conv_width

        def groups(rows):                # [..., 2 G N] -> B, C [..., G, N]
            return (rows[..., :G * N].reshape(rows.shape[:-1] + (G, N)),
                    rows[..., G * N:].reshape(rows.shape[:-1] + (G, N)))

        w_in = Weight((D, inner + width + H), name="in_proj")()
        conv_w = Weight((K, width), name="conv1d")()
        conv_b = Vector(width, name="conv1d_bias")()
        dt_bias = Vector(H, name="dt_bias")().astype(jnp.float32)
        A = -jnp.exp(Vector(H, name="A_log")().astype(jnp.float32))
        skip = Vector(H, name="D")().astype(jnp.float32)
        proj = _mm("bld,de->ble", u, w_in, dt_)                # f32
        z = proj[..., :inner]
        xbc = proj[..., inner:inner + width].astype(dt_)
        dt = jax.nn.softplus(proj[..., inner + width:] + dt_bias)   # [B,L,H]
        shape = (B, N, inner)
        if decode and L == 1:
            pos = positions[:, 0]
            ring = self.variable("cache", "conv", jnp.zeros,
                                 (B, K, width), dt_)
            S = self.variable("cache", "state", jnp.zeros, shape,
                              jnp.float32)
            ring.value, act = ops.ssd_conv_step(ring.value, xbc[:, 0],
                                                conv_w, conv_b, pos)
            x = act[:, :inner].reshape(B, H, P)
            S.value, y = ops.ssd_state_step(
                S.value, x, dt[:, 0], A, *groups(act[:, inner:]), fold, pos)
            x, y = x[:, None], y[:, None]
        else:
            act, tail = ops.ssd_conv(xbc, conv_w, conv_b, true_len)
            if true_len is not None:
                # a bucket's padding neither decays a state nor enters it
                dt = jnp.where((jnp.arange(L) < jnp.asarray(
                    true_len, jnp.int32).reshape(-1, 1))[..., None], dt, 0.0)
            x = act[..., :inner].reshape(B, L, H, P)
            y, last = ops.ssd_chunk_scan(x, dt, A,
                                         *groups(act[..., inner:]))
            if decode:
                self.variable("cache", "conv", jnp.zeros, (B, K, width),
                              dt_).value = tail
                self.variable("cache", "state", jnp.zeros, shape,
                              jnp.float32).value = last
        y = y + skip[:, None] * x.astype(jnp.float32)
        # the gated norm: over each group's channels, one learned scale
        y = rms_norm((y.reshape(B, L, inner) * jax.nn.silu(z)).reshape(
            B, L, G, inner // G), Scale(inner, name="norm")().reshape(
                G, inner // G), cfg.rms_norm_eps).reshape(B, L, inner)
        return _mm("ble,ed->bld", y, Weight((inner, D), name="out_proj")(),
                   dt_)


class AttentionMixer(nn.Module):
    """Grouped-query attention (``cfg`` as :class:`MambaMixer`'s: the
    ``num_*_heads``, ``head_dim``, ``attention_multiplier`` and ``max_len``
    of any family that runs it). As this family, nemotron_h and jamba (a
    fourth family, whose 20 query heads share ONE key-value head: ``G`` 1)
    run it: no position signal at all, the whole context, a ``kv`` leaf a
    decode step attends through ``ops.hybrid_attention.gqa_decode_attend``
    (on the TPU the live rows' blocks up to each row's depth, in place).
    What models/exaone_moe.py adds, each off by default:

    - ``qk_norm_eps`` > 0: ``q`` and ``k`` through an RMSNorm over each
      head's ``head_dim`` numbers (``q_norm``, ``k_norm``: one learned
      scale each, shared by the heads), before any rotation;
    - ``rope_theta`` > 0: ``q`` and ``k`` rotated by position (halves,
      every dimension: ``models/transformer.py::rope_rotate``), so what
      the cache holds are ROTATED keys;
    - ``window`` w > 0: a query sees keys ``(t - w, t]``, and a slot keeps
      ``kv_ring`` ``[B, w, 2 G d]``, position p in row ``p mod w``, in
      place of ``kv`` ``[B, max_len, 2 G d]``; the prefill leaves the last
      ``w`` rows before ``true_len`` in it, and a decode step attends
      the ring slot-blind (``dense_decode_attend``).
    """
    cfg: Any
    qk_norm_eps: float = 0.0
    rope_theta: float = 0.0
    window: int = 0

    @nn.compact
    def __call__(self, u, positions, decode: bool, true_len=None):
        cfg = self.cfg
        dt = cfg.compute_dtype
        B, L, D = u.shape
        H, G, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        scale, W = cfg.attention_multiplier, self.window
        q = _mm("bld,dhe->blhe", u, Weight((D, H, d), name="q")(), dt)
        k = _mm("bld,dge->blge", u, Weight((D, G, d), name="k")(), dt)
        v = _mm("bld,dge->blge", u, Weight((D, G, d), name="v")(),
                dt).astype(dt)
        if self.qk_norm_eps:
            q = rms_norm(q, Scale(d, name="q_norm")(), self.qk_norm_eps)
            k = rms_norm(k, Scale(d, name="k_norm")(), self.qk_norm_eps)
        if self.rope_theta:
            q = rope_rotate(q, positions, self.rope_theta)    # f32 in, out
            k = rope_rotate(k, positions, self.rope_theta)
        q, k = q.astype(dt), k.astype(dt)
        w_o = Weight((H, d, D), name="o")()
        step = decode and L == 1
        if decode:
            rows = jnp.concatenate(
                [k.reshape(B, L, G * d), v.reshape(B, L, G * d)], -1)
            ckv = self.variable(
                "cache", "kv_ring" if W else "kv", jnp.zeros,
                (B, W or cfg.max_len, 2 * G * d), dt)
            if W and not step:
                ckv.value = hyb_ops.ring_rows(rows, true_len, W)
            else:
                at = positions[:, 0]
                ckv.value = lat_ops.write_rows(ckv.value, rows,
                                               at % W if W else at)
        if step:
            qg, at = q[:, 0].reshape(B, G, H // G, d), positions[:, 0]
            if W:
                # the rows written so far, all W of them once the ring
                # has wrapped
                o = hyb_ops.dense_decode_attend(
                    qg, ckv.value, jnp.minimum(at, W - 1), W, scale)
            else:
                o = hyb_ops.gqa_decode_attend(qg, ckv.value, at, scale)
            o = o.reshape(B, 1, H, d)
        else:
            # A fresh row: the new tokens ARE the whole context. The flash
            # forward takes one key and value a query head.
            def heads(x, rep):                    # [B,L,n,d] -> [B,n*rep,L,d]
                return jnp.repeat(x.transpose(0, 2, 1, 3), rep, axis=1)

            o = jax.vmap(lambda a, b, c: lat_ops.prefill_attend(
                a, b, c, None, scale, W))(heads(q, 1), heads(k, H // G),
                                          heads(v, H // G))
            o = o.transpose(0, 2, 1, 3)                        # [B,L,H,d]
        return _mm("blhe,hed->bld", o, w_o, dt)


# -- the expert layer ---------------------------------------------------------

def route(xs: jax.Array, w_r: jax.Array, k: int):
    """The source's router (``TopKGating``): logits in float32 over every
    published expert, the ``k`` largest LOGITS picked (ties to the lower
    index, as ``lax.top_k`` breaks them), weighted by the softmax over
    the picked logits alone. xs [N, D], w_r [D, E] -> (ids [N, k] int32,
    weights [N, k] f32, summing to 1 a token)."""
    logits = jnp.einsum("nd,de->ne", xs.astype(jnp.float32),
                        w_r.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top, ids = jax.lax.top_k(logits, k)
    return ids.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


class MoeLayer(nn.Module):
    """The held experts' part of the routed layer plus the shared
    expert."""
    cfg: GraniteMoeHybridConfig

    @nn.compact
    def __call__(self, u: jax.Array, live=None) -> jax.Array:
        cfg = self.cfg
        dt = cfg.compute_dtype
        B, L, D = u.shape
        E, F, Fs = (len(cfg.experts_held), cfg.intermediate_size,
                    cfg.shared_intermediate_size)
        w_r = Weight((D, cfg.router_experts), name="router")()
        gate = Weight((E, D, F), name="experts_gate")()
        up = Weight((E, D, F), name="experts_up")()
        down = Weight((E, F, D), name="experts_down")()
        xs = u.reshape(B * L, D)
        ids, weights = route(xs, w_r, cfg.num_experts_per_tok)
        local = held_index(ids, cfg)                          # [N,k], -1
        y = lat_ops.held_experts_once(xs, local, weights, gate, up, down,
                                      dt, held_share(cfg))
        if live is not None:
            count_held_pairs(self, local.reshape(B, -1), live, E)
        y = y + swiglu(xs, Weight((D, Fs), name="shared_gate")(),
                       Weight((D, Fs), name="shared_up")(),
                       Weight((Fs, D), name="shared_down")(), dt)
        return y.reshape(B, L, D)                              # f32


class Layer(nn.Module):
    cfg: GraniteMoeHybridConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, decode: bool, true_len, fold, live):
        cfg = self.cfg
        dt = cfg.compute_dtype
        D, r = cfg.hidden_size, cfg.residual_multiplier
        u = rms_norm(x, Scale(D, name="mixer_norm")(),
                     cfg.rms_norm_eps).astype(dt)
        if self.kind == "mamba":
            y = MambaMixer(cfg, name="mixer")(u, positions, decode,
                                              true_len, fold)
        else:
            y = AttentionMixer(cfg, name="mixer")(u, positions, decode)
        x = x + r * y
        u = rms_norm(x, Scale(D, name="moe_norm")(),
                     cfg.rms_norm_eps).astype(dt)
        return x + r * MoeLayer(cfg, name="moe")(u, live)


class GraniteMoeHybridLM(nn.Module):
    """tokens [B, L] -> logits [B, L, V] f32 (``logits_at`` [B]: only at
    that position of each row, [B, 1, V]). With ``decode=True`` the call
    goes through the ``cache`` collection: ``L > 1`` prefills a FRESH row
    (positions start at 0; ``true_len``: the tokens that count, the rest
    of the row is a bucket's padding), ``L == 1`` is one decode step at
    each row's own position."""

    cfg: GraniteMoeHybridConfig
    mesh: Any = None
    # serve/engine.py: the prefill program asks for the last logits only
    # and hands the model the prompt's true length; the decode program
    # returns what a step counted (the ``stats`` collection below).
    last_logits_only = True
    prefill_true_len = True
    decode_stats = True

    @nn.compact
    def __call__(self, tokens: jax.Array, *, train: bool = False,
                 decode: bool = False,
                 positions: Optional[jax.Array] = None,
                 logits_at: Optional[jax.Array] = None,
                 true_len: Optional[jax.Array] = None):
        cfg = self.cfg
        if train:
            raise ValueError(
                "the granitemoehybrid family has no training path")
        B, L = tokens.shape
        if positions is None:
            if decode:
                raise ValueError("decode=True requires positions")
            positions = jnp.arange(L)[None, :]
        positions = jnp.broadcast_to(positions.astype(jnp.int32), (B, L))
        emb = self.param("tok_emb", nn.initializers.normal(stddev=0.02),
                         (cfg.vocab_size, cfg.hidden_size), PARAM_DTYPE)
        # float32 residual stream, as the other served families': only
        # matmul OPERANDS are the compute dtype.
        x = cfg.embedding_multiplier * emb[tokens].astype(jnp.float32)
        fold, live = stamp_states(self, positions, decode, true_len)
        counting = live is not None and self.is_mutable_collection("stats")
        if counting:
            count_state_step(self, positions[:, 0], live, fold, cfg.n_mamba,
                             len(cfg.layers) - cfg.n_mamba, cfg.max_len)
        for i, kind in enumerate(cfg.layers):
            x = Layer(cfg, kind, name=f"layer_{i}")(
                x, positions, decode, true_len, fold,
                live if counting else None)
        if logits_at is not None:
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(logits_at.astype(jnp.int32),
                                    (B,))[:, None, None], axis=1)
        x = rms_norm(x, Scale(cfg.hidden_size, name="final_norm")(),
                     cfg.rms_norm_eps) / cfg.logits_scaling
        return _mm("bld,vd->blv", x, emb, cfg.compute_dtype)

    def moe_plan(self, num_slots: int, buckets) -> Dict[str, Any]:
        return describe_moe_plan(self.cfg, self.cfg.intermediate_size,
                                 num_slots, buckets)

    def summarize_stats(self, totals: Dict[str, Any], decode_steps: int
                        ) -> Dict[str, Any]:
        """``serve_summary``'s counters from the ``stats`` collection
        summed over a run's decode steps: the state steps' and the
        attends' (:func:`summarize_state_step`) and the expert layers'
        pairs as the latent family counts them."""
        out = summarize_state_step(totals, self.cfg,
                                   len(self.cfg.layers) - self.cfg.n_mamba)
        out.update(summarize_moe(totals, decode_steps))
        return out


def granitemoehybrid_lm(mesh=None, size: str = "", source: str = "",
                        compute_dtype=jnp.bfloat16, max_len: int = 0,
                        vocab_size: int = 0) -> GraniteMoeHybridLM:
    """The family's builder: ``source`` (``--model-config``) is a JSON
    file of the source's keys, the one way its sizes come in."""
    if size or not source:
        raise ValueError(
            "granitemoehybrid takes its sizes from --model-config <json of "
            "the source's config.json keys>[#dotted.key] and has no "
            f"--model-size preset (got size={size!r}, "
            f"model_config={source!r})")
    over: Dict[str, Any] = {"compute_dtype": compute_dtype}
    if max_len:
        over["max_position_embeddings"] = int(max_len)
    if vocab_size:
        over["vocab_size"] = int(vocab_size)
    if mesh is not None and any(
            n > 1 for ax, n in dict(mesh.shape).items() if ax != "data"):
        raise ValueError("granitemoehybrid serves one chip's share: it has "
                         "no sharded form (a pure data mesh replicates it)")
    return GraniteMoeHybridLM(config_from_source(dict(load_source(source)),
                                                 **over))
