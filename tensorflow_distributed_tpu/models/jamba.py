"""The ``jamba`` family (the source's ``model_type``): Mamba-1 SELECTIVE-SCAN
layers, whose recurrence has a decay for every channel and state number a
token (not one a head, as Mamba-2's in models/granitemoehybrid.py) and whose
``dt``, ``B`` and ``C`` pass through RMSNorms, beside multi-query attention
layers without any position signal; a dense SwiGLU feed-forward in every
layer; the embedding tied to the head. Served WHOLE on one chip: every
published layer and the whole vocabulary.

A model of this family is a list of layer kinds made from the source's
``attn_layer_period`` and ``attn_layer_offset`` (layer ``i`` is an attention
layer iff ``i mod period == offset``): the parameters AND the decode cache
are built from that list. Hidden ``D``, RMSNorm with a learned scale, eps
from the source, no bias but the convolution's and ``dt``'s:

- **Model.** ``x0 = E[tok]``; **layer** ``h = x + Mixer(rms_in(x))``, ``y = h
  + FF(rms_ff(h))``; after the last layer ``rms_final``, then logits ``= y
  E^T`` (the table it embeds with, held once). No position signal anywhere.
- **``mamba``** (``d_inner = mamba_expand D`` channels, ``N = mamba_d_state``
  numbers a channel, ``R = mamba_dt_rank``): ``[x | z] = u W_in``; ``x_t =
  silu(b + sum_j w_j x_{t-3+j})`` depthwise, zeros before the sequence
  (``ops.state_space.ssd_conv``: granite's convolution and its ring);
  ``[r | B | C] = x W_x``, each through its own RMSNorm (``dt_norm``,
  ``b_norm``, ``c_norm``); ``dt = softplus(r W_dt + b_dt)`` float32 a
  channel; ``A = -exp(A_log)``; for channel ``c`` and state number ``n``:
  ``h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c]
  B_t[n]``, ``y_t[c] = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]`` in float32
  (``ops.state_space.s6_chunk_scan`` / ``s6_state_step``); out ``(y
  silu(z)) W_out``.
  What a slot keeps: ``state`` ``[B, N, d_inner]`` float32 (channels along
  the lanes: ``N`` is 16 where a lane tile is 128, so ``[d_inner, N]``
  would use an eighth of every tile; ``A_log`` is held ``[N, d_inner]``
  for the same reason) and ``conv`` ``[B, d_conv, d_inner]``, a ring of the
  last ``d_conv`` pre-convolution rows, row ``position mod d_conv``.
- **``attention``** (models/granitemoehybrid.py's ``AttentionMixer`` as it
  stands): ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads (one, in the published model),
  no rotation, no norm a head, no window, scores times ``head_dim^-1/2``,
  causal softmax in float32, ``W_o``. A slot keeps ``kv`` ``[B, max_len, 2
  G d]``.
- **Feed-forward.** ``W_d (silu(u W_g) * (u W_u))`` (models/glm_moe_dsa
  .py's ``DenseMlp``). A routed Jamba (``num_experts`` above 1) is refused
  by name.

**A state cannot be rewritten** (models/minicpm_sala.py says why): the
cache carries ``state_pos`` and a prefill leaves the state AT ``true_len``
(models/granitemoehybrid.py::stamp_states, ``prefill_true_len``).

Matrices are stored bfloat16 and never materialised in float32; ``A_log``,
``D``, ``dt``'s bias and the norms' scales are float32. Served only (the
scan has no backward here, ROADMAP B2).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflow_distributed_tpu.models.glm_moe_dsa import (
    PARAM_DTYPE, DenseMlp, Scale, Weight, _mm, load_source, rms_norm)
from tensorflow_distributed_tpu.models.granitemoehybrid import (
    AttentionMixer, Vector, count_state_step, stamp_states,
    summarize_state_step)
from tensorflow_distributed_tpu.ops import state_space as ops

LAYER_KINDS = ("mamba", "attention")


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    """Sizes under the SOURCE's key names (``config.json`` of
    ``model_type: jamba``), and under the names the shared modules read
    (``AttentionMixer``, ``DenseMlp``)."""
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_dt_rank: int
    mamba_expand: int
    rms_norm_eps: float
    max_position_embeddings: int
    layers: Tuple[str, ...]
    compute_dtype: Any = jnp.bfloat16
    causal: bool = True

    @property
    def max_len(self) -> int:
        return self.max_position_embeddings

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def attention_multiplier(self) -> float:
        return self.head_dim ** -0.5

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def n_mamba(self) -> int:
        return sum(1 for k in self.layers if k == "mamba")

    @property
    def n_attention(self) -> int:
        return len(self.layers) - self.n_mamba

    @property
    def state_bytes_per_slot(self) -> int:
        return self.n_mamba * self.mamba_inner * self.mamba_d_state * 4

    @property
    def conv_bytes_per_slot(self) -> int:
        return self.n_mamba * self.mamba_d_conv * self.mamba_inner \
            * jnp.dtype(self.compute_dtype).itemsize


def layer_list(src: Dict[str, Any]) -> Tuple[str, ...]:
    """The kind of every layer: attention where ``i mod attn_layer_period
    == attn_layer_offset``, Mamba elsewhere."""
    n, period, offset = (int(src["num_hidden_layers"]),
                         int(src["attn_layer_period"]),
                         int(src["attn_layer_offset"]))
    if not 0 <= offset < period:
        raise ValueError(f"attn_layer_offset {offset} is no layer of a "
                         f"period of {period}")
    return tuple("attention" if i % period == offset else "mamba"
                 for i in range(n))


def config_from_source(src: Dict[str, Any], **overrides) -> JambaConfig:
    """A configuration from a dict of the source's ``config.json`` keys.
    What the equations above assume of the source's switches is checked,
    not ignored."""
    if int(src.get("num_experts", 1)) != 1:
        raise ValueError(
            f"jamba is written down with a dense feed-forward in every "
            f"layer (num_experts 1); the source says num_experts "
            f"{src['num_experts']}: a routed Jamba is not served")
    want = {"tie_word_embeddings": True, "mamba_conv_bias": True,
            "mamba_proj_bias": False, "hidden_act": "silu",
            "sliding_window": None}
    differ = {k: src[k] for k, v in want.items() if src.get(k, v) != v}
    if differ:
        raise ValueError(f"jamba is written down for {want}; the source "
                         f"says {differ}")
    kw = dict(
        vocab_size=int(src["vocab_size"]),
        hidden_size=int(src["hidden_size"]),
        intermediate_size=int(src["intermediate_size"]),
        num_attention_heads=int(src["num_attention_heads"]),
        num_key_value_heads=int(src["num_key_value_heads"]),
        mamba_d_state=int(src["mamba_d_state"]),
        mamba_d_conv=int(src["mamba_d_conv"]),
        mamba_dt_rank=int(src["mamba_dt_rank"]),
        mamba_expand=int(src["mamba_expand"]),
        rms_norm_eps=float(src["rms_norm_eps"]),
        max_position_embeddings=int(src["max_position_embeddings"]),
        layers=layer_list(src))
    kw.update(overrides)
    cfg = JambaConfig(**kw)
    if cfg.num_attention_heads % cfg.num_key_value_heads or \
            cfg.hidden_size % cfg.num_attention_heads:
        raise ValueError("query heads divide into the key-value heads and "
                         "hidden_size into the query heads")
    return cfg


class S6Mixer(nn.Module):
    """Mamba-1. ``fold`` [B]: the rows of a decode step whose states do
    not hold this token yet."""
    cfg: JambaConfig

    @nn.compact
    def __call__(self, u, positions, decode: bool, true_len, fold):
        cfg = self.cfg
        dt_, f32 = cfg.compute_dtype, jnp.float32
        B, L, D = u.shape
        inner, N, K, R = (cfg.mamba_inner, cfg.mamba_d_state,
                          cfg.mamba_d_conv, cfg.mamba_dt_rank)
        eps = cfg.rms_norm_eps
        w_in = Weight((D, 2 * inner), name="in_proj")()
        conv_w = Weight((K, inner), name="conv1d")()
        conv_b = Vector(inner, name="conv1d_bias")()
        w_x = Weight((inner, R + 2 * N), name="x_proj")()
        w_dt = Weight((R, inner), name="dt_proj")()
        dt_bias = Vector(inner, dtype=f32, name="dt_bias")()
        A = -jnp.exp(self.param("A_log", nn.initializers.zeros_init(),
                                (N, inner), f32))
        skip = Vector(inner, dtype=f32, name="D")()
        proj = _mm("bld,de->ble", u, w_in, dt_)                # f32
        x, z = proj[..., :inner].astype(dt_), proj[..., inner:]
        step = decode and L == 1
        with jax.named_scope("s6_conv"):
            if step:
                pos = positions[:, 0]
                ring = self.variable("cache", "conv", jnp.zeros,
                                     (B, K, inner), dt_)
                ring.value, act = ops.ssd_conv_step(ring.value, x[:, 0],
                                                    conv_w, conv_b, pos)
                act = act[:, None]
            else:
                act, tail = ops.ssd_conv(x, conv_w, conv_b, true_len)
        rbc = _mm("ble,ef->blf", act, w_x, dt_)                # f32
        r = rms_norm(rbc[..., :R], Scale(R, dtype=f32, name="dt_norm")(),
                     eps)
        Bm = rms_norm(rbc[..., R:R + N],
                      Scale(N, dtype=f32, name="b_norm")(), eps)
        Cm = rms_norm(rbc[..., R + N:],
                      Scale(N, dtype=f32, name="c_norm")(), eps)
        dt = jax.nn.softplus(_mm("blr,re->ble", r, w_dt, dt_) + dt_bias)
        if step:
            S = self.variable("cache", "state", jnp.zeros, (B, N, inner),
                              f32)
            S.value, y = ops.s6_state_step(
                S.value, act[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], skip,
                fold, pos)
            y = y[:, None]
        else:
            y, last = ops.s6_chunk_scan(act, dt, A, Bm, Cm, skip, true_len)
            if decode:
                self.variable("cache", "conv", jnp.zeros, (B, K, inner),
                              dt_).value = tail
                self.variable("cache", "state", jnp.zeros, (B, N, inner),
                              f32).value = last
        return _mm("ble,ed->bld", y * jax.nn.silu(z),
                   Weight((inner, D), name="out_proj")(), dt_)


class Layer(nn.Module):
    cfg: JambaConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, decode: bool, true_len, fold):
        cfg = self.cfg
        dt, D = cfg.compute_dtype, cfg.hidden_size
        u = rms_norm(x, Scale(D, dtype=jnp.float32, name="mixer_norm")(),
                     cfg.rms_norm_eps).astype(dt)
        if self.kind == "mamba":
            y = S6Mixer(cfg, name="mixer")(u, positions, decode, true_len,
                                           fold)
        else:
            with jax.named_scope("attn_full"):
                y = AttentionMixer(cfg, name="mixer")(u, positions, decode)
        x = x + y
        u = rms_norm(x, Scale(D, dtype=jnp.float32, name="mlp_norm")(),
                     cfg.rms_norm_eps).astype(dt)
        return x + DenseMlp(cfg, name="mlp")(u)


class JambaLM(nn.Module):
    """tokens [B, L] -> logits [B, L, V] f32 (``logits_at`` [B]: only at
    that position of each row, [B, 1, V]). With ``decode=True`` the call
    goes through the ``cache`` collection: ``L > 1`` prefills a FRESH row
    (positions start at 0; ``true_len``: the tokens that count, the rest
    of the row is a bucket's padding), ``L == 1`` is one decode step at
    each row's own position."""

    cfg: JambaConfig
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
            raise ValueError("the jamba family has no training path")
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
        x = emb[tokens].astype(jnp.float32)
        fold, live = stamp_states(self, positions, decode, true_len)
        if live is not None and self.is_mutable_collection("stats"):
            count_state_step(self, positions[:, 0], live, fold, cfg.n_mamba,
                             cfg.n_attention, cfg.max_len)
        for i, kind in enumerate(cfg.layers):
            x = Layer(cfg, kind, name=f"layer_{i}")(x, positions, decode,
                                                    true_len, fold)
        if logits_at is not None:
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(logits_at.astype(jnp.int32),
                                    (B,))[:, None, None], axis=1)
        x = rms_norm(x, Scale(cfg.hidden_size, dtype=jnp.float32,
                              name="final_norm")(), cfg.rms_norm_eps)
        return _mm("bld,vd->blv", x, emb, cfg.compute_dtype)

    def summarize_stats(self, totals: Dict[str, Any], decode_steps: int
                        ) -> Dict[str, Any]:
        """``serve_summary``'s counters from the ``stats`` collection
        summed over a run's decode steps, under the names granite's
        readers know (``summarize_state_step``) and, for the attention
        layers, exaone_moe's ``full_attend_keys`` (every one of them
        attends the whole context)."""
        del decode_steps
        out = summarize_state_step(totals, self.cfg, self.cfg.n_attention)
        out["full_attend_keys"] = out["select_keys_kept"]
        return out

    def summarize_prefills(self, bucket_positions: int,
                           prompt_positions: int) -> Dict[str, int]:
        """``serve_summary``'s count of what the run's prefills scanned
        (serve/engine.py sums the buckets and the prompts' lengths): the
        positions the state-space layers' scans were handed
        (``s6_scan_positions``: every layer's, the buckets' padding with
        them) and those before ``true_len``
        (``s6_scan_positions_live``: what a scan that stops at the prompt's
        end computes)."""
        return {"s6_scan_positions": self.cfg.n_mamba * bucket_positions,
                "s6_scan_positions_live": self.cfg.n_mamba
                * prompt_positions}


def jamba_lm(mesh=None, size: str = "", source: str = "",
             compute_dtype=jnp.bfloat16, max_len: int = 0,
             vocab_size: int = 0) -> JambaLM:
    """The family's builder: ``source`` (``--model-config``) is a JSON
    file of the source's keys, the one way its sizes come in."""
    if size or not source:
        raise ValueError(
            "jamba takes its sizes from --model-config <json of the "
            "source's config.json keys>[#dotted.key] and has no "
            f"--model-size preset (got size={size!r}, "
            f"model_config={source!r})")
    over: Dict[str, Any] = {"compute_dtype": compute_dtype}
    if max_len:
        over["max_position_embeddings"] = int(max_len)
    if vocab_size:
        over["vocab_size"] = int(vocab_size)
    if mesh is not None and any(
            n > 1 for ax, n in dict(mesh.shape).items() if ax != "data"):
        raise ValueError("jamba is served whole on one chip: it has no "
                         "sharded form (a pure data mesh replicates it)")
    return JambaLM(config_from_source(dict(load_source(source)), **over))
