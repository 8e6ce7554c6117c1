"""The ``exaone_moe`` family (the source's ``model_type``): grouped-query
attention layers of TWO kinds in one model, ``sliding_attention`` (a window
of ``sliding_window`` positions, rotary) and ``full_attention`` (the whole
context, no position signal), each beside a dense or a routed feed-forward
part. Served as ONE CHIP'S SHARE of a deployment: a run of consecutive
published layers, of each routed layer the experts this chip holds, and a
slice of the untied embedding and head.

A model of this family is two lists read from the source, one entry a layer
from ``first_layer_held``: ``layer_types`` (the attention kind) and
``mlp_layer_types`` (``dense`` or ``sparse``). The parameters AND the decode
cache are built from them. RMSNorm with a learned scale, eps
``rms_norm_eps``; no bias anywhere:

- **Model.** ``x0 = E[tok]``; after the last layer ``rms``, then the UNTIED
  head. **Layer.** ``h = x + Attn(rms_1(x))``, ``y = h + FF(rms_2(h))``
  (pre-norm: the source's config has no key for where the norms stand).
- **Attention** (models/granitemoehybrid.py's ``AttentionMixer``, given this
  family's options): ``q = u W_q`` (``num_attention_heads`` x ``head_dim``),
  ``k, v = u W_k, u W_v`` (``num_key_value_heads`` x ``head_dim``); ``q`` and
  ``k`` each through an RMSNorm over the ``head_dim`` numbers of a head, one
  learned scale for ``q`` and one for ``k`` shared by the heads; on a
  ``sliding_attention`` layer ONLY, ``q`` and ``k`` rotated by position
  (halves, ``rope_parameters.rope_theta``, every dimension:
  ``models/transformer.py::rope_rotate``); softmax of ``q k^T / sqrt(head_dim)``
  in float32 over ``j <= i`` on a full layer and over ``i - sliding_window
  < j <= i`` on a window layer (the window counts the query's own position:
  ``ops/flash_attention.py::window_keep``); ``W_o``.
  What a slot keeps: a full layer ``kv`` ``[B, max_len, 2 G d]`` (granite's
  leaf and lane order), attended on the TPU by ``gqa_dense_attend`` over
  the live rows' blocks up to each row's depth; a window layer ``kv_ring``
  ``[B, sliding_window, 2 G d]``, position ``p`` in row ``p mod
  sliding_window``. Keys are cached ROTATED, so the ring's order does not
  enter the softmax: a step attends the rows ``j <= p`` while ``p <
  sliding_window`` and every row after. A prefill leaves positions
  ``(true_len - sliding_window, true_len - 1]`` in the ring and nothing of
  its bucket's padding (``prefill_true_len``).
- **``dense``.** ``W_d (silu(u W_g) * (u W_u))``, ``intermediate_size`` wide.
- **``sparse``** (models/glm_moe_dsa.py's ``SparseMoe``: the latent family's
  router and experts). ``s = sigmoid(u W_r)`` in float32 over ALL published
  experts; the ``num_experts_per_tok`` largest ``s + b`` PICKED, WEIGHED by
  ``s`` of the picked, normalised, times ``routed_scaling_factor``; expert
  ``e`` the gated ``W_d,e (silu(u W_g,e) * (u W_u,e))``,
  ``moe_intermediate_size`` wide; one shared expert of the same shape on
  every token, weight 1. This chip computes the pairs whose expert it holds
  (``experts_held``) under the weights of all the picked; no exchange,
  nothing stands in for absent chips.

A ring CAN be rewritten (a step computed again writes the same row), but it
cannot be rolled back: what a drafted token displaced is gone, which is why
``--serve.spec-tokens`` is refused for this family (config.py). The
source's next-token-prediction layer is not served.

Parameters are stored bfloat16 and never materialised in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflow_distributed_tpu.models.glm_moe_dsa import (
    PARAM_DTYPE, DenseMlp, Scale, SparseMoe, Weight, _count, _mm,
    describe_moe_plan, experts_held_from, load_source, rms_norm,
    summarize_moe)
from tensorflow_distributed_tpu.models.granitemoehybrid import AttentionMixer
from tensorflow_distributed_tpu.ops import hybrid_attention as hyb_ops
from tensorflow_distributed_tpu.ops import latent_attention as lat_ops

ATTENTION_KINDS = ("sliding_attention", "full_attention")
MLP_KINDS = ("dense", "sparse")


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    """Sizes read from the SOURCE's keys (``config.json`` of ``model_type:
    exaone_moe``), under the names the shared modules read
    (``AttentionMixer``, ``SparseMoe``, ``DenseMlp``), plus what this chip
    holds."""
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    sliding_window: int
    rope_theta: float
    num_experts_per_tok: int
    n_shared_experts: int
    n_group: int
    topk_group: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rms_norm_eps: float
    max_position_embeddings: int
    # (attention kind, feed-forward kind) of each layer held
    layers: Tuple[Tuple[str, str], ...]
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
    def attention_multiplier(self) -> float:
        return self.head_dim ** -0.5

    def count(self, kind: str) -> int:
        """Layers whose attention or feed-forward part is ``kind``."""
        return sum(kind in pair for pair in self.layers)


def layer_list(src: Dict[str, Any]) -> Tuple[Tuple[str, str], ...]:
    """The kinds of the layers held: ``num_hidden_layers`` entries of the
    source's ``layer_types`` and of its ``mlp_layer_types`` from
    ``first_layer_held`` (0 when absent)."""
    n, lo = int(src["num_hidden_layers"]), int(src.get("first_layer_held", 0))
    out = []
    for key, kinds in (("layer_types", ATTENTION_KINDS),
                       ("mlp_layer_types", MLP_KINDS)):
        mine = tuple(src[key][lo:lo + n])
        if len(mine) != n or set(mine) - set(kinds):
            raise ValueError(
                f"layers {lo}..{lo + n - 1} of {key} ({len(src[key])} "
                f"entries) must each be one of {kinds}, got {mine}")
        out.append(mine)
    return tuple(zip(*out))


def config_from_source(src: Dict[str, Any], **overrides) -> ExaoneMoeConfig:
    """A configuration from a dict of the source's ``config.json`` keys.
    ``num_experts`` counts the routed experts HELD here and ``experts_held``
    names them; ``num_experts_published`` (the router's width) defaults to
    ``num_experts`` for a whole layer. What the equations above assume of
    the source's switches is checked, not ignored."""
    want = {"hidden_act": "silu", "tie_word_embeddings": False,
            "attention_bias": False, "scoring_func": "sigmoid",
            "num_nextn_predict_layers": 0, "num_shared_experts": 1}
    differ = {k: src[k] for k, v in want.items() if src.get(k, v) != v}
    if differ:
        raise ValueError(f"exaone_moe is written down for {want}; the "
                         f"source says {differ}")
    rope = dict(src.get("rope_parameters") or {})
    if rope.get("rope_type", "default") != "default" or \
            float(rope.get("partial_rotary_factor", 1.0)) != 1.0:
        raise ValueError("exaone_moe is written down for rope_type default "
                         f"over every dimension; the source says {rope}")
    held_n = int(src["num_experts"])
    width = int(src.get("num_experts_published", held_n))
    kw = dict(
        vocab_size=int(src["vocab_size"]),
        hidden_size=int(src["hidden_size"]),
        intermediate_size=int(src["intermediate_size"]),
        moe_intermediate_size=int(src["moe_intermediate_size"]),
        num_attention_heads=int(src["num_attention_heads"]),
        num_key_value_heads=int(src["num_key_value_heads"]),
        head_dim=int(src["head_dim"]),
        sliding_window=int(src["sliding_window"]),
        rope_theta=float(rope["rope_theta"]),
        num_experts_per_tok=int(src["num_experts_per_tok"]),
        n_shared_experts=int(src.get("num_shared_experts", 1)),
        n_group=int(src.get("n_group", 1)),
        topk_group=int(src.get("topk_group", 1)),
        norm_topk_prob=bool(src.get("norm_topk_prob", True)),
        routed_scaling_factor=float(src.get("routed_scaling_factor", 1.0)),
        rms_norm_eps=float(src["rms_norm_eps"]),
        max_position_embeddings=int(src["max_position_embeddings"]),
        layers=layer_list(src), router_experts=width,
        experts_held=experts_held_from(src, held_n, width))
    kw.update(overrides)
    cfg = ExaoneMoeConfig(**kw)
    lo = int(src.get("first_layer_held", 0))
    dense = tuple(i + lo < int(src.get("first_k_dense_replace", 0))
                  for i in range(len(cfg.layers)))
    if dense != tuple(ff == "dense" for _, ff in cfg.layers):
        raise ValueError(
            f"mlp_layer_types {[ff for _, ff in cfg.layers]} from layer "
            f"{lo} and first_k_dense_replace "
            f"{src.get('first_k_dense_replace')} disagree")
    if cfg.num_attention_heads % cfg.num_key_value_heads or cfg.head_dim % 2:
        raise ValueError("query heads divide into the key-value heads and "
                         "a head rotates in halves")
    if cfg.n_group != 1 or cfg.topk_group != 1:
        raise ValueError("exaone_moe's router is written down without a "
                         "group limit (n_group 1, topk_group 1)")
    if not 0 < cfg.num_experts_per_tok <= cfg.router_experts:
        raise ValueError("num_experts_per_tok exceeds the router's width")
    if cfg.sliding_window < 1:
        raise ValueError("sliding_window counts the query's own position: "
                         "at least 1")
    return cfg


class Layer(nn.Module):
    cfg: ExaoneMoeConfig
    attention: str
    mlp: str

    @nn.compact
    def __call__(self, x, positions, decode: bool, true_len, live):
        cfg = self.cfg
        dt = cfg.compute_dtype
        window = self.attention == "sliding_attention"
        u = rms_norm(x, Scale(cfg.hidden_size, name="attn_norm")(),
                     cfg.rms_norm_eps).astype(dt)
        with jax.named_scope("attn_window" if window else "attn_full"):
            y = AttentionMixer(
                cfg, qk_norm_eps=cfg.rms_norm_eps,
                rope_theta=cfg.rope_theta if window else 0.0,
                window=cfg.sliding_window if window else 0,
                name="mixer")(u, positions, decode, true_len)
        x = x + y
        u = rms_norm(x, Scale(cfg.hidden_size, name="mlp_norm")(),
                     cfg.rms_norm_eps).astype(dt)
        if self.mlp == "sparse":
            return x + SparseMoe(cfg, name="moe")(u, live)
        return x + DenseMlp(cfg, name="mlp")(u)


class ExaoneMoeLM(nn.Module):
    """tokens [B, L] -> logits [B, L, V] f32 (``logits_at`` [B]: only at
    that position of each row, [B, 1, V]). With ``decode=True`` the call
    goes through the ``cache`` collection: ``L > 1`` prefills a FRESH row
    (positions start at 0; ``true_len``: the tokens that count, the rest
    of the row is a bucket's padding), ``L == 1`` is one decode step at
    each row's own position."""

    cfg: ExaoneMoeConfig
    mesh: Any = None
    # serve/engine.py: the prefill program asks for the last logits only
    # and hands the model the prompt's true length (a ring holds the rows
    # BEFORE it, not the bucket's last); the decode program returns what a
    # step counted (the ``stats`` collection below).
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
            raise ValueError("the exaone_moe family has no training path")
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
        live = None
        if decode and L == 1 and self.is_mutable_collection("stats"):
            # One step's counters, over LIVE rows: a row at depth 0 is a
            # free slot (an admitted row is at least one token deep). A
            # live row at depth p has p + 1 causal keys in every layer;
            # a full layer attends them all, a window layer the last
            # ``sliding_window`` of them.
            pos = positions[:, 0]
            live = pos > 0
            have = jnp.sum(jnp.where(live, pos + 1, 0))
            n_full, n_ring = (cfg.count("full_attention"),
                              cfg.count("sliding_attention"))
            W = cfg.sliding_window
            _count(self, "live_rows", jnp.sum(live, dtype=jnp.int32))
            _count(self, "keys_available", (n_full + n_ring) * have)
            _count(self, "full_keys", n_full * have)
            _count(self, "keys_kept", n_full * have + n_ring * jnp.sum(
                jnp.where(live, jnp.minimum(pos + 1, W), 0)))
            # what the attends' blocks cover over ALL slots: the full
            # layers' kernel the live rows' blocks to their depth, the
            # rings' slot-blind attend every slot's whole ring
            _count(self, "positions_visited",
                   n_full * hyb_ops.gqa_attend_visits(pos, cfg.max_len)
                   + n_ring * B * W)
        for i, (attention, mlp) in enumerate(cfg.layers):
            x = Layer(cfg, attention, mlp, name=f"layer_{i}")(
                x, positions, decode, true_len, live)
        if logits_at is not None:
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(logits_at.astype(jnp.int32),
                                    (B,))[:, None, None], axis=1)
        x = rms_norm(x, Scale(cfg.hidden_size, name="final_norm")(),
                     cfg.rms_norm_eps)
        head = Weight((cfg.hidden_size, cfg.vocab_size), name="lm_head")()
        return _mm("bld,dv->blv", x, head, cfg.compute_dtype)

    def prefill_attend_plan(self, buckets) -> Dict[str, Any]:
        """What a serve run's ``start`` record carries: for each prefill
        bucket, the form, blocks and computed tiles of the expanded attend
        on a full layer (``full``) and under the window (``window``:
        ``ops.latent_attention.prefill_attend_describe``)."""
        cfg = self.cfg
        d = cfg.head_dim
        return {str(b): {
            "full": lat_ops.prefill_attend_describe(b, d, d,
                                                    cfg.compute_dtype),
            "window": lat_ops.prefill_attend_describe(
                b, d, d, cfg.compute_dtype, cfg.sliding_window)}
            for b in buckets}

    def moe_plan(self, num_slots: int, buckets) -> Dict[str, Any]:
        return describe_moe_plan(self.cfg, self.cfg.moe_intermediate_size,
                                 num_slots, buckets)

    def summarize_stats(self, totals: Dict[str, Any], decode_steps: int
                        ) -> Dict[str, Any]:
        """``serve_summary``'s counters from the ``stats`` collection
        summed over a run's decode steps, under the names the latent
        family's readers know: ``select_keys_available`` (the causal
        positions of the live rows over every attention layer: what full
        attention everywhere would attend), ``select_keys_kept`` (what
        this model attends: depth on a full layer, at most the window on
        a ring), ``attend_positions_visited`` (what the attends' blocks
        covered, over all slots), ``full_attend_keys`` (the full layers'
        alone), and ``moe_pairs_routed`` (live rows x picks x routed
        layers) beside the held pairs."""
        live = int(totals["live_rows"])
        out: Dict[str, Any] = {
            "decode_live_rows": live,
            "select_keys_available": int(totals["keys_available"]),
            "select_keys_kept": int(totals["keys_kept"]),
            "attend_positions_visited": int(totals["positions_visited"]),
            "full_attend_keys": int(totals["full_keys"]),
            "moe_pairs_routed": live * self.cfg.num_experts_per_tok
            * self.cfg.count("sparse")}
        if out["select_keys_available"]:
            out["index_keep_share"] = round(
                out["select_keys_kept"] / out["select_keys_available"], 6)
        out.update(summarize_moe(totals, decode_steps))
        return out


def exaone_moe_lm(mesh=None, size: str = "", source: str = "",
                  compute_dtype=jnp.bfloat16, max_len: int = 0,
                  vocab_size: int = 0) -> ExaoneMoeLM:
    """The family's builder: ``source`` (``--model-config``) is a JSON
    file of the source's keys, the one way its sizes come in."""
    if size or not source:
        raise ValueError(
            "exaone_moe takes its sizes from --model-config <json of the "
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
        raise ValueError("exaone_moe serves one chip's share: it has no "
                         "sharded form (a pure data mesh replicates it)")
    return ExaoneMoeLM(config_from_source(dict(load_source(source)), **over))
