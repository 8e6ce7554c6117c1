"""The ``nemotron_h`` family (the source's ``model_type``): layers that are
ONE part each, ``x + mixer(rms(x))``, the mixer a Mamba-2 state-space mixer
(``M``), a NoPE grouped-query attention mixer (``*``) or a LATENT expert
layer (``E``), in the order the source's ``hybrid_override_pattern`` spells.
Served as ONE CHIP'S SHARE of a deployment: a run of consecutive published
layers, of each expert layer the routed experts this chip holds, and a
slice of the untied embedding and head.

The two sequence mixers are models/granitemoehybrid.py's own (``MambaMixer``
with ``mamba_n_groups`` groups of ``B`` and ``C`` and a gated norm a group;
``AttentionMixer`` with ``attention_multiplier = head_dim^-1/2``): that
file's docstring has their equations and what a slot keeps of them
(``state`` and ``conv``, ``kv``). The router, ``held_index``,
``count_held_pairs`` and ``summarize_moe`` are models/glm_moe_dsa.py's. What
is this family's own:

- **Model.** ``x0 = E[tok]``; after the last layer ``rms``, then the UNTIED
  head. RMSNorm with a learned scale, ``layer_norm_epsilon``; no bias but
  the convolution's.
- **``E`` (LatentMoE).** ``s = sigmoid(W_r u)`` in float32 over ALL
  published experts; the ``num_experts_per_tok`` largest ``s + b`` PICKED,
  WEIGHED by ``s`` of the picked, normalised, times
  ``routed_scaling_factor`` (``glm_moe_dsa.route``). The routed experts
  work in a LATENT: ``l = W_down_latent u`` (``hidden -> moe_latent_size``),
  expert e is the UNGATED ``W_down^e relu(W_up^e l)^2``
  (``ops.latent_attention.held_experts`` with ``gate`` None), and the
  weighted sum goes back through ``W_up_latent``. The router reads ``u``
  itself, and so does the shared expert, ``W_sd relu(W_su u)^2``. This chip
  computes the pairs whose expert it holds (``experts_held``) under the
  weights of all the picked; no exchange, nothing stands in for absent
  chips. An expert layer keeps NOTHING in the cache.

**A state cannot be rewritten** (models/minicpm_sala.py says why): the
cache carries ``state_pos`` ``[B]`` exactly as granite's does.

Parameters are stored bfloat16 and never materialised in float32. Served
only (the scan has no backward here, ROADMAP B2); the source's multi-token
prediction layers are not served (a self-drafting head over a recurrent
state needs state snapshots, ROADMAP B).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflow_distributed_tpu.models.glm_moe_dsa import (
    PARAM_DTYPE, Scale, Weight, _mm, count_held_pairs,
    describe_moe_plan, experts_held_from, held_index, held_share,
    load_source, rms_norm, route, summarize_moe)
from tensorflow_distributed_tpu.models.granitemoehybrid import (
    AttentionMixer, MambaMixer, count_state_step, stamp_states,
    summarize_state_step)
from tensorflow_distributed_tpu.ops import latent_attention as lat_ops

#: ``hybrid_override_pattern``'s letters -> the kind of a layer's one mixer.
LAYER_KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Sizes read from the SOURCE's keys (``config.json`` of ``model_type:
    nemotron_h``), under the names the shared mixers read
    (models/granitemoehybrid.py), plus what this chip holds."""
    vocab_size: int
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_n_groups: int
    moe_intermediate_size: int
    moe_latent_size: int
    shared_intermediate_size: int
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    rms_norm_eps: float
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
    def attention_multiplier(self) -> float:
        return self.head_dim ** -0.5

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_width(self) -> int:
        """Channels of the convolution: ``x`` and every group's ``B``
        and ``C``."""
        return self.mamba_inner + 2 * self.mamba_n_groups \
            * self.mamba_d_state

    def count(self, kind: str) -> int:
        return sum(1 for k in self.layers if k == kind)

    @property
    def state_bytes_per_slot(self) -> int:
        return self.count("mamba") * self.mamba_inner \
            * self.mamba_d_state * 4

    @property
    def conv_bytes_per_slot(self) -> int:
        return self.count("mamba") * self.mamba_d_conv * self.conv_width \
            * jnp.dtype(self.compute_dtype).itemsize


def layer_list(src: Dict[str, Any]) -> Tuple[str, ...]:
    """The kinds of the layers held: ``num_hidden_layers`` letters of the
    source's ``hybrid_override_pattern`` from ``first_layer_held`` (0 when
    absent)."""
    n, lo = int(src["num_hidden_layers"]), int(src.get("first_layer_held", 0))
    letters = src["hybrid_override_pattern"][lo:lo + n]
    if len(letters) != n or set(letters) - set(LAYER_KINDS):
        raise ValueError(
            f"layers {lo}..{lo + n - 1} of hybrid_override_pattern "
            f"({len(src['hybrid_override_pattern'])} letters) must each be "
            f"one of {sorted(LAYER_KINDS)}, got {letters!r}")
    return tuple(LAYER_KINDS[c] for c in letters)


def config_from_source(src: Dict[str, Any], **overrides) -> NemotronHConfig:
    """A configuration from a dict of the source's ``config.json`` keys.
    ``n_routed_experts`` counts the routed experts HELD here and
    ``experts_held`` names them; ``n_routed_experts_published`` (the
    router's width) defaults to ``n_routed_experts`` for a whole layer.
    What the equations above assume of the source's switches is checked,
    not ignored (its ``chunk_size`` is its own kernel's tiling, not
    mathematics: the scan here runs in ``ops.state_space.SCAN_CHUNK``)."""
    want = {"mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
            "tie_word_embeddings": False, "use_conv_bias": True,
            "mamba_proj_bias": False, "attention_bias": False,
            "mlp_bias": False, "use_bias": False, "n_shared_experts": 1,
            "num_nextn_predict_layers": 0}
    differ = {k: src[k] for k, v in want.items() if src.get(k, v) != v}
    if differ:
        raise ValueError(f"nemotron_h is written down for {want}; the "
                         f"source says {differ}")
    held_n = int(src["n_routed_experts"])
    width = int(src.get("n_routed_experts_published", held_n))
    kw = dict(
        vocab_size=int(src["vocab_size"]),
        hidden_size=int(src["hidden_size"]),
        num_attention_heads=int(src["num_attention_heads"]),
        num_key_value_heads=int(src["num_key_value_heads"]),
        head_dim=int(src["head_dim"]),
        mamba_n_heads=int(src["mamba_num_heads"]),
        mamba_d_head=int(src["mamba_head_dim"]),
        mamba_d_state=int(src["ssm_state_size"]),
        mamba_d_conv=int(src["conv_kernel"]),
        mamba_n_groups=int(src["n_groups"]),
        moe_intermediate_size=int(src["moe_intermediate_size"]),
        moe_latent_size=int(src["moe_latent_size"]),
        shared_intermediate_size=int(
            src["moe_shared_expert_intermediate_size"]),
        num_experts_per_tok=int(src["num_experts_per_tok"]),
        n_group=int(src.get("n_group", 1)),
        topk_group=int(src.get("topk_group", 1)),
        norm_topk_prob=bool(src.get("norm_topk_prob", True)),
        routed_scaling_factor=float(src.get("routed_scaling_factor", 1.0)),
        rms_norm_eps=float(src["layer_norm_epsilon"]),
        max_position_embeddings=int(src["max_position_embeddings"]),
        layers=layer_list(src), router_experts=width,
        experts_held=experts_held_from(src, held_n, width))
    kw.update(overrides)
    cfg = NemotronHConfig(**kw)
    if cfg.mamba_inner != int(src["expand"]) * cfg.hidden_size:
        raise ValueError(
            f"mamba_num_heads x mamba_head_dim = {cfg.mamba_inner} is not "
            f"expand x hidden_size")
    if cfg.mamba_n_heads % cfg.mamba_n_groups:
        raise ValueError(f"n_groups {cfg.mamba_n_groups} does not divide "
                         f"mamba_num_heads {cfg.mamba_n_heads}")
    if float(src.get("norm_eps", cfg.rms_norm_eps)) != cfg.rms_norm_eps:
        raise ValueError("norm_eps and layer_norm_epsilon differ: one eps "
                         "is written down here")
    if cfg.num_attention_heads % cfg.num_key_value_heads:
        raise ValueError("query heads divide into the key-value heads")
    if cfg.n_group != 1 or cfg.topk_group != 1:
        raise ValueError("nemotron_h's router is written down without a "
                         "group limit (n_group 1, topk_group 1)")
    if not 0 < cfg.num_experts_per_tok <= cfg.router_experts:
        raise ValueError("num_experts_per_tok exceeds the router's width")
    return cfg


# -- the expert layer ---------------------------------------------------------

def relu2(x: jax.Array) -> jax.Array:
    """The source's ``relu2``: the squared ReLU."""
    return jnp.square(jax.nn.relu(x))


class LatentMoe(nn.Module):
    """The held experts' part of the routed layer, computed in the latent,
    plus the shared expert."""
    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, u: jax.Array, live=None) -> jax.Array:
        cfg = self.cfg
        dt = cfg.compute_dtype
        B, L, D = u.shape
        E, F, Fs, Dl = (len(cfg.experts_held), cfg.moe_intermediate_size,
                        cfg.shared_intermediate_size, cfg.moe_latent_size)
        w_r = Weight((D, cfg.router_experts), name="router")()
        bias = self.param("router_bias", nn.initializers.zeros_init(),
                          (cfg.router_experts,), jnp.float32)
        up = Weight((E, Dl, F), name="experts_up")()
        down = Weight((E, F, Dl), name="experts_down")()
        xs = u.reshape(B * L, D)
        ids, weights = route(xs, w_r, bias, cfg)
        local = held_index(ids, cfg)                          # [N,k], -1
        with jax.named_scope("moe_latent_down"):
            lat = _mm("nd,dl->nl", xs, Weight((D, Dl), name="latent_down")(),
                      dt)
        y = lat_ops.held_experts_once(lat, local, weights, None, up, down,
                                      dt, held_share(cfg), relu2)
        with jax.named_scope("moe_latent_up"):
            y = _mm("nl,ld->nd", y, Weight((Dl, D), name="latent_up")(), dt)
        if live is not None:
            count_held_pairs(self, local.reshape(B, -1), live, E)
        with jax.named_scope("moe_shared_expert"):
            h = relu2(_mm("nd,df->nf", xs,
                          Weight((D, Fs), name="shared_up")(), dt))
            y = y + _mm("nf,fd->nd", h,
                        Weight((Fs, D), name="shared_down")(), dt)
        return y.reshape(B, L, D)                              # f32


class Layer(nn.Module):
    cfg: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, decode: bool, true_len, fold, live):
        cfg = self.cfg
        u = rms_norm(x, Scale(cfg.hidden_size, name="norm")(),
                     cfg.rms_norm_eps).astype(cfg.compute_dtype)
        if self.kind == "mamba":
            y = MambaMixer(cfg, name="mixer")(u, positions, decode,
                                              true_len, fold)
        elif self.kind == "attention":
            y = AttentionMixer(cfg, name="mixer")(u, positions, decode)
        else:
            y = LatentMoe(cfg, name="moe")(u, live)
        return x + y


class NemotronHLM(nn.Module):
    """tokens [B, L] -> logits [B, L, V] f32 (``logits_at`` [B]: only at
    that position of each row, [B, 1, V]). With ``decode=True`` the call
    goes through the ``cache`` collection: ``L > 1`` prefills a FRESH row
    (positions start at 0; ``true_len``: the tokens that count, the rest
    of the row is a bucket's padding), ``L == 1`` is one decode step at
    each row's own position."""

    cfg: NemotronHConfig
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
            raise ValueError("the nemotron_h family has no training path")
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
        counting = live is not None and self.is_mutable_collection("stats")
        if counting:
            count_state_step(self, positions[:, 0], live, fold,
                             cfg.count("mamba"), cfg.count("attention"),
                             cfg.max_len)
        for i, kind in enumerate(cfg.layers):
            x = Layer(cfg, kind, name=f"layer_{i}")(
                x, positions, decode, true_len, fold,
                live if counting else None)
        if logits_at is not None:
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(logits_at.astype(jnp.int32),
                                    (B,))[:, None, None], axis=1)
        x = rms_norm(x, Scale(cfg.hidden_size, name="final_norm")(),
                     cfg.rms_norm_eps)
        head = Weight((cfg.hidden_size, cfg.vocab_size), name="lm_head")()
        return _mm("bld,dv->blv", x, head, cfg.compute_dtype)

    def moe_plan(self, num_slots: int, buckets) -> Dict[str, Any]:
        """The held experts' plan: they work in the latent, so its width
        is the ``D`` of the grouped matmuls."""
        return describe_moe_plan(self.cfg, self.cfg.moe_intermediate_size,
                                 num_slots, buckets,
                                 self.cfg.moe_latent_size)

    def summarize_stats(self, totals: Dict[str, Any], decode_steps: int
                        ) -> Dict[str, Any]:
        """``serve_summary``'s counters from the ``stats`` collection
        summed over a run's decode steps, under the names granite's and
        the latent family's readers know, plus ``moe_pairs_routed``: the
        (token, expert) pairs the live rows' routers picked over ALL
        published experts (live rows x ``num_experts_per_tok`` x expert
        layers), of which ``moe_held_pairs`` landed here."""
        out = summarize_state_step(totals, self.cfg,
                                   self.cfg.count("attention"))
        out["moe_pairs_routed"] = (
            out["decode_live_rows"] * self.cfg.num_experts_per_tok
            * self.cfg.count("moe"))
        out.update(summarize_moe(totals, decode_steps))
        return out


def nemotron_h_lm(mesh=None, size: str = "", source: str = "",
                  compute_dtype=jnp.bfloat16, max_len: int = 0,
                  vocab_size: int = 0) -> NemotronHLM:
    """The family's builder: ``source`` (``--model-config``) is a JSON
    file of the source's keys, the one way its sizes come in."""
    if size or not source:
        raise ValueError(
            "nemotron_h takes its sizes from --model-config <json of the "
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
        raise ValueError("nemotron_h serves one chip's share: it has no "
                         "sharded form (a pure data mesh replicates it)")
    return NemotronHLM(config_from_source(dict(load_source(source)),
                                          **over))
