"""The ``glm_moe_dsa`` family (also registered as ``axk1``, the other
source ``model_type`` it serves): latent attention (MLA), dense or under
a learned sparse selection with shared indices (DSA), and a
sigmoid-routed dropless MoE with a shared expert, served as ONE CHIP'S
SHARE of a deployment.

A model of this family is a list of per-layer specifications
(:class:`LayerSpec`) derived from the source's own ``config.json`` keys
(``mlp_layer_types`` or ``first_k_dense_replace`` / ``moe_layer_freq``,
``indexer_types``, ``n_routed_experts`` and the experts held here), not
a bag of whole-model flags: the parameters AND the decode cache are
built from that list, so a ``shared`` layer has neither indexer
parameters nor an index-key cache leaf, and a ``none`` layer (a source
without ``indexer_types``: DeepSeek-V3's keys, A.X-K1) has no indexer at
all and attends every cached position.

Per layer (hidden ``D``, heads ``H``, RMSNorm eps from the source):

- **MLA.** ``c_q = rms(x W_qa)``; ``q = c_q W_qb`` -> ``H x (nope +
  rope)``, interleaved RoPE on the rope part. ``[c_kv, k_r] = x W_kva``;
  ``c_kv = rms(c_kv)``, ``k_r = rope(k_r)`` (one ``k_r`` for all heads).
  What a token leaves in the cache is ``[c_kv, k_r]`` (one *latent* leaf
  ``[B, T, kv_lora_rank + rope]``, stored in rows of whole lane tiles:
  ``GlmMoeDsaConfig.latent_row``). Prefill expands keys and values
  (``c_kv W_kvb``); a decode step absorbs ``W_kvb`` into the query and
  the output and attends in the latent space. Same mathematics.
- **DSA.** A ``full`` layer has an indexer: ``q^I = c_q W^I_q`` (heads
  x ``index_head_dim``, RoPE on the first ``rope`` numbers), ``k^I =
  LayerNorm(x W^I_k)`` (one key a token, cached in an *index-key* leaf
  ``[B, T, index_head_dim]``), ``w = x W^I_w``; ``I[t, s] = sum_j w[t,
  j] relu(q^I[t, j] . k^I[s])`` (scaled). The softmax runs over the
  ``index_topk`` causal positions with the largest ``I`` only (all of
  them while fewer exist). A ``shared`` layer reuses the selection of
  the nearest ``full`` layer before it. A ``none`` layer has no
  selection: prefill is causal over every earlier position, a decode
  step attends the row's whole cache row up to its depth, in place.
- **RoPE.** Interleaved pairs at ``theta^(-2i/d)``; with the source's
  ``rope_scaling`` (YaRN) the frequencies are blended with ``f / factor``
  along the ramp between ``beta_fast`` and ``beta_slow`` rotations of
  the original context and the softmax scale is multiplied by
  ``m(factor, mscale_all_dim)^2`` (:func:`rope_frequencies`,
  :func:`softmax_scale`).
- **MLP.** Dense SwiGLU, or: ``s = sigmoid(x W_g)`` over ALL published
  experts, the ``num_experts_per_tok`` largest ``s + b`` are picked
  (with ``n_group`` > 1 only inside the ``topk_group`` groups of
  consecutive experts whose two largest ``s + b`` sum highest),
  weights ``s / sum(s picked) * routed_scaling_factor``. The layer is
  told which experts it HOLDS (``experts_held``), computes only their
  part with a grouped matmul over the (token, expert) pairs that landed
  on them (dropless: no capacity), and adds the shared expert. On one
  chip there is no exchange and nothing stands in for absent chips.

Parameters are stored bfloat16 (what the source publishes) and never
materialised in float32; the router's correction bias is float32 as in
the source. The family is serve/generate only (no training path: latent
attention and dropless routing are not trained here, ROADMAP B).
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflow_distributed_tpu.ops import latent_attention as lat_ops

PARAM_DTYPE = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer's kind: ``mlp`` "dense" | "sparse"; ``indexer`` "full"
    (has an indexer and an index-key cache) | "shared" (reuses the
    selection of the last full layer) | "none" (no selection: dense
    latent attention)."""
    mlp: str
    indexer: str


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """The source's ``rope_scaling`` of ``type: yarn``."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig:
    """Sizes under the SOURCE's key names (``config.json`` of
    ``model_type: glm_moe_dsa``), plus what this chip holds."""
    vocab_size: int
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    n_shared_experts: int
    rms_norm_eps: float
    rope_theta: float
    max_position_embeddings: int
    layers: Tuple[LayerSpec, ...]
    # The router's width: the PUBLISHED number of routed experts.
    router_experts: int
    # Ids (in [0, router_experts)) of the routed experts this chip holds.
    experts_held: Tuple[int, ...]
    # The indexer's sizes: a source whose layers are all "none" has none.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    rope_scaling: Optional[RopeScaling] = None
    # Group-limited routing: the router's experts are ``n_group`` groups
    # of consecutive ids, of which ``topk_group`` may be picked from.
    n_group: int = 1
    topk_group: int = 1
    compute_dtype: Any = jnp.bfloat16
    causal: bool = True

    @property
    def max_len(self) -> int:
        return self.max_position_embeddings

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The width a token's latent is STORED at: ``latent_dim``
        rounded up to whole 128-lane tiles, the rest zero. A row-major
        TPU array pads its minor dimension to the lane tile anyway, so
        this costs no memory; but a leaf whose minor dimension is NOT a
        lane multiple is kept T-minor by the TPU, and XLA then copied the
        whole leaf before every row write and row gather (1.96 ms a layer
        a decode step at 32 x 16,384 x 576; my chip run, PR 28)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def layer_specs(src: Dict[str, Any]) -> Tuple[LayerSpec, ...]:
    """The per-layer specification list from the source's keys:
    ``num_hidden_layers`` layers starting at published layer
    ``first_layer_held`` (0 when absent) of ``mlp_layer_types`` and
    ``indexer_types``. A source without ``mlp_layer_types`` gives
    ``first_k_dense_replace`` leading dense layers and an expert layer
    wherever ``moe_layer_freq`` divides the published index after them;
    one without ``indexer_types`` gives every layer ``"none"``."""
    n = int(src["num_hidden_layers"])
    lo = int(src.get("first_layer_held", 0))
    mlp, idx = src.get("mlp_layer_types"), src.get("indexer_types")
    if mlp is None:
        k, freq = (int(src["first_k_dense_replace"]),
                   int(src.get("moe_layer_freq", 1)))
        mlp = ["sparse" if i >= k and i % freq == 0 else "dense"
               for i in range(lo + n)]
    if idx is None:
        idx = ["none"] * (lo + n)
    if lo + n > min(len(mlp), len(idx)):
        raise ValueError(
            f"layers {lo}..{lo + n - 1} are outside mlp_layer_types "
            f"({len(mlp)}) / indexer_types ({len(idx)})")
    specs = tuple(LayerSpec(mlp[lo + i], idx[lo + i]) for i in range(n))
    for s in specs:
        if s.mlp not in ("dense", "sparse") or \
                s.indexer not in ("full", "shared", "none"):
            raise ValueError(f"unknown layer kind {s}")
    if specs[0].indexer == "shared":
        raise ValueError(
            "the first layer held must be a 'full' indexer layer: a "
            "'shared' layer reuses the selection of a full layer before "
            "it")
    if "mlp_layer_types" in src:
        dense = sum(1 for s in specs if s.mlp == "dense")
        if dense != int(src.get("first_k_dense_replace", dense)):
            raise ValueError(
                f"first_k_dense_replace {src['first_k_dense_replace']} "
                f"but the layers held have {dense} dense MLPs")
    return specs


def rope_scaling_from_source(src: Dict[str, Any]) -> Optional[RopeScaling]:
    """The source's ``rope_scaling`` group (None: plain RoPE)."""
    group = src.get("rope_scaling")
    if not group or float(group.get("factor", 1.0)) == 1.0:
        return None
    kind = group.get("type", group.get("rope_type"))
    if kind != "yarn":
        raise ValueError(f"rope_scaling type {kind!r}: only yarn is "
                         "implemented")
    return RopeScaling(
        factor=float(group["factor"]),
        original_max_position_embeddings=int(
            group["original_max_position_embeddings"]),
        beta_fast=float(group.get("beta_fast", 32.0)),
        beta_slow=float(group.get("beta_slow", 1.0)),
        mscale=float(group.get("mscale", 1.0)),
        mscale_all_dim=float(group.get("mscale_all_dim", 0.0)))


def config_from_source(src: Dict[str, Any], **overrides
                       ) -> GlmMoeDsaConfig:
    """A configuration from a dict of the source's ``config.json`` keys.
    ``n_routed_experts`` counts the experts HELD here and
    ``experts_held`` names them; ``n_routed_experts_published`` (the
    router's width) defaults to ``n_routed_experts`` for a whole
    model."""
    if src.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("glm_moe_dsa routes by sigmoid scores")
    held_n = int(src["n_routed_experts"])
    width = int(src.get("n_routed_experts_published", held_n))
    held = experts_held_from(src, held_n, width)
    rope = src.get("rope_parameters") or {}
    kw = dict(
        vocab_size=int(src["vocab_size"]),
        hidden_size=int(src["hidden_size"]),
        num_attention_heads=int(src["num_attention_heads"]),
        q_lora_rank=int(src["q_lora_rank"]),
        kv_lora_rank=int(src["kv_lora_rank"]),
        qk_nope_head_dim=int(src["qk_nope_head_dim"]),
        qk_rope_head_dim=int(src["qk_rope_head_dim"]),
        v_head_dim=int(src["v_head_dim"]),
        index_n_heads=int(src.get("index_n_heads", 0)),
        index_head_dim=int(src.get("index_head_dim", 0)),
        index_topk=int(src.get("index_topk", 0)),
        rope_scaling=rope_scaling_from_source(src),
        n_group=int(src.get("n_group", 1)),
        topk_group=int(src.get("topk_group", 1)),
        intermediate_size=int(src["intermediate_size"]),
        moe_intermediate_size=int(src["moe_intermediate_size"]),
        num_experts_per_tok=int(src["num_experts_per_tok"]),
        routed_scaling_factor=float(src["routed_scaling_factor"]),
        norm_topk_prob=bool(src.get("norm_topk_prob", True)),
        n_shared_experts=int(src.get("n_shared_experts", 1)),
        rms_norm_eps=float(src["rms_norm_eps"]),
        rope_theta=float(rope.get("rope_theta",
                                  src.get("rope_theta", 10000.0))),
        max_position_embeddings=int(src["max_position_embeddings"]),
        layers=layer_specs(src), router_experts=width, experts_held=held)
    kw.update(overrides)
    cfg = GlmMoeDsaConfig(**kw)
    indexed = any(s.indexer != "none" for s in cfg.layers)
    if cfg.qk_rope_head_dim % 2 or (
            indexed and cfg.index_head_dim < cfg.qk_rope_head_dim):
        raise ValueError("rope needs an even qk_rope_head_dim no larger "
                         "than index_head_dim")
    if indexed and not (cfg.index_n_heads and cfg.index_topk):
        raise ValueError("a source with indexer_types gives the indexer's "
                         "sizes: index_n_heads, index_head_dim, index_topk")
    if cfg.num_experts_per_tok > cfg.router_experts:
        raise ValueError("num_experts_per_tok exceeds the router's width")
    if cfg.router_experts % cfg.n_group or not (
            1 <= cfg.topk_group <= cfg.n_group) or cfg.num_experts_per_tok \
            > cfg.topk_group * (cfg.router_experts // cfg.n_group):
        raise ValueError(
            f"n_group {cfg.n_group} must divide the router's width "
            f"{cfg.router_experts}, and topk_group {cfg.topk_group} of "
            f"them must hold num_experts_per_tok "
            f"{cfg.num_experts_per_tok} experts")
    return cfg


def experts_held_from(src: Dict[str, Any], held_n: int, width: int
                      ) -> Tuple[int, ...]:
    """The source's ``experts_held`` (the first ``held_n`` ids when
    absent): ``held_n`` distinct ids below the router's ``width``."""
    held = tuple(int(e) for e in src.get("experts_held", range(held_n)))
    if len(held) != held_n or len(set(held)) != held_n or \
            not all(0 <= e < width for e in held):
        raise ValueError(
            f"experts_held {held} must be {held_n} distinct ids below "
            f"the router's width {width}")
    return held


def load_source(spec: str) -> Dict[str, Any]:
    """``--model-config``: a JSON file of the source's keys, optionally
    ``path#dotted.key`` for an object nested in it (a benchmark
    configuration's ``rehearsal.sizes``)."""
    path, _, inner = spec.partition("#")
    with open(path) as f:
        obj = json.load(f)
    for key in filter(None, inner.split(".")):
        obj = obj[key]
    return obj


# -- pieces -------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm in f32; returns f32."""
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def layer_norm(x, scale, bias, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32) + bias.astype(jnp.float32))


def yarn_correction_range(rs: RopeScaling, d: int, theta: float
                          ) -> Tuple[int, int]:
    """(low, high): the pair indices between which YaRN's ramp goes from
    the unscaled to the scaled frequency. ``cd(n)`` is the pair whose
    wavelength makes ``n`` rotations over the original context."""
    def cd(n):
        return d * math.log(rs.original_max_position_embeddings
                            / (2 * math.pi * n)) / (2 * math.log(theta))
    return (max(math.floor(cd(rs.beta_fast)), 0),
            min(math.ceil(cd(rs.beta_slow)), d - 1))


def yarn_mscale(factor: float, a: float) -> float:
    """``m(s, a) = 0.1 a ln s + 1`` (1 at ``s <= 1``)."""
    return 1.0 if factor <= 1.0 else 0.1 * a * math.log(factor) + 1.0


def rope_frequencies(cfg: GlmMoeDsaConfig, d: int) -> jax.Array:
    """The ``d / 2`` rotary frequencies of a ``d``-wide part:
    ``theta^(-2i/d)``, under YaRN ``f (1 - ramp) + (f / factor) ramp``
    with ``ramp`` linear from pair ``low`` to pair ``high``."""
    freqs = cfg.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    rs = cfg.rope_scaling
    if rs is None:
        return freqs
    low, high = yarn_correction_range(rs, d, cfg.rope_theta)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freqs * (1.0 - ramp) + (freqs / rs.factor) * ramp


def rope_magnitude(cfg: GlmMoeDsaConfig) -> float:
    """What YaRN multiplies cosine and sine by:
    ``m(s, mscale) / m(s, mscale_all_dim)`` (1 without scaling)."""
    rs = cfg.rope_scaling
    if rs is None:
        return 1.0
    return (yarn_mscale(rs.factor, rs.mscale)
            / yarn_mscale(rs.factor, rs.mscale_all_dim))


def softmax_scale(cfg: GlmMoeDsaConfig) -> float:
    """``qk_head_dim^-1/2``, times ``m(s, mscale_all_dim)^2`` under
    YaRN with ``mscale_all_dim`` set."""
    scale = cfg.qk_head_dim ** -0.5
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def rope_interleaved(x: jax.Array, positions: jax.Array, freqs: jax.Array,
                     magnitude: float = 1.0) -> jax.Array:
    """Interleaved rotary embedding: pair i is ``(x[2i], x[2i+1])``,
    rotated by ``positions * freqs[i]`` (cosine and sine times
    ``magnitude``). ``x`` [..., L, d] or [..., L, H, d] with
    ``positions`` [..., L]; f32 in, f32 out."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[..., None] * freqs    # [..., L, d/2]
    if x.ndim == ang.ndim + 1:                                # head axis
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


class Weight(nn.Module):
    """One bfloat16 matrix under ``<name>/kernel``."""
    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("kernel", nn.initializers.normal(stddev=0.02),
                          self.shape, PARAM_DTYPE)


class Scale(nn.Module):
    """A norm's ``scale`` (and ``bias`` for a LayerNorm), bfloat16 unless
    the family keeps its norms in another ``dtype`` (models/jamba.py:
    float32)."""
    dim: int
    bias: bool = False
    dtype: Any = PARAM_DTYPE

    @nn.compact
    def __call__(self):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (self.dim,), self.dtype)
        if not self.bias:
            return scale
        return scale, self.param("bias", nn.initializers.zeros_init(),
                                 (self.dim,), self.dtype)


def _mm(spec: str, a: jax.Array, w: jax.Array, dtype) -> jax.Array:
    """``a`` and the stored weight as ``dtype`` operands, f32
    accumulation (float32 compute: ``highest`` precision, for the CPU
    comparisons with the reference)."""
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None)
    return jnp.einsum(spec, a.astype(dtype), w.astype(dtype),
                      preferred_element_type=jnp.float32, precision=prec)


def swiglu(x: jax.Array, gate, up, down, dtype) -> jax.Array:
    h = jax.nn.silu(_mm("...d,df->...f", x, gate, dtype)) \
        * _mm("...d,df->...f", x, up, dtype)
    return _mm("...f,fd->...d", h, down, dtype)


# -- the indexer and the selection -------------------------------------------

class Indexer(nn.Module):
    """The learned scorer of a ``full`` layer. Returns the selection for
    this call's queries and caches one index key a token."""
    cfg: GlmMoeDsaConfig

    @nn.compact
    def __call__(self, x, c_q, positions, decode: bool):
        cfg = self.cfg
        dt = cfg.compute_dtype
        B, L, _ = x.shape
        nh, dh, dr = cfg.index_n_heads, cfg.index_head_dim, \
            cfg.qk_rope_head_dim
        wq = Weight((cfg.q_lora_rank, nh, dh), name="wq_b")()
        wk = Weight((cfg.hidden_size, dh), name="wk")()
        k_scale, k_bias = Scale(dh, bias=True, name="k_norm")()
        ww = Weight((cfg.hidden_size, nh), name="weights_proj")()

        freqs, mag = rope_frequencies(cfg, dr), rope_magnitude(cfg)
        q = _mm("blr,rhd->blhd", c_q, wq, dt)                 # [B,L,nh,dh]
        q = jnp.concatenate(
            [rope_interleaved(q[..., :dr], positions, freqs, mag),
             q[..., dr:]], axis=-1)
        k = layer_norm(_mm("bld,de->ble", x, wk, dt), k_scale, k_bias)
        k = jnp.concatenate(
            [rope_interleaved(k[..., :dr], positions, freqs, mag),
             k[..., dr:]], axis=-1).astype(dt)                # [B,L,dh]
        w = _mm("bld,dh->blh", x, ww, dt) * (nh ** -0.5 * dh ** -0.5)
        q = q.astype(dt)
        if decode:
            ck = self.variable("cache", "index_keys", jnp.zeros,
                               (B, cfg.max_len, dh), dt)
            ck.value = lat_ops.write_rows(ck.value, k, positions[:, 0])
        if not decode or L > 1:
            # The new tokens are the whole context (a fresh row).
            return jax.vmap(lambda a, b, c: lat_ops.prefill_selection(
                a, b, c, cfg.index_topk))(q, k, w)
        return lat_ops.decode_selection(q[:, 0], w[:, 0], ck.value,
                                        positions[:, 0], cfg.index_topk)


# -- attention ---------------------------------------------------------------

class LatentAttention(nn.Module):
    """MLA over the selection. ``selection``: what the last full layer
    chose (None on a full layer, which computes it, and on a ``none``
    layer, which attends every causal position). Returns the block's
    output and the selection in force."""
    cfg: GlmMoeDsaConfig
    spec: LayerSpec

    @nn.compact
    def __call__(self, x, positions, decode: bool, selection):
        cfg = self.cfg
        dt = cfg.compute_dtype
        B, L, D = x.shape
        H, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        r_kv = cfg.kv_lora_rank
        w_qa = Weight((D, cfg.q_lora_rank), name="q_a")()
        q_norm = Scale(cfg.q_lora_rank, name="q_a_norm")()
        w_qb = Weight((cfg.q_lora_rank, H, dn + dr), name="q_b")()
        w_kva = Weight((D, r_kv + dr), name="kv_a")()
        kv_norm = Scale(r_kv, name="kv_a_norm")()
        w_kvb = Weight((r_kv, H, dn + dv), name="kv_b")()
        w_o = Weight((H, dv, D), name="o")()

        freqs, mag = rope_frequencies(cfg, dr), rope_magnitude(cfg)
        c_q = rms_norm(_mm("bld,dr->blr", x, w_qa, dt), q_norm,
                       cfg.rms_norm_eps).astype(dt)
        q = _mm("blr,rhe->blhe", c_q, w_qb, dt)               # [B,L,H,dn+dr]
        q_nope = q[..., :dn].astype(dt)
        q_rope = rope_interleaved(q[..., dn:], positions, freqs,
                                  mag).astype(dt)
        kv_a = _mm("bld,de->ble", x, w_kva, dt)
        c_kv = rms_norm(kv_a[..., :r_kv], kv_norm, cfg.rms_norm_eps)
        k_r = rope_interleaved(kv_a[..., r_kv:], positions, freqs, mag)
        latent = jnp.concatenate([c_kv, k_r], -1).astype(dt)  # [B,L,r+dr]

        dense = self.spec.indexer == "none"
        if self.spec.indexer == "full":
            selection = Indexer(cfg, name="indexer")(x, c_q, positions,
                                                     decode)
        elif not dense and selection is None:
            raise ValueError("a 'shared' layer needs a selection")
        scale = softmax_scale(cfg)
        step = decode and L == 1
        if decode:
            cl = self.variable("cache", "latent", jnp.zeros,
                               (B, cfg.max_len, cfg.latent_row), dt)
            cl.value = lat_ops.write_rows(
                cl.value, jnp.pad(latent, (
                    (0, 0), (0, 0), (0, cfg.latent_row - r_kv - dr))),
                positions[:, 0])
        if step:
            # Absorbed form: W_kvb goes into the query and the output,
            # the attend reads only the selected rows of the latent
            # cache (a dense layer: every row up to the depth, in place).
            w_uk, w_uv = w_kvb[..., :dn], w_kvb[..., dn:]
            q_abs = _mm("bhe,rhe->bhr", q_nope[:, 0], w_uk, dt).astype(dt)
            if dense:
                o_lat = lat_ops.decode_attend_dense(
                    q_abs, q_rope[:, 0], cl.value, positions[:, 0], scale,
                    r_kv, dr)
            else:
                idx, valid = selection
                o_lat = lat_ops.decode_attend(
                    q_abs, q_rope[:, 0], cl.value, idx, valid,
                    positions[:, 0], scale, r_kv, dr)
            o = _mm("bhr,rhv->bhv", o_lat.astype(dt), w_uv,
                    dt).astype(dt)[:, None]                   # [B,1,H,dv]
        else:
            # Expanded form over the new tokens (a fresh row: positions
            # start at 0, so the new tokens ARE the whole context).
            lat_r = latent[..., :r_kv]
            kv = _mm("blr,rhe->bhle", lat_r, w_kvb, dt).astype(dt)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    latent[:, None, :, r_kv:], (B, H, L, dr))], -1)
            qf = jnp.concatenate([q_nope, q_rope], -1).transpose(0, 2, 1, 3)
            o = jax.vmap(lambda a, b, c, keep: lat_ops.prefill_attend(
                a, b, c, keep, scale))(
                    qf, k, kv[..., dn:], None if dense else selection)
            o = o.transpose(0, 2, 1, 3)                       # [B,L,H,dv]
        out = _mm("blhv,hvd->bld", o, w_o, dt)
        return out, selection                    # f32: the residual's


# -- the expert layer --------------------------------------------------------

class SparseMoe(nn.Module):
    """Sigmoid-routed top-k over the published experts; this chip's
    held experts' part of the result plus the shared expert."""
    cfg: GlmMoeDsaConfig

    @nn.compact
    def __call__(self, x: jax.Array, live=None) -> jax.Array:
        cfg = self.cfg
        dt = cfg.compute_dtype
        B, L, D = x.shape
        E, F = len(cfg.experts_held), cfg.moe_intermediate_size
        w_g = Weight((D, cfg.router_experts), name="router")()
        bias = self.param("router_bias", nn.initializers.zeros_init(),
                          (cfg.router_experts,), jnp.float32)
        gate = Weight((E, D, F), name="experts_gate")()
        up = Weight((E, D, F), name="experts_up")()
        down = Weight((E, F, D), name="experts_down")()
        xs = x.reshape(B * L, D)
        ids, weights = route(xs, w_g, bias, cfg)
        local = held_index(ids, cfg)                          # [N,k], -1
        y = lat_ops.held_experts_once(xs, local, weights, gate, up, down,
                                      dt, held_share(cfg))
        if live is not None:
            count_held_pairs(self, local.reshape(B, -1), live, E)
        if cfg.n_shared_experts:
            Fs = F * cfg.n_shared_experts
            with jax.named_scope("moe_shared_expert"):
                y = y + swiglu(xs, Weight((D, Fs), name="shared_gate")(),
                               Weight((D, Fs), name="shared_up")(),
                               Weight((Fs, D), name="shared_down")(), dt)
        return y.reshape(B, L, D)                # f32


def route(xs: jax.Array, w_g: jax.Array, bias: jax.Array,
          cfg: GlmMoeDsaConfig):
    """Router of the source (``noaux_tc``): scores in f32; the experts
    are PICKED by ``s + b``, WEIGHTED by ``s`` alone, normalised over
    the picked and scaled. With ``n_group`` > 1 the pick is
    group-limited: the router's experts are ``n_group`` groups of
    consecutive ids, a group scores the sum of its two largest ``s +
    b``, and only the experts of the ``topk_group`` best groups can be
    picked. Returns (ids, weights) [N, k]."""
    logits = jnp.einsum("nd,de->ne", xs.astype(jnp.float32),
                        w_g.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    choice = s + bias[None, :]
    if cfg.n_group > 1:
        grouped = choice.reshape(choice.shape[0], cfg.n_group, -1)
        top2, _ = jax.lax.top_k(grouped, min(2, grouped.shape[-1]))
        _, groups = jax.lax.top_k(jnp.sum(top2, -1), cfg.topk_group)
        kept = jnp.any(groups[:, :, None] == jnp.arange(cfg.n_group),
                       axis=1)                                # [N, G]
        choice = jnp.where(kept[:, :, None], grouped,
                           -jnp.inf).reshape(choice.shape)
    _, ids = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w * cfg.routed_scaling_factor


def held_index(ids: jax.Array, cfg: GlmMoeDsaConfig) -> jax.Array:
    """Expert id -> its index among the experts held here, -1 if it
    lives on another chip."""
    table = jnp.full((cfg.router_experts,), -1, jnp.int32).at[
        jnp.asarray(cfg.experts_held, jnp.int32)].set(
        jnp.arange(len(cfg.experts_held), dtype=jnp.int32))
    return table[ids]


class DenseMlp(nn.Module):
    cfg: GlmMoeDsaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        D, F = cfg.hidden_size, cfg.intermediate_size
        return swiglu(x, Weight((D, F), name="gate")(),
                      Weight((D, F), name="up")(),
                      Weight((F, D), name="down")(),
                      cfg.compute_dtype)         # f32


class Layer(nn.Module):
    cfg: GlmMoeDsaConfig
    spec: LayerSpec

    @nn.compact
    def __call__(self, x, positions, decode: bool, selection, live=None):
        cfg = self.cfg
        dt = cfg.compute_dtype
        y = rms_norm(x, Scale(cfg.hidden_size, name="attn_norm")(),
                     cfg.rms_norm_eps).astype(dt)
        y, selection = LatentAttention(cfg, self.spec, name="attn")(
            y, positions, decode, selection)
        x = x + y
        y = rms_norm(x, Scale(cfg.hidden_size, name="mlp_norm")(),
                     cfg.rms_norm_eps).astype(dt)
        if self.spec.mlp == "sparse":
            y = SparseMoe(cfg, name="moe")(y, live)
        else:
            y = DenseMlp(cfg, name="mlp")(y)
        return x + y, selection


class GlmMoeDsaLM(nn.Module):
    """tokens [B, L] -> logits [B, L, V] f32 (``logits_at`` [B]: only
    at that position of each row, [B, 1, V]). With ``decode=True`` the
    call goes through the ``cache`` collection: ``L > 1`` prefills a
    FRESH row (positions must start at 0), ``L == 1`` is one absorbed
    decode step at each row's own position."""

    cfg: GlmMoeDsaConfig
    mesh: Any = None
    # serve/engine.py: the prefill program asks for the last logits only,
    # and the decode program returns what a step counted (the ``stats``
    # collection below), which the engine sums over the run and hands
    # back to :meth:`summarize_stats`.
    last_logits_only = True
    decode_stats = True

    @nn.compact
    def __call__(self, tokens: jax.Array, *, train: bool = False,
                 decode: bool = False,
                 positions: Optional[jax.Array] = None,
                 logits_at: Optional[jax.Array] = None):
        cfg = self.cfg
        if train:
            raise ValueError("the glm_moe_dsa family has no training path")
        B, L = tokens.shape
        if positions is None:
            if decode:
                raise ValueError("decode=True requires positions")
            positions = jnp.arange(L)[None, :]
        positions = jnp.broadcast_to(positions.astype(jnp.int32), (B, L))
        emb = self.param("tok_emb", nn.initializers.normal(stddev=0.02),
                         (cfg.vocab_size, cfg.hidden_size), PARAM_DTYPE)
        # The residual stream is float32 throughout: the blocks' outputs
        # (float32 accumulations) are added unrounded, and only matmul
        # OPERANDS are the compute dtype. A bfloat16 stream rounds a sum of
        # magnitude 3-6 to 0.02-0.03 at every add, and the error reaches
        # the last full layer's index scores, where it swaps selected keys.
        x = emb[tokens].astype(jnp.float32)
        live = None
        if decode and L == 1 and self.is_mutable_collection("stats"):
            # One step's counters, over LIVE rows: a row at depth 0 is a
            # free slot (an admitted row is at least one token deep). A
            # live row at depth p has p + 1 keys to select from and keeps
            # at most index_topk of them.
            live = positions[:, 0] > 0
            have = jnp.where(live, positions[:, 0] + 1, 0)
            _count(self, "live_rows", jnp.sum(live, dtype=jnp.int32))
            _count(self, "keys_available", jnp.sum(have))
            if all(s.indexer == "none" for s in cfg.layers):
                # A dense layer keeps every key it has, and its attend
                # walks the cache in blocks: what the blocks cover, over
                # ALL slots, as the kernel's grid visits them.
                _count(self, "keys_kept", jnp.sum(have))
                _count(self, "positions_visited",
                       lat_ops.dense_attend_visits(positions[:, 0],
                                                   cfg.max_len))
            else:
                _count(self, "keys_kept",
                       jnp.sum(jnp.minimum(have, cfg.index_topk)))
                # What the layers with a selection gather for their
                # attends: the selection's width a LIVE slot, none of a
                # free slot's.
                _count(self, "rows_gathered",
                       sum(s.indexer != "none" for s in cfg.layers)
                       * lat_ops.live_rows_gathered(
                           positions[:, 0],
                           min(cfg.index_topk, cfg.max_len)))
        selection = None
        for i, spec in enumerate(cfg.layers):
            x, selection = Layer(cfg, spec, name=f"layer_{i}")(
                x, positions, decode, selection, live)
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
        bucket, the form of the expanded attend its program traces (the
        fused kernel or the XLA loop), its blocks and the tiles of the
        score square it computes
        (``ops.latent_attention.prefill_attend_describe``)."""
        cfg = self.cfg
        return {str(b): lat_ops.prefill_attend_describe(
            b, cfg.qk_head_dim, cfg.v_head_dim, cfg.compute_dtype)
            for b in buckets}

    def moe_plan(self, num_slots: int, buckets) -> Dict[str, Any]:
        return describe_moe_plan(self.cfg, self.cfg.moe_intermediate_size,
                                 num_slots, buckets)

    def summarize_stats(self, totals: Dict[str, Any], decode_steps: int
                        ) -> Dict[str, Any]:
        """``serve_summary``'s counters from the ``stats`` collection
        summed over a run's ``decode_steps`` decode steps (host arrays):
        the keys the selection had to choose from and kept (a dense
        model: both the positions its live rows attend, beside the
        positions its attend's blocks covered; a model with a selection:
        the cache rows its gathers moved), the routed pairs that
        landed on the experts held here by expert, and the held experts
        a step reached at all."""
        out: Dict[str, Any] = {"decode_live_rows": int(totals["live_rows"])}
        if int(totals["keys_available"]):
            out.update(
                select_keys_available=int(totals["keys_available"]),
                select_keys_kept=int(totals["keys_kept"]),
                index_keep_share=round(int(totals["keys_kept"])
                                       / int(totals["keys_available"]), 6))
        if "positions_visited" in totals:
            out["attend_positions_visited"] = int(
                totals["positions_visited"])
        if "rows_gathered" in totals:
            out["select_rows_gathered"] = int(totals["rows_gathered"])
        out.update(summarize_moe(totals, decode_steps))
        return out


def held_share(cfg) -> float:
    """The part of a token's routed pairs a configuration expects on the
    experts held here: held over routed experts."""
    return len(cfg.experts_held) / cfg.router_experts


def describe_moe_plan(cfg, expert_width: int, num_slots: int, buckets,
                      model_width: int = 0) -> Dict[str, Any]:
    """``serve_summary.moe_plan``: how ``ops.latent_attention
    .held_experts`` takes the pairs of the decode step (``num_slots``
    tokens) and of each prefill bucket, all static by shape
    (``moe_plan``): the form, the rows of a block, the trips the
    configuration's share takes and the most a routing could force, the
    grouped matmuls' tiles (tm, tk, tn) and the lanes of the result the
    combine holds. ``model_width``: the width the experts read and write where
    it is not ``hidden_size`` (a latent: models/nemotron_h.py)."""
    def one(tokens: int) -> Dict[str, Any]:
        plan = lat_ops.moe_plan(
            tokens, cfg.num_experts_per_tok, len(cfg.experts_held),
            model_width or cfg.hidden_size, expert_width, held_share(cfg))
        return dict(form="one_hot" if plan.one_hot else "gather",
                    block_rows=plan.block_rows,
                    expected_trips=plan.expected_trips,
                    max_trips=plan.max_trips, tiles_in=list(plan.tiles_in),
                    tiles_out=list(plan.tiles_out),
                    combine_tile=plan.combine_tile)
    return {"decode": one(num_slots), **{str(b): one(b) for b in buckets}}


def summarize_moe(totals: Dict[str, Any], decode_steps: int
                  ) -> Dict[str, Any]:
    """The expert layers' part of ``serve_summary`` from a run's summed
    ``stats`` (every ``layer_i/moe`` that counted ``held_pairs`` and
    ``experts_hit``, whichever family's): the routed pairs that landed
    on the experts held here, by expert, and the held experts a step
    reached at all."""
    moe = [v["moe"] for _, v in sorted(totals.items())
           if isinstance(v, dict) and "moe" in v]
    if not moe or not decode_steps:
        return {}
    by_expert = sum(m["held_pairs"] for m in moe)                  # [held]
    return dict(
        moe_layers=len(moe),
        moe_held_pairs=int(by_expert.sum()),
        moe_held_pairs_by_expert=[int(x) for x in by_expert],
        moe_pairs_spread=round(float(
            by_expert.max() / max(by_expert.mean(), 1e-9)), 4),
        moe_pairs_per_expert_step=round(float(
            by_expert.sum() / (by_expert.size * len(moe)
                               * decode_steps)), 6),
        moe_experts_hit=int(sum(int(m["experts_hit"]) for m in moe)))


def count_held_pairs(module: nn.Module, local: jax.Array, live: jax.Array,
                     n_held: int) -> None:
    """One step's (token, expert) pairs of LIVE rows on each expert held
    here, and how many of the held experts a live row reached at all (a
    grouped matmul skips an empty group: only those experts' weights are
    read this step). local [B, pairs a row] the pair's index among the
    held experts or -1; live [B]."""
    pairs = jnp.sum(
        (local[:, :, None] == jnp.arange(n_held)[None, None, :])
        & live[:, None, None], axis=(0, 1)).astype(jnp.int32)
    _count(module, "held_pairs", pairs)
    _count(module, "experts_hit", jnp.sum(pairs > 0, dtype=jnp.int32))


def _count(module: nn.Module, name: str, value: jax.Array) -> None:
    """Add ``value`` to the step's ``stats`` collection under ``name``."""
    module.sow("stats", name, value, reduce_fn=lambda a, b: a + b,
               init_fn=lambda: jnp.zeros_like(value))


def glm_moe_dsa_lm(mesh=None, size: str = "", source: str = "",
                   compute_dtype=jnp.bfloat16, max_len: int = 0,
                   vocab_size: int = 0) -> GlmMoeDsaLM:
    """The family's builder: ``source`` (``--model-config``) is a JSON
    file of the source's keys, the one way its sizes come in (no preset:
    a run that forgets the flag fails, it does not serve a toy)."""
    if size or not source:
        raise ValueError(
            "glm_moe_dsa takes its sizes from --model-config <json of the "
            "source's config.json keys>[#dotted.key] and has no "
            f"--model-size preset (got size={size!r}, "
            f"model_config={source!r})")
    src = dict(load_source(source))
    over: Dict[str, Any] = {"compute_dtype": compute_dtype}
    if max_len:
        over["max_position_embeddings"] = int(max_len)
    if vocab_size:
        over["vocab_size"] = int(vocab_size)
    if mesh is not None and any(
            n > 1 for ax, n in dict(mesh.shape).items() if ax != "data"):
        raise ValueError("glm_moe_dsa serves one chip's share: it has no "
                         "sharded form (a pure data mesh replicates it)")
    return GlmMoeDsaLM(config_from_source(src, **over))
