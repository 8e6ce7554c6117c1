"""The ``minicpm_sala`` family (the source's ``model_type``): a hybrid of
lightning LINEAR-attention layers, whose cache is a fixed-size recurrent
state a slot, and InfLLM-v2 BLOCK-SPARSE grouped-query layers, whose cache
is K and V a position plus mean-pooled keys, under MiniCPM's muP scalings,
served as ONE PIPELINE STAGE of a deployment.

A model of this family is a list of mixer kinds read from the source's own
``mixer_types`` (``num_hidden_layers`` entries from ``first_layer_held``),
not a flag: the parameters AND the decode cache are built from that list.
Per layer (hidden ``D``, RMSNorm eps from the source, no biases):

- **Block.** ``h = x + r Mixer(rms(x))``, ``x' = h + r MLP(rms(h))`` with
  ``r = scale_depth / sqrt(PUBLISHED layers)`` whatever the depth held;
  ``x0 = scale_emb E[tok]``; logits ``W_head (rms(x_L) / (hidden_size /
  dim_model_base))``; the MLP a dense SwiGLU.
- **``lightning-attn``.** ``q, k, v`` as heads x 128; RMSNorm over each
  head of ``q`` and ``k``; RoPE in halves on both; a fixed decay a head
  (``ops.hybrid_attention.decay_slopes``); ``S_t = lambda S_{t-1} + k_t^T
  v_t`` in float32, ``o_t = 128^-1/2 q_t S_t``, no softmax; RMSNorm over
  each head's ``o_t``, times ``sigmoid(W_g u)``, then ``W_o``. What a slot
  keeps is ``S`` (cache leaf ``state`` ``[B, H, 128, 128]`` float32,
  whatever the depth) and nothing a position.
- **``minicpm4``.** 32 query heads over 2 key-value heads, no rotation,
  the same per-head RMSNorm. A query whose context is at most
  ``dense_len`` long attends all of it; a longer one keeps ``topk`` blocks
  of ``block_size`` positions a key-value group, chosen from the pooled
  keys (leaf ``pooled_keys`` ``[B, max_len / kernel_stride, G 128]``:
  window j is the mean of K rows ``stride j .. stride j + kernel_size -
  1``, written when its last row is, FROM the rows in the cache, so that
  the write can be repeated). K and V of a position are one row of the
  leaf ``kv`` ``[B, max_len, 2 G 128]``. The output times ``sigmoid(W_g
  u)``, then ``W_o``.

**A state cannot be rewritten**, unlike a row a position: a decode step
that folded a token into ``S`` and is then computed AGAIN (the engine
drops a step in flight and recomputes it: ``SlotDecodeEngine.drain``)
would fold it twice. The cache therefore carries ``state_pos`` ``[B]``,
the number of tokens each row's states hold: a step at position ``p``
folds iff ``p == state_pos`` and reads ``q S`` either way, and a prefill
leaves ``state_pos = true_len`` with the states AT ``true_len``, not at
the end of the padded bucket (``prefill_true_len``: the engine hands the
prefill program the true length).

Parameters are stored bfloat16 and never materialised in float32. The
family is served only (no training path: the scan has no backward here,
ROADMAP B2).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflow_distributed_tpu.models.glm_moe_dsa import (
    PARAM_DTYPE, Scale, Weight, _count, _mm, load_source, rms_norm, swiglu)
from tensorflow_distributed_tpu.models.transformer import rope_rotate
from tensorflow_distributed_tpu.ops import hybrid_attention as ops
from tensorflow_distributed_tpu.ops import latent_attention as lat_ops

MIXERS = ("lightning-attn", "minicpm4")
#: Positions a block of the prefill's MLP (three ``[block, intermediate]``
#: temporaries instead of three ``[bucket, intermediate]`` ones).
MLP_BLOCK = 4096


@dataclasses.dataclass(frozen=True)
class MiniCpmSalaConfig:
    """Sizes under the SOURCE's key names (``config.json`` of
    ``model_type: minicpm_sala``), plus the layers this stage holds."""
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    lightning_nh: int
    lightning_head_dim: int
    rms_norm_eps: float
    rope_theta: float
    scale_emb: float
    scale_depth: float
    dim_model_base: int
    max_position_embeddings: int
    mixers: Tuple[str, ...]
    # ``num_hidden_layers`` as PUBLISHED: the residual scale's.
    published_layers: int
    sparse: ops.SparseConfig
    compute_dtype: Any = jnp.bfloat16
    causal: bool = True

    @property
    def max_len(self) -> int:
        return self.max_position_embeddings

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_layers)

    @property
    def n_lightning(self) -> int:
        return sum(1 for m in self.mixers if m == "lightning-attn")

    @property
    def state_bytes_per_slot(self) -> int:
        return self.n_lightning * self.lightning_nh \
            * self.lightning_head_dim ** 2 * 4


def mixer_list(src: Dict[str, Any]) -> Tuple[str, ...]:
    """The mixer kinds of the layers held: ``num_hidden_layers`` entries
    of the source's ``mixer_types`` from ``first_layer_held`` (0 when
    absent)."""
    n, lo = int(src["num_hidden_layers"]), int(src.get("first_layer_held", 0))
    kinds = tuple(src["mixer_types"][lo:lo + n])
    if len(kinds) != n or set(kinds) - set(MIXERS):
        raise ValueError(
            f"layers {lo}..{lo + n - 1} of mixer_types "
            f"({len(src['mixer_types'])} entries) must each be one of "
            f"{MIXERS}, got {kinds}")
    return kinds


def config_from_source(src: Dict[str, Any], **overrides
                       ) -> MiniCpmSalaConfig:
    """A configuration from a dict of the source's ``config.json`` keys
    (with its ``sparse_config`` group). What the equations above assume of
    the source's switches is checked, not ignored."""
    want = {"qk_norm": True, "lightning_use_rope": True,
            "use_output_gate": True, "use_output_norm": True,
            "attn_use_output_gate": True, "attn_use_rope": False,
            "lightning_scale": "1/sqrt(d)", "hidden_act": "silu",
            "attention_bias": False, "tie_word_embeddings": False}
    differ = {k: src[k] for k, v in want.items() if src.get(k, v) != v}
    if differ or int(src["lightning_nkv"]) != int(src["lightning_nh"]):
        raise ValueError(
            f"minicpm_sala is written down for {want} and one key and "
            f"value a lightning head; the source says {differ}, "
            f"lightning_nkv {src['lightning_nkv']}")
    kw = dict(
        vocab_size=int(src["vocab_size"]),
        hidden_size=int(src["hidden_size"]),
        intermediate_size=int(src["intermediate_size"]),
        num_attention_heads=int(src["num_attention_heads"]),
        num_key_value_heads=int(src["num_key_value_heads"]),
        head_dim=int(src["head_dim"]),
        lightning_nh=int(src["lightning_nh"]),
        lightning_head_dim=int(src["lightning_head_dim"]),
        rms_norm_eps=float(src["rms_norm_eps"]),
        rope_theta=float(src["rope_theta"]),
        scale_emb=float(src["scale_emb"]),
        scale_depth=float(src["scale_depth"]),
        dim_model_base=int(src["dim_model_base"]),
        max_position_embeddings=int(src["max_position_embeddings"]),
        mixers=mixer_list(src),
        published_layers=int(src.get("num_hidden_layers_published",
                                     src["num_hidden_layers"])),
        sparse=ops.SparseConfig(**{
            f.name: int(src["sparse_config"][f.name])
            for f in dataclasses.fields(ops.SparseConfig)}))
    kw.update(overrides)
    cfg = MiniCpmSalaConfig(**kw)
    if cfg.num_attention_heads % cfg.num_key_value_heads or \
            cfg.lightning_head_dim % 2:
        raise ValueError("query heads divide into the key-value heads, and "
                         "RoPE needs an even lightning_head_dim")
    return cfg


class LightningMixer(nn.Module):
    """Linear attention with a fixed decay a head. ``fold`` [B]: the rows
    of a decode step whose states do not hold this token yet."""
    cfg: MiniCpmSalaConfig

    @nn.compact
    def __call__(self, u, positions, decode: bool, true_len, fold):
        cfg = self.cfg
        dt = cfg.compute_dtype
        B, L, D = u.shape
        H, d = cfg.lightning_nh, cfg.lightning_head_dim
        eps = cfg.rms_norm_eps
        w = {n: Weight((D, H, d), name=n)() for n in ("q", "k", "v", "g")}
        w_o = Weight((H, d, D), name="o")()
        q = rms_norm(_mm("bld,dhe->blhe", u, w["q"], dt),
                     Scale(d, name="q_norm")(), eps)
        k = rms_norm(_mm("bld,dhe->blhe", u, w["k"], dt),
                     Scale(d, name="k_norm")(), eps)
        # RoPE in halves (rotate_half: pair i is (x[i], x[i + d/2])), f32
        q = rope_rotate(q, positions, cfg.rope_theta).astype(dt)
        k = rope_rotate(k, positions, cfg.rope_theta).astype(dt)
        v = _mm("bld,dhe->blhe", u, w["v"], dt).astype(dt)
        slopes = ops.decay_slopes(H)
        if decode and L == 1:
            S = self.variable("cache", "state", jnp.zeros, (B, H, d, d),
                              jnp.float32)
            S.value, o = ops.lightning_state_step(
                S.value, q[:, 0], k[:, 0], v[:, 0], slopes, fold,
                positions[:, 0])
            o = o[:, None]
        else:
            o, last = ops.lightning_chunk_scan(q, k, v, slopes, true_len)
            if decode:
                self.variable("cache", "state", jnp.zeros, (B, H, d, d),
                              jnp.float32).value = last
        o = rms_norm(o * d ** -0.5,
                     Scale(H * d, name="o_norm")().reshape(H, d), eps)
        o = o * jax.nn.sigmoid(_mm("bld,dhe->blhe", u, w["g"], dt))
        return _mm("blhe,hed->bld", o, w_o, dt)


class SparseMixer(nn.Module):
    """InfLLM-v2 grouped-query attention. ``live`` [B]: the rows of a
    decode step that belong to a request."""
    cfg: MiniCpmSalaConfig

    @nn.compact
    def __call__(self, u, positions, decode: bool, live):
        cfg, sp = self.cfg, self.cfg.sparse
        dt = cfg.compute_dtype
        B, L, D = u.shape
        H, G, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        eps, scale = cfg.rms_norm_eps, d ** -0.5
        w_q = Weight((D, H, d), name="q")()
        w_k = Weight((D, G, d), name="k")()
        w_v = Weight((D, G, d), name="v")()
        w_g = Weight((D, H, d), name="g")()
        w_o = Weight((H, d, D), name="o")()
        q = rms_norm(_mm("bld,dhe->blhe", u, w_q, dt),
                     Scale(d, name="q_norm")(), eps).astype(dt)
        k = rms_norm(_mm("bld,dge->blge", u, w_k, dt),
                     Scale(d, name="k_norm")(), eps).astype(dt)
        v = _mm("bld,dge->blge", u, w_v, dt).astype(dt)
        q = q.reshape(B, L, G, H // G, d)
        if decode:
            T = cfg.max_len
            ckv = self.variable("cache", "kv", jnp.zeros,
                                (B, T, 2 * G * d), dt)
            cpk = self.variable("cache", "pooled_keys", jnp.zeros,
                                (B, sp.pooled_len(T), G * d), dt)
            ckv.value = lat_ops.write_rows(
                ckv.value, jnp.concatenate(
                    [k.reshape(B, L, G * d), v.reshape(B, L, G * d)], -1),
                positions[:, 0])
        if decode and L == 1:
            pos = positions[:, 0]
            j, mean = ops.window_of_step(ckv.value, pos, sp, G * d)
            cpk.value = lat_ops.write_rows(cpk.value, mean, j)
            grp = ops.sparse_block_scores(q[:, 0], cpk.value,
                                          sp.windows_seen(pos), pos, scale)
            idx, valid = ops.decode_selection(grp, pos, sp,
                                              -(-T // sp.block_size))
            o = ops.sparse_block_attend(q[:, 0], ckv.value, idx, valid, pos,
                                        sp, scale)
            # A live row whose context is at most dense_len attends all of
            # it: a branch the device takes only while such a row is live.
            dense = pos + 1 <= sp.dense_len
            limit = min(sp.dense_len, T)
            o = jax.lax.cond(
                jnp.any(live & dense),
                lambda: jnp.where(
                    dense[:, None, None, None], ops.dense_decode_attend(
                        q[:, 0], ckv.value, pos, limit, scale), o),
                lambda: o)[:, None]
        else:
            # A fresh row: the new tokens ARE the whole context.
            kc = jax.vmap(lambda a: ops.pool_keys(a, sp))(
                k.reshape(B, L, G * d))
            if decode and kc.shape[1]:
                cpk.value = lat_ops.write_rows(
                    cpk.value, kc, jnp.zeros((B,), jnp.int32))
            dense = L <= sp.dense_len

            def one(qb, kb, vb, kcb):
                keep = None if dense else ops.prefill_selection(
                    qb, kcb.reshape(-1, G, d), sp, scale)
                return ops.sparse_prefill_attend(qb, kb, vb, keep, sp, scale)

            o = jax.vmap(one)(q, k, v, kc)
        o = o.reshape(B, L, H, d) * jax.nn.sigmoid(
            _mm("bld,dhe->blhe", u, w_g, dt))
        return _mm("blhe,hed->bld", o, w_o, dt)


class Layer(nn.Module):
    cfg: MiniCpmSalaConfig
    kind: str

    @nn.compact
    def __call__(self, x, positions, decode: bool, true_len, fold, live):
        cfg = self.cfg
        dt = cfg.compute_dtype
        D = cfg.hidden_size
        r = cfg.residual_scale
        u = rms_norm(x, Scale(D, name="attn_norm")(),
                     cfg.rms_norm_eps).astype(dt)
        if self.kind == "lightning-attn":
            y = LightningMixer(cfg, name="mixer")(u, positions, decode,
                                                  true_len, fold)
        else:
            y = SparseMixer(cfg, name="mixer")(u, positions, decode, live)
        x = x + r * y
        u = rms_norm(x, Scale(D, name="mlp_norm")(),
                     cfg.rms_norm_eps).astype(dt)
        return x + r * Mlp(cfg, name="mlp")(u)


class Mlp(nn.Module):
    """Dense SwiGLU; a long prefill goes through it in blocks of
    positions."""
    cfg: MiniCpmSalaConfig

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        dt = cfg.compute_dtype
        D, F = cfg.hidden_size, cfg.intermediate_size
        gate, up, down = (Weight((D, F), name="gate")(),
                          Weight((D, F), name="up")(),
                          Weight((F, D), name="down")())
        B, L, _ = u.shape
        if L <= MLP_BLOCK or L % MLP_BLOCK:
            return swiglu(u, gate, up, down, dt)
        y = jax.lax.map(lambda ub: swiglu(ub, gate, up, down, dt),
                        u.reshape(B, L // MLP_BLOCK, MLP_BLOCK, D
                                  ).transpose(1, 0, 2, 3))
        return y.transpose(1, 0, 2, 3).reshape(B, L, D)


class MiniCpmSalaLM(nn.Module):
    """tokens [B, L] -> logits [B, L, V] f32 (``logits_at`` [B]: only at
    that position of each row, [B, 1, V]). With ``decode=True`` the call
    goes through the ``cache`` collection: ``L > 1`` prefills a FRESH row
    (positions start at 0; ``true_len``: the tokens that count, the rest
    of the row is a bucket's padding), ``L == 1`` is one decode step at
    each row's own position."""

    cfg: MiniCpmSalaConfig
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
        cfg, sp = self.cfg, self.cfg.sparse
        if train:
            raise ValueError("the minicpm_sala family has no training path")
        B, L = tokens.shape
        if positions is None:
            if decode:
                raise ValueError("decode=True requires positions")
            positions = jnp.arange(L)[None, :]
        positions = jnp.broadcast_to(positions.astype(jnp.int32), (B, L))
        emb = self.param("tok_emb", nn.initializers.normal(stddev=0.02),
                         (cfg.vocab_size, cfg.hidden_size), PARAM_DTYPE)
        # float32 residual stream, as the other served family's: only
        # matmul OPERANDS are the compute dtype.
        x = cfg.scale_emb * emb[tokens].astype(jnp.float32)
        fold = live = None
        if decode:
            held = self.variable("cache", "state_pos", jnp.zeros, (B,),
                                 jnp.int32)
            if L == 1:
                pos = positions[:, 0]
                # A row at depth 0 is a free slot (an admitted row is at
                # least one token deep): its states are not touched.
                live = pos > 0
                fold = live & (pos == held.value)
                held.value = jnp.where(fold, pos + 1, held.value)
            else:
                held.value = jnp.broadcast_to(jnp.asarray(
                    L if true_len is None else true_len, jnp.int32), (B,))
        if live is not None and self.is_mutable_collection("stats"):
            n_live = ops.live_slots(pos)[1]
            _count(self, "live_rows", n_live)
            _count(self, "keys_available", jnp.sum(
                jnp.where(live, pos + 1, 0)))
            _count(self, "keys_kept", jnp.sum(
                jnp.where(live, sp.kept_positions(pos), 0)))
            _count(self, "blocks_kept", jnp.sum(jnp.where(
                live, cfg.num_key_value_heads * jnp.where(
                    pos + 1 <= sp.dense_len, pos // sp.block_size + 1,
                    sp.topk), 0)))
            _count(self, "rows_dense", jnp.sum(
                live & (pos + 1 <= sp.dense_len), dtype=jnp.int32))
            # the state step's loop runs once a live slot a layer
            _count(self, "state_rows_stepped", cfg.n_lightning * n_live)
        for i, kind in enumerate(cfg.mixers):
            x = Layer(cfg, kind, name=f"layer_{i}")(
                x, positions, decode, true_len, fold, live)
        if logits_at is not None:
            x = jnp.take_along_axis(
                x, jnp.broadcast_to(logits_at.astype(jnp.int32),
                                    (B,))[:, None, None], axis=1)
        x = rms_norm(x, Scale(cfg.hidden_size, name="final_norm")(),
                     cfg.rms_norm_eps) / (cfg.hidden_size
                                          / cfg.dim_model_base)
        head = Weight((cfg.hidden_size, cfg.vocab_size), name="lm_head")()
        return _mm("bld,dv->blv", x, head, cfg.compute_dtype)

    def summarize_stats(self, totals: Dict[str, Any], decode_steps: int
                        ) -> Dict[str, Any]:
        """``serve_summary``'s counters from the ``stats`` collection
        summed over a run's decode steps: the positions a live row's
        sparse layers could attend and did (a layer's, a key-value
        group's), the blocks kept (both groups), the row-steps at or under
        ``dense_len``, and the slot-rows whose state a step moved beside
        the live rows."""
        del decode_steps
        out: Dict[str, Any] = {
            "decode_live_rows": int(totals["live_rows"]),
            "state_rows_stepped": int(totals["state_rows_stepped"]),
            "state_bytes_per_slot": self.cfg.state_bytes_per_slot,
            "sparse_blocks_kept": int(totals["blocks_kept"]),
            "sparse_rows_dense": int(totals["rows_dense"])}
        if int(totals["keys_available"]):
            out.update(
                select_keys_available=int(totals["keys_available"]),
                select_keys_kept=int(totals["keys_kept"]),
                index_keep_share=round(int(totals["keys_kept"])
                                       / int(totals["keys_available"]), 6))
        return out


def minicpm_sala_lm(mesh=None, size: str = "", source: str = "",
                    compute_dtype=jnp.bfloat16, max_len: int = 0,
                    vocab_size: int = 0) -> MiniCpmSalaLM:
    """The family's builder: ``source`` (``--model-config``) is a JSON
    file of the source's keys, the one way its sizes come in."""
    if size or not source:
        raise ValueError(
            "minicpm_sala takes its sizes from --model-config <json of the "
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
        raise ValueError("minicpm_sala serves one pipeline stage on one "
                         "chip: it has no sharded form (a pure data mesh "
                         "replicates it)")
    return MiniCpmSalaLM(config_from_source(dict(load_source(source)),
                                            **over))
