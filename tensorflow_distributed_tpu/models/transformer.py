"""Transformer encoder / BERT-style MLM with TP + SP shardings.

The BASELINE.json stretch config ("BERT-base MLM pretrain — prove the
ps->allreduce port generalizes past convnets"). The reference has no
sequence models (SURVEY.md §5), so this family is designed TPU-first
with no reference counterpart to mirror:

- **Tensor parallelism** (mesh "model" axis), Megatron-style: attention
  heads and MLP hidden dim are sharded via ``nn.with_partitioning``
  metadata; XLA's SPMD partitioner inserts the two allreduces per block
  (after attention out-proj and MLP down-proj) — nobody writes them.
- **Sequence parallelism** (mesh "seq" axis): activations are sharded
  along the sequence dim end-to-end; attention runs as exact ring
  attention (parallel.ring_attention) with K,V blocks rotating over ICI
  via ppermute.
- bf16 compute / f32 params, f32 layernorm and softmax statistics.

Layout conventions (matched to ``parallel.sharding.param_sharding``):
    qkv kernel   [d_model, 3, H, Dh]   P(None, None, "model", None)
    out kernel   [H, Dh, d_model]      P("model", None, None)
    mlp up       [d_model, d_ff]       P(None, "model")
    mlp down     [d_ff, d_model]       P("model", None)
    embeddings   [vocab, d_model]      replicated by default;
                                       P("model", None) with shard_vocab
                                       (Megatron vocab-parallel table)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tensorflow_distributed_tpu.parallel.mesh import (
    AXIS_DATA, AXIS_MODEL, AXIS_SEQ)
from tensorflow_distributed_tpu.ops.flash_attention import attention
from tensorflow_distributed_tpu.parallel.ring_attention import ring_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 30522          # BERT-base WordPiece vocab
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_len: int = 512
    dropout_rate: float = 0.1
    compute_dtype: Any = jnp.bfloat16
    remat: bool = False              # jax.checkpoint each block
    # "full": save only block boundaries (max recompute, min HBM);
    # "dots": jax.checkpoint_policies.dots_saveable — keep matmul
    # outputs, recompute the cheap elementwise tail (the usual sweet
    # spot on TPU where HBM bandwidth, not FLOPs, binds).
    remat_policy: str = "full"
    causal: bool = False             # autoregressive (GPT) vs bidirectional
    # TP partition metadata on kernels. Disabled by the pipelined
    # variant: flax's DenseGeneral validates params at apply by
    # eval_shape-ing its init, which flattens multi-dim kernels to 2D
    # and then applies the 4-axis partition constraint to the flat
    # value — a rank mismatch that only errors inside a manual-axes
    # shard_map (the pipeline's). With mesh model == 1 the metadata is
    # meaningless there anyway.
    tp_partitioning: bool = True
    # Pallas flash attention on TPU. Works in the pipelined variant
    # too: the dispatcher (ops.flash_attention.attention) nests a
    # shard_map over the remaining auto axes inside the pipe-manual
    # region, so the Mosaic call sees fully-manual axes ("Mosaic
    # kernels cannot be automatically partitioned" otherwise).
    use_flash: bool = True
    # Sliding-window attention (Mistral-style): each token attends
    # to the last `attn_window` positions only (0 = full causal).
    # Causal families only; rides the flash kernel's block-skip so
    # compute is O(L * W) not O(L^2 / 2), and the decode path masks
    # cache entries older than the window. Long-context note: at
    # W << L this replaces ring attention (mesh.seq must be 1 —
    # windowing the zigzag schedule is not implemented).
    attn_window: int = 0
    # KV-cache storage for decode: "none" (cache in compute dtype)
    # or "int8" (per-(token, head) absmax quantization; the attend
    # consumes int8 directly via exact scale-adjusted dots, so the
    # full-cache HBM read — decode's dominant traffic — halves vs
    # bf16). Composes with GQA: n_kv_heads narrows the cache,
    # int8 thins it.
    kv_cache_quant: str = "none"  # none | int8
    # Paged KV cache for decode (serve/paging): > 0 replaces the
    # per-row [B, max_len, ...] cache with a shared page pool
    # [kv_num_pages, kv_page_size, ...] addressed through a per-row
    # ``page_table`` ([B, max_pages] int32, max_pages * page_size ==
    # max_len). Writes scatter each token's K/V into
    # (table[pos // page_size], pos % page_size); reads gather the
    # row's pages back into the SAME [B, max_len, ...] logical layout
    # the dense path attends — the attend itself (masking, scale
    # handling, numerics) is shared, so paged and dense decode produce
    # identical math over identical cache bytes. 0 = dense (default;
    # generate()/beam and the plain serve engine never pay paging).
    kv_page_size: int = 0
    # Physical pages in the pool (required > 0 when kv_page_size > 0;
    # page 0 is the serve engine's write-off page for freed rows).
    kv_num_pages: int = 0
    # Mixture-of-Experts: 0 = dense MLP; > 0 replaces every block's MLP
    # with an expert-parallel MoeMlp (models/moe.py).
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # Routing-group length (0 = whole sequence); see
    # models/moe.py's scale-envelope note.
    moe_group_len: int = 0
    # Token-movement formulation: "dense" (GShard one-hot
    # einsums) or "scatter" (slot scatter/gather); models/moe.py.
    moe_dispatch: str = "dense"
    # Mesh axis the expert dim shards over: "model" (the default — EP
    # composes with TP's axis) or the dedicated "expert" axis
    # (MeshConfig.expert). moe_lm auto-selects "expert" when the mesh
    # has one.
    moe_expert_axis: str = AXIS_MODEL
    # Position encoding: "learned" (additive embedding, the GPT-2/BERT
    # scheme) or "rope" (rotary, applied to q/k per layer — relative
    # positions, the modern long-context default). RoPE composes with
    # flash/ring attention unchanged: rotation happens BEFORE the
    # kernel sees q/k, and it's elementwise along the sequence dim so
    # seq-sharding partitions it like any other activation op.
    pos_emb: str = "learned"  # learned | rope
    rope_theta: float = 10000.0
    # Share the input embedding as the output projection (GPT-2 ties
    # them): logits = x @ tok_emb.T via nn.Embed.attend. Saves a
    # [d_model, vocab] matrix and its optimizer slots; the [MASK]
    # sentinel row (extra_vocab) is sliced off the logits.
    tie_embeddings: bool = False
    # Grouped-query attention: number of K/V heads (None = n_heads,
    # standard MHA; 1 = MQA). Q keeps n_heads; K/V project to
    # n_kv_heads and broadcast to the query heads right before each
    # attend, so the flash/ring kernels and the XLA oracle are
    # untouched — what shrinks is the KV projection params and,
    # crucially, the decode cache: [B, max_len, n_kv, Dh] instead of
    # [B, max_len, H, Dh] (the decode-bandwidth win GQA exists for).
    n_kv_heads: Optional[int] = None
    # MLP nonlinearity: "gelu" (GPT-2/BERT two-matrix MLP) or "swiglu"
    # (gated: silu(gate(x)) * up(x) -> down; the Llama-family MLP).
    mlp_variant: str = "gelu"  # gelu | swiglu
    # Shard the token-embedding table's vocab dim over the "model"
    # axis (Megatron's vocab-parallel embedding). At vocab 50257 the
    # table + its Adam slots are ~460 MB f32 per replica on GPT-2-small
    # — the knob that splits them across TP ranks. The untied lm_head
    # already shards vocab this way; this extends it to the input table
    # and the tied path (logits come out vocab-sharded; GSPMD inserts
    # the gather/reduce where the loss needs them). Requires
    # tp_partitioning (i.e. not the pipelined family).
    shard_vocab: bool = False
    # Block normalization: "layernorm" (mean+variance, bias+scale) or
    # "rmsnorm" (scale-only, no mean subtraction — cheaper and the
    # modern default). Both run in f32.
    norm: str = "layernorm"  # layernorm | rmsnorm
    # Model-health activation taps (--observe.health-taps): each block
    # sows the f32 RMS of its output into the transient "health"
    # collection; the train step folds it into the cadence-gated
    # per-layer health metrics (observe/health.py). Off by default —
    # a tap is one elementwise reduction per block per step, but it
    # also pins the residual stream as a live value, so it is a knob,
    # not a constant. Sown only when the "health" collection is
    # mutable (training forward passes), so eval/decode never pay it.
    health_taps: bool = False


def train_flash_plan(cfg, seq_len: int, mesh=None) -> Optional[dict]:
    """The flash kernels' plan for a training forward of ``seq_len``
    tokens through a model of config ``cfg`` (what the run's ``start``
    record carries as ``flash_plan``), or None where the step does not
    reach ``flash_attention``: another model family, ``use_flash``
    off, off the TPU, a shape the kernel refuses, or the seq-sharded
    ring path (its partial calls plan for their own shard lengths)."""
    from tensorflow_distributed_tpu.ops.flash_attention import (
        flash_plan, use_flash)
    if not isinstance(cfg, TransformerConfig) or not cfg.use_flash:
        return None
    if mesh is not None and mesh.shape[AXIS_SEQ] > 1:
        return None
    head_dim = cfg.d_model // cfg.n_heads
    if not use_flash(seq_len, seq_len, head_dim, cfg.compute_dtype):
        return None
    return flash_plan(seq_len, seq_len, head_dim, cfg.compute_dtype,
                      causal=cfg.causal,
                      window=cfg.attn_window).describe()


def bert_base_config(**overrides) -> TransformerConfig:
    return dataclasses.replace(TransformerConfig(), **overrides)


def tiny_config(**overrides) -> TransformerConfig:
    """Small config for tests/CI: same code paths, toy scale."""
    base = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                             n_heads=4, d_ff=64, max_len=128,
                             dropout_rate=0.0, compute_dtype=jnp.float32)
    return dataclasses.replace(base, **overrides)


def resolve_remat_policy(name: str):
    """remat_policy name -> jax.checkpoint policy (the ONE mapping,
    shared by TransformerLM and PipelinedLM)."""
    if name == "dots":
        return jax.checkpoint_policies.dots_saveable
    if name == "full":
        return None
    raise ValueError(f"remat_policy {name!r}; have ('full', 'dots')")


def _dense_init():
    return nn.initializers.normal(stddev=0.02)  # BERT-style


def rope_rotate(x: jax.Array, positions: jax.Array,
                theta: float = 10000.0) -> jax.Array:
    """Rotary position embedding (Su et al., RoFormer).

    x: [B, L, H, Dh] (Dh even), positions: [B, L] or [1, L] int.
    Rotates each (x[2i], x[2i+half]) pair by positions * theta^(-i/half)
    in f32 (angle precision matters at long context), returning x's
    dtype. The defining property — attention scores depend only on
    RELATIVE position — is pinned in tests/test_rope.py.
    """
    if x.shape[-1] % 2:
        raise ValueError(
            f"rope needs an even head dim, got Dh={x.shape[-1]}")
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [B,L,half]
    cos = jnp.cos(angles)[..., None, :]                        # [B,L,1,half]
    sin = jnp.sin(angles)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _auto_expert_axis(mesh, overrides) -> None:
    """Any MoE config on a mesh with a real dedicated "expert" axis
    defaults to sharding experts over it — otherwise wi/wo would name
    the size-1 "model" axis and the expert-axis device group would do
    fully redundant work with no warning."""
    if (overrides.get("moe_experts", 0) > 0 and mesh is not None
            and dict(mesh.shape).get("expert", 1) > 1):
        overrides.setdefault("moe_expert_axis", "expert")


def _auto_tp_partitioning(mesh, overrides) -> None:
    """Default TP metadata OFF when the mesh has no model axis to shard
    over: the annotations are meaningless at mesh.model == 1 (the
    pipelined variant already disables them for the same reason) and
    flax-version skew can make the boxed with_sharding_constraint
    reject them outright at init. shard_vocab keeps them (its vocab-
    parallel embedding requires the metadata), as does an explicit
    tp_partitioning override, and a mesh-less build keeps the factory
    default (pure metadata, nothing constrains it)."""
    if overrides.get("shard_vocab"):
        return
    if mesh is not None and dict(mesh.shape).get("model", 1) == 1:
        overrides.setdefault("tp_partitioning", False)



def _maybe_partitioned(cfg, names):
    """kernel_init with TP metadata, or plain when tp_partitioning=False
    (see TransformerConfig.tp_partitioning for why)."""
    init = _dense_init()
    return nn.with_partitioning(init, names) if cfg.tp_partitioning else init


class SelfAttention(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool = False,
                 decode: bool = False,
                 positions: Optional[jax.Array] = None,
                 page_table: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        h, dh = cfg.n_heads, cfg.d_model // cfg.n_heads
        # None AND 0 both mean MHA (TrainConfig uses 0 as its sentinel).
        nk = cfg.n_kv_heads or h
        if h % nk:
            raise ValueError(
                f"n_heads {h} not divisible by n_kv_heads {nk}")
        if nk == h:
            # Standard MHA: one fused projection (param tree unchanged
            # from before GQA existed — checkpoints stay loadable).
            qkv = nn.DenseGeneral(
                features=(3, h, dh), axis=-1, use_bias=True,
                kernel_init=_maybe_partitioned(
                    cfg, (None, None, AXIS_MODEL, None)),
                dtype=cfg.compute_dtype, name="qkv")(x)
            q, k, v = (qkv[..., 0, :, :], qkv[..., 1, :, :],
                       qkv[..., 2, :, :])
        else:
            q = nn.DenseGeneral(
                features=(h, dh), axis=-1, use_bias=True,
                kernel_init=_maybe_partitioned(cfg, (None, AXIS_MODEL, None)),
                dtype=cfg.compute_dtype, name="q")(x)
            # K/V kernels stay replicated: nk is typically smaller than
            # the TP axis, and the tensors are small by construction.
            kv = nn.DenseGeneral(
                features=(2, nk, dh), axis=-1, use_bias=True,
                kernel_init=_dense_init(),
                dtype=cfg.compute_dtype, name="kv")(x)
            k, v = kv[..., 0, :, :], kv[..., 1, :, :]

        def widen(t):
            """[B, L, nk, Dh] -> [B, L, H, Dh] for the attend."""
            return (t if nk == h else
                    jnp.repeat(t, h // nk, axis=2))
        if cfg.pos_emb == "rope":
            if positions is None:
                raise ValueError("pos_emb='rope' needs positions")
            # Rotate BEFORE caching/dispatch: cached keys are stored
            # rotated, so decode attends rotated-q against rotated-k
            # with no per-step re-rotation of the cache.
            q = rope_rotate(q, positions, cfg.rope_theta)
            k = rope_rotate(k, positions, cfg.rope_theta)
        if decode:
            # KV-cache incremental decoding: stash k/v at each row's
            # position, attend q (the L new tokens) against the whole
            # cache with a position mask. Static shapes throughout —
            # the cache is always [B, max_len, H, Dh]. POSITIONS are
            # the authority on where writes land (they already had to
            # be per-step correct for RoPE and the mask): rows may sit
            # at DIFFERENT depths — the serving engine's slots
            # (serve/engine.py) decode a [num_slots] batch whose
            # requests joined at different times — so writes are
            # per-row dynamic_update_slices vmapped over the batch.
            # A [1, L] positions array broadcasts to the whole batch
            # (the generate()/beam path, every row in lockstep).
            if not cfg.causal:
                raise ValueError("decode=True needs a causal config")
            B, L = x.shape[0], x.shape[1]
            from tensorflow_distributed_tpu.ops import kv_attend, kv_write
            from tensorflow_distributed_tpu.parallel.ring_attention import (
                full_attention)
            quant = cfg.kv_cache_quant == "int8"
            cache_dt = jnp.int8 if quant else k.dtype
            paged = cfg.kv_page_size > 0
            if paged:
                # Paged layout (serve/paging): the cache is a POOL of
                # fixed-size pages shared by every row; ``page_table``
                # maps each row's logical pages to physical ones. The
                # write/read addressing below is the only paged code —
                # masking and the attend are the dense path's.
                if page_table is None:
                    raise ValueError(
                        "kv_page_size > 0 needs a page_table "
                        "([B, max_pages] int32)")
                npages, psz = cfg.kv_num_pages, cfg.kv_page_size
                if npages < 2:
                    raise ValueError(
                        f"kv_num_pages must be >= 2 (page 0 is the "
                        f"write-off page), got {npages}")
                if page_table.shape != (B, cfg.max_len // psz) or \
                        cfg.max_len % psz:
                    raise ValueError(
                        f"page_table {page_table.shape} must be "
                        f"[B={B}, max_len/page_size="
                        f"{cfg.max_len}/{psz}] (max_len must divide "
                        f"evenly into pages)")
                kv_shape = (npages, psz, nk, dh)
                sc_shape = (npages, psz, nk)
            else:
                kv_shape = (B, cfg.max_len, nk, dh)
                sc_shape = (B, cfg.max_len, nk)
            ck = self.variable("cache", "key", jnp.zeros,
                               kv_shape, cache_dt)
            cv = self.variable("cache", "value", jnp.zeros,
                               kv_shape, cache_dt)
            if quant:
                # Per-(token, head) absmax scales — the standard
                # inference quantization grain: one f32 per cached
                # row, 2*dh fewer bytes than the row it scales.
                cks = self.variable("cache", "key_scale", jnp.zeros,
                                    sc_shape, jnp.float32)
                cvs = self.variable("cache", "value_scale", jnp.zeros,
                                    sc_shape, jnp.float32)
            ci = self.variable("cache", "index",
                               lambda: jnp.zeros((), jnp.int32))
            pos = positions.astype(jnp.int32)       # [1 | B, L]
            # Each row's L new tokens are contiguous from its first
            # position (prefill: arange; decode: a single token).
            start = jnp.broadcast_to(pos[:, :1], (B, 1))[:, 0]  # [B]

            def _row_put(buf, new, s):
                return jax.lax.dynamic_update_slice(
                    buf, new, (s,) + (0,) * (new.ndim - 1))

            if paged:
                # Scatter each token's K/V into its physical page:
                # pid = table[pos // page_size], off = pos % page_size.
                # Positions stay the single authority on depth — the
                # table only relocates where a position's bytes live.
                # Bucket-padding positions PAST the cache end (a tail
                # prefill at offset m may pad to m + bucket > max_len;
                # a dense row had max_len of slack for that garbage)
                # park in the write-off page 0, which no table ever
                # exposes to an unmasked column.
                posb = jnp.broadcast_to(pos, (B, L))
                lp = jnp.minimum(posb // psz, page_table.shape[1] - 1)
                pid = jnp.take_along_axis(
                    page_table.astype(jnp.int32), lp, axis=1)
                pid = jnp.where(posb < cfg.max_len, pid, 0)
                off = posb % psz                              # [B, L]

                def put(buf, new, _start):
                    return buf.at[pid, off].set(new)
            elif L == 1 and kv_write.use_token_write(
                    kv_shape, cache_dt, self.mesh):
                # One token a row on the TPU: the scatter loop the
                # vmapped update lowers to costs 8 us a ROW there (the
                # cache is kept T-minor); the Pallas write does the
                # row's one lane block in place (ops/kv_write.py).
                put = kv_write.token_write
            else:
                put = jax.vmap(_row_put)

            def q8(x):
                scale = jnp.maximum(
                    jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
                    / 127.0, 1e-8)                     # [B, L, nk]
                rounded = jnp.round(x.astype(jnp.float32)
                                    / scale[..., None])
                return (jnp.clip(rounded, -127, 127).astype(jnp.int8),
                        scale)

            if quant:
                k8, ks = q8(k)
                v8, vs = q8(v)
                ck.value = put(ck.value, k8, start)
                cv.value = put(cv.value, v8, start)
                cks.value = put(cks.value, ks, start)
                cvs.value = put(cvs.value, vs, start)
            else:
                ck.value = put(ck.value, k, start)
                cv.value = put(cv.value, v, start)
            # Scalar running index kept for callers that step every row
            # in lockstep (meaningless for mixed-depth slot batches —
            # positions are the authority either way).
            ci.value = start[0] + L
            from tensorflow_distributed_tpu.ops.flash_attention import (
                NEG_INF, window_keep)
            cols = jnp.arange(cfg.max_len)[None, None, :]
            # The SAME (pos - window, pos] band as training
            # (window_keep is the one construction), per row: cache
            # entries past each row's position — or older than the
            # window — are masked out. [1 | B, L, max_len].
            bias = jnp.where(
                window_keep(pos[:, :, None], cols, cfg.attn_window),
                0.0, float(NEG_INF))
            def grouped_attend(kc, vc, kscale=None, vscale=None):
                # ONE grouped attend for every cache layout (g == 1
                # covers MHA): narrow (GQA) caches stay narrow, and
                # int8 caches pass their per-(token, head) scales —
                # the scale-adjusted dots are mathematically exact
                # rescalings (q.dequant(K)^T = (q.K8^T) * kscale[col];
                # P.dequant(V) = (P * vscale[col]).V8), so no
                # dequantized cache is ever materialized and the only
                # full-cache HBM reads are int8. Rows are never fully
                # masked (the just-written diagonal entry at col
                # each row's position is always inside its band), so plain
                # softmax is safe.
                g = h // nk
                qg = q.reshape(B, L, nk, g, dh).astype(jnp.float32)
                s = jnp.einsum("bqngd,bknd->bngqk", qg,
                               kc.astype(jnp.float32))
                if kscale is not None:
                    s = s * kscale.transpose(0, 2, 1)[:, :, None, None]
                s = s / jnp.sqrt(jnp.asarray(dh, jnp.float32))
                s = s + bias[:, None, None]
                p = jax.nn.softmax(s, axis=-1)
                if vscale is not None:
                    p = p * vscale.transpose(0, 2, 1)[:, :, None, None]
                o = jnp.einsum("bngqk,bknd->bqngd", p,
                               vc.astype(jnp.float32))
                return o.reshape(B, L, h, dh).astype(q.dtype)

            if paged:
                # Gather the row's pages back into the SAME
                # [B, max_len, ...] logical layout the dense attend
                # reads — identical bytes in identical order, so the
                # shared attend below is numerically the dense one.
                def gathered(buf):
                    g = buf[page_table.astype(jnp.int32)]
                    return g.reshape((B, cfg.max_len) + buf.shape[2:])

                kc_v, vc_v = gathered(ck.value), gathered(cv.value)
                ks_v = gathered(cks.value) if quant else None
                vs_v = gathered(cvs.value) if quant else None
            else:
                kc_v, vc_v = ck.value, cv.value
                ks_v = cks.value if quant else None
                vs_v = cvs.value if quant else None
            if quant:
                out = grouped_attend(kc_v, vc_v, ks_v, vs_v)
            elif (L == 1 and not paged and not cfg.attn_window
                    and kv_attend.use_kv_attend(kv_shape, cache_dt,
                                                self.mesh)):
                # One query a row on the TPU: the attends above read the
                # WHOLE [B, max_len] leaf whatever is live (3.0 of the
                # 6.1 GB a GPT-2-large step moves); the Pallas attend
                # walks each live row's blocks to its depth, in place
                # (ops/kv_attend.py).
                out = kv_attend.decode_attend(q, kc_v, vc_v, start, v)
            elif nk == h:
                out = full_attention(q, kc_v, vc_v, bias)
            else:
                out = grouped_attend(kc_v, vc_v)
        elif self.mesh is not None and self.mesh.shape[AXIS_SEQ] > 1:
            if cfg.attn_window:
                raise ValueError(
                    "attn_window with mesh.seq > 1 is not "
                    "implemented (the zigzag ring schedule is not "
                    "windowed); at W << L the window IS the "
                    "long-context strategy — use mesh.seq == 1")
            out = ring_attention(q, widen(k), widen(v), self.mesh,
                                 causal=cfg.causal)
        else:
            # Pallas flash kernel on TPU (shard_mapped over dp x tp when
            # the mesh is partitioned), XLA oracle elsewhere.
            out = attention(q, widen(k), widen(v), causal=cfg.causal,
                            window=cfg.attn_window, mesh=self.mesh,
                            allow_flash=cfg.use_flash)
        out = nn.DenseGeneral(
            features=cfg.d_model, axis=(-2, -1), use_bias=True,
            kernel_init=_maybe_partitioned(cfg, (AXIS_MODEL, None, None)),
            dtype=cfg.compute_dtype, name="out")(out)
        return out


def _norm(cfg, name: str):
    """Block normalization module per cfg.norm, f32 either way."""
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(dtype=jnp.float32, name=name)
    if cfg.norm == "layernorm":
        return nn.LayerNorm(dtype=jnp.float32, name=name)
    raise ValueError(f"norm {cfg.norm!r}; have ('layernorm', 'rmsnorm')")


class Mlp(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        def proj(name):
            return nn.Dense(
                cfg.d_ff,
                kernel_init=_maybe_partitioned(cfg, (None, AXIS_MODEL)),
                dtype=cfg.compute_dtype, name=name)

        if cfg.mlp_variant == "swiglu":
            x = nn.silu(proj("gate")(x)) * proj("up")(x)
        elif cfg.mlp_variant == "gelu":
            x = nn.gelu(proj("up")(x))
        else:
            raise ValueError(f"mlp_variant {cfg.mlp_variant!r}; "
                             f"have ('gelu', 'swiglu')")
        x = nn.Dense(cfg.d_model,
                     kernel_init=_maybe_partitioned(cfg, (AXIS_MODEL, None)),
                     dtype=cfg.compute_dtype, name="down")(x)
        return x


class Block(nn.Module):
    cfg: TransformerConfig
    mesh: Optional[Mesh] = None

    # NOTE: ``train`` is positional (not kw-only) so nn.remat can mark
    # it static by index — (self, x, train) -> static_argnums=(2,).
    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False,
                 decode: bool = False,
                 positions: Optional[jax.Array] = None,
                 page_table: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        # Pre-LN (trains without warmup games, unlike BERT's post-LN).
        y = _norm(cfg, "ln1")(x)
        y = SelfAttention(cfg, self.mesh, name="attn")(
            y.astype(cfg.compute_dtype), train=train, decode=decode,
            positions=positions, page_table=page_table)
        y = nn.Dropout(cfg.dropout_rate, deterministic=not train)(y)
        x = x + y
        y = _norm(cfg, "ln2")(x)
        if cfg.moe_experts > 0:
            if cfg.mlp_variant != "gelu":
                raise ValueError(
                    "mlp_variant has no effect with moe_experts > 0 "
                    "(MoeMlp replaces the block MLP)")
            from tensorflow_distributed_tpu.models.moe import MoeMlp
            y = MoeMlp(d_model=cfg.d_model, d_ff=cfg.d_ff,
                       num_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                       capacity_factor=cfg.moe_capacity_factor,
                       group_len=cfg.moe_group_len,
                       dispatch=cfg.moe_dispatch,
                       compute_dtype=cfg.compute_dtype,
                       expert_axis=cfg.moe_expert_axis,
                       partitioned=cfg.tp_partitioning,
                       name="moe_mlp")(y.astype(cfg.compute_dtype))
        else:
            y = Mlp(cfg, name="mlp")(y.astype(cfg.compute_dtype))
        y = nn.Dropout(cfg.dropout_rate, deterministic=not train)(y)
        out = x + y
        if cfg.health_taps:
            # f32 RMS of the block's residual-stream output, sown into
            # the transient "health" collection (a no-op unless the
            # caller made it mutable — train.step.apply_model does
            # during training). The per-layer activation-scale vital:
            # a block whose output RMS runs away precedes the loss
            # spike by many steps.
            self.sow("health", "act_rms", jnp.sqrt(jnp.mean(
                jnp.square(out.astype(jnp.float32)))))
        return out


class _LmHead(nn.Module):
    """The untied output projection, param-compatible with the nn.Dense
    it replaced (same 'kernel'/'bias' names, shapes, inits — checkpoints
    carry over), but able to hand out its parameters WITHOUT computing
    logits: the fused-CE path (ops/fused_ce.py) runs the head matmul
    inside the loss, chunk by chunk, so the model must expose the raw
    [D, V] kernel instead of a [B, L, V] product."""

    d_in: int
    d_out: int
    kernel_init: Any
    dtype: Any

    @nn.compact
    def __call__(self, x: Optional[jax.Array] = None):
        kernel = self.param("kernel", self.kernel_init,
                            (self.d_in, self.d_out))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.d_out,))
        if x is None:
            return kernel, bias
        return (jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype))
                + bias.astype(self.dtype))


# The tied head's compute-dtype copy of ``tok_emb``'s table in a serving
# tree (``TransformerLM.serving_params``): a leaf at the root of
# ``params`` that ``init`` never creates and the head reads when there.
HEAD_TABLE = "head_table"


def _cast_at_use(names) -> bool:
    """Whether the leaf at ``names`` (the dict keys from the root of a
    ``TransformerLM``'s ``params``) is only ever read through
    ``astype(cfg.compute_dtype)``, going by the module that owns it."""
    if names[0] == "lm_head":
        return True
    if not names[0].startswith("layer_"):
        return False        # tok_emb, pos_emb, ln_f
    owner = names[1]
    return (owner in ("attn", "mlp")         # Dense projections only
            or (owner == "moe_mlp" and names[2] in ("wi", "wo")))


class TransformerLM(nn.Module):
    """Transformer LM backbone: tokens [B, L] int32 -> logits [B, L, V].

    ``extra_vocab`` widens the input embedding only (BERT's [MASK]
    sentinel); ``cfg.causal`` selects autoregressive attention (the GPT
    family) vs bidirectional (BERT)."""

    cfg: TransformerConfig
    mesh: Optional[Mesh] = None
    extra_vocab: int = 0

    @nn.compact
    def __call__(self, tokens: jax.Array, *, train: bool = False,
                 decode: bool = False,
                 positions: Optional[jax.Array] = None,
                 page_table: Optional[jax.Array] = None,
                 features_only: bool = False):
        cfg = self.cfg
        if cfg.pos_emb not in ("learned", "rope"):
            raise ValueError(f"pos_emb {cfg.pos_emb!r}; "
                             f"have ('learned', 'rope')")
        B, L = tokens.shape
        emb_init = _dense_init()
        vocab_pad = 0
        if cfg.shard_vocab:
            if not cfg.tp_partitioning:
                raise ValueError(
                    "shard_vocab needs tp_partitioning (the pipelined "
                    "family manages its shell params without TP "
                    "metadata — use mesh.pipe for its memory)")
            emb_init = nn.with_partitioning(emb_init, (AXIS_MODEL, None))
            # Megatron-style vocab padding: round the table rows up to
            # a multiple of the TP axis so the shard is well-formed at
            # ANY real vocab (50257 is odd; BERT adds a sentinel row).
            # Padded rows are never looked up, and padded logits are
            # sliced off below before the loss sees them.
            tp = (dict(self.mesh.shape).get(AXIS_MODEL, 1)
                  if self.mesh is not None else 1)
            vocab_pad = (-(cfg.vocab_size + self.extra_vocab)) % tp
        emb = nn.Embed(cfg.vocab_size + self.extra_vocab + vocab_pad,
                       cfg.d_model,
                       embedding_init=emb_init, name="tok_emb")
        x = emb(tokens)
        if positions is None:
            if decode:
                # arange(L) would embed a continuation token at position
                # 0 while the cache attends it at the running index —
                # silently wrong logits. Make the caller say where.
                raise ValueError("decode=True requires positions")
            positions = jnp.arange(L)[None, :]
        if cfg.pos_emb == "learned":
            pos = nn.Embed(cfg.max_len, cfg.d_model,
                           embedding_init=_dense_init(), name="pos_emb")(
                positions)
            x = (x + pos).astype(cfg.compute_dtype)
        else:  # rope: no additive embedding; q/k rotate per layer
            x = x.astype(cfg.compute_dtype)
        if self.mesh is not None:
            # Pin activation layout: batch over "data", seq over "seq".
            x = jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(
                    self.mesh,
                    jax.sharding.PartitionSpec(AXIS_DATA, AXIS_SEQ, None)))

        block = Block
        if cfg.remat:
            # Rematerialize each block on backward: HBM for FLOPs, the
            # standard long-context trade. train/decode must be static
            # (indices 2,3 counting self) — they select branches.
            block = nn.remat(Block, static_argnums=(2, 3),
                             policy=resolve_remat_policy(cfg.remat_policy))
        for i in range(cfg.n_layers):
            x = block(cfg, self.mesh, name=f"layer_{i}")(x, train, decode,
                                                         positions,
                                                         page_table)
        x = _norm(cfg, "ln_f")(x)
        if features_only:
            # Hand the loss the pieces of the head instead of its
            # product: (features, head matrix, bias, vocab axis of the
            # matrix) — ops.fused_ce consumes them chunk by chunk
            # (single-rank scan, Pallas kernel, or at mesh.model > 1
            # the vocab-parallel form — padding rows are sliced off
            # here and re-derived where the TP dispatch needs them).
            xc = x.astype(cfg.compute_dtype)
            if cfg.tie_embeddings:
                return xc, emb.embedding[:cfg.vocab_size], None, 0
            head_pad = ((-cfg.vocab_size) % tp if cfg.shard_vocab else 0)
            head = _LmHead(cfg.d_model, cfg.vocab_size + head_pad,
                           _maybe_partitioned(cfg, (None, AXIS_MODEL)),
                           cfg.compute_dtype, name="lm_head")
            kernel, bias = head(None)
            if head_pad:
                kernel, bias = (kernel[:, :cfg.vocab_size],
                                bias[:cfg.vocab_size])
            return xc, kernel, bias, 1
        if cfg.tie_embeddings:
            # Cast the shared table to compute dtype so the logits
            # matmul (the model's largest) stays on the bf16 MXU path
            # like the untied head. With shard_vocab the table rows are
            # split over "model", so the einsum emits vocab-sharded
            # logits (same layout as the untied sharded head); without
            # it the tied logits compute replicated.
            # A server's tree holds that cast, made once
            # (:meth:`serving_params`); ``init`` never creates the leaf,
            # so every other caller casts here as it always did.
            table = (nn.meta.unbox(self.get_variable("params", HEAD_TABLE))
                     if self.has_variable("params", HEAD_TABLE)
                     else emb.embedding.astype(cfg.compute_dtype))
            logits = jnp.einsum("...d,vd->...v",
                                x.astype(cfg.compute_dtype), table)
            logits = logits[..., :cfg.vocab_size]  # drop sentinel rows
        else:
            # Same padding treatment for the untied head's output dim
            # (the kernel's vocab dim is TP-sharded whenever
            # tp_partitioning is on).
            head_pad = ((-cfg.vocab_size) % tp if cfg.shard_vocab else 0)
            logits = _LmHead(
                cfg.d_model, cfg.vocab_size + head_pad,
                _maybe_partitioned(cfg, (None, AXIS_MODEL)),
                cfg.compute_dtype, name="lm_head")(
                x.astype(cfg.compute_dtype))
            if head_pad:
                logits = logits[..., :cfg.vocab_size]
        return logits.astype(jnp.float32)

    def serving_params(self, params):
        """``params`` as a server holds them: each leaf that the
        programs only ever read through a cast to the compute dtype is
        held IN that dtype, so a decode step stops reading float32
        weights to round them (half of what GPT-2 large's step moved).
        Pure and traceable; ``serve.params.serving_tree`` runs it once,
        jitted. The same operands, in the same dtypes, enter the same
        operations, so the logits are the trained tree's bit for bit.

        Cast at use, so held cast: the ``kernel`` and ``bias`` of every
        compute-dtype ``Dense`` (all of ``attn`` and ``mlp``), the
        untied ``lm_head``'s, and the dense-MoE experts' ``wi`` /
        ``wo``. With ``tie_embeddings`` the table has two readers: the
        lookup sums ``emb + pos`` in float32 and rounds once, so
        ``tok_emb`` stays; the head's product reads a compute-dtype
        copy, carried as one more leaf (``HEAD_TABLE``). Read in
        float32 today, so left alone: every norm, ``pos_emb`` and the
        MoE router's matrix ``moe_mlp/gate`` (swiglu's ``mlp/gate`` is
        a ``Dense``: the rule goes by the module that owns a leaf, not
        by its bare name). Under float32 compute nothing is cast and
        ``params`` comes back as it is."""
        dt = jnp.dtype(self.cfg.compute_dtype)
        if dt == jnp.float32:
            return params

        def held(path, leaf):
            names = [k.key for k in path
                     if isinstance(k, jax.tree_util.DictKey)]
            return leaf.astype(dt) if _cast_at_use(names) else leaf

        out = jax.tree_util.tree_map_with_path(held, params)
        if self.cfg.tie_embeddings:
            out = {**out, HEAD_TABLE: jax.tree_util.tree_map(
                lambda t: t.astype(dt), params["tok_emb"]["embedding"])}
        return out


class BertMLM(TransformerLM):
    """Encoder-only masked-LM (bidirectional, +[MASK] sentinel)."""

    extra_vocab: int = 1


class CausalLM(TransformerLM):
    """Decoder-only autoregressive LM (the GPT family). Construct with
    a ``causal=True`` config (the factories below enforce it)."""


def bert_base_mlm(mesh: Optional[Mesh] = None, size: str = "base",
                  **overrides) -> BertMLM:
    """Factory for the registry. ``size``: "base" (BERT-base) or "tiny"
    (test scale); ``overrides`` are TransformerConfig fields."""
    _auto_expert_axis(mesh, overrides)
    _auto_tp_partitioning(mesh, overrides)
    if size == "base":
        cfg = bert_base_config(**overrides)
    elif size == "tiny":
        cfg = tiny_config(**overrides)
    else:
        raise ValueError(f"bert_mlm size {size!r}; have ('base', 'tiny')")
    return BertMLM(cfg, mesh)


def bert_tiny_mlm(mesh: Optional[Mesh] = None, **overrides) -> BertMLM:
    return BertMLM(tiny_config(**overrides), mesh)


def gpt2_small_config(**overrides) -> TransformerConfig:
    """GPT-2-small (12L x 768d x 12H, learned positions, pre-LN) — the
    flagship config, shared by gpt_lm and the pipelined factory so the
    two families can never drift apart."""
    return dataclasses.replace(
        TransformerConfig(vocab_size=50257, d_model=768, n_layers=12,
                          n_heads=12, d_ff=3072, max_len=1024,
                          causal=True),
        **overrides)


# The GPT-2 ladder (Radford et al. 2019 table 2): d_ff = 4 * d_model
# throughout; head dim stays 64. The benchmark trains "medium" and
# serves "large" (PERF.md); the larger rungs are what --remat,
# --param-partition fsdp/zero1, --ce-chunk and the pipeline exist for.
GPT2_SIZES = {
    "small": dict(d_model=768, n_layers=12, n_heads=12, d_ff=3072),
    "medium": dict(d_model=1024, n_layers=24, n_heads=16, d_ff=4096),
    "large": dict(d_model=1280, n_layers=36, n_heads=20, d_ff=5120),
    "xl": dict(d_model=1600, n_layers=48, n_heads=25, d_ff=6400),
}


def gpt_lm(mesh: Optional[Mesh] = None, size: str = "small",
           **overrides) -> CausalLM:
    """GPT-style decoder-only LM. ``size``: the GPT-2 ladder
    ("small" 124M-class / "medium" 355M / "large" 774M / "xl" 1.6B
    backbone shapes, GPT2_SIZES) or "tiny" (test scale). No reference
    counterpart (the reference has no sequence models, SURVEY.md §5)
    — designed TPU-first like the rest of this family."""
    overrides["causal"] = True
    _auto_expert_axis(mesh, overrides)
    _auto_tp_partitioning(mesh, overrides)
    if size in GPT2_SIZES:
        cfg = gpt2_small_config(**{**GPT2_SIZES[size], **overrides})
    elif size == "tiny":
        cfg = tiny_config(**overrides)
    else:
        raise ValueError(f"gpt_lm size {size!r}; have "
                         f"({', '.join(GPT2_SIZES)}, tiny)")
    return CausalLM(cfg, mesh)


# The factory-default expert count moe_lm applies when none is given.
# Named so the auto-layout planner's model facts (analysis/planner/
# candidates.model_facts) prune expert-axis shapes against the SAME
# number the scorer's real build uses.
MOE_DEFAULT_EXPERTS = 4


def moe_lm(mesh: Optional[Mesh] = None, size: str = "tiny",
           **overrides) -> CausalLM:
    """Expert-parallel causal LM ("moe_lm" registry entry): the GPT
    family with every MLP a top-2 MoE (models/moe.py). No reference
    counterpart (SURVEY.md §2b "Expert parallel: NO")."""
    overrides.setdefault("moe_experts", MOE_DEFAULT_EXPERTS)
    if overrides["moe_experts"] <= 0:
        raise ValueError("moe_lm needs moe_experts > 0")
    return gpt_lm(mesh=mesh, size=size, **overrides)  # auto expert axis
