"""Pipeline-parallel transformer LM.

The transformer block stack as S pipeline stages over the "pipe" mesh
axis (parallel.pipeline). Embeddings and the LM head run replicated
outside the pipeline (they're cheap); the block stack — where the
FLOPs are — runs stage-sharded with the GPipe microbatch schedule.

Unlike models/transformer.py (an nn.Module whose GSPMD sharding comes
from param metadata), the pipelined variant owns its params as ONE
stacked pytree (block leaves [n_layers, ...] regrouped to
[S, layers_per_stage, ...] and pipe-sharded via nn.Partitioned boxes),
because the pipeline schedule needs to slice stages explicitly inside
shard_map. It duck-types the flax surface create_train_state/apply_model
consume: ``init(key, tokens, train=False) -> {"params": ...}`` and
``apply(variables, tokens, *, train=..., rngs=...)``.

Composition: the pipe shard_map manualizes ONLY the "pipe" axis, so
"data" (batch) and "model" (TP) sharding of activations and stage
params continue to be handled by the surrounding GSPMD partitioner.
TP metadata can't ride flax module boxes here (tp_partitioning=False,
see TransformerConfig) — instead init() re-attaches Megatron-style
"model" names to the STACKED leaves by key-path suffix (_TP_SUFFIX
rules matching models/transformer.py's layout conventions), so
PP x TP x DP runs from one boxed pytree. "seq" > 1 composes too
(causal only): the Block routes seq-sharded activations to ring
attention, whose shard_map nests over the remaining auto axes inside
the pipe-manual region exactly like the flash dispatcher's
(parallel.ring_attention; pinned by
tests/test_pipelined_modern.py::test_pipelined_ring_attention_parity).
Dropout is plumbed: pipeline_apply folds the step key over
(microbatch, stage), stages fold per-layer.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from tensorflow_distributed_tpu.models.transformer import (
    Block, TransformerConfig, _dense_init, _LmHead, _norm,
    resolve_remat_policy, tiny_config)
from tensorflow_distributed_tpu.parallel.mesh import (
    AXIS_MODEL, AXIS_PIPE, AXIS_SEQ)
from tensorflow_distributed_tpu.parallel.pipeline import (
    pipeline_apply, stack_stage_params)
from tensorflow_distributed_tpu.parallel.sharding import path_key

# Megatron-style TP ("model" axis) names for stacked block leaves, by
# key-path suffix — the same layout conventions models/transformer.py
# attaches via nn.with_partitioning (its module docstring table). Tuples
# are the names for the leaf's ORIGINAL dims; init() prepends
# (pipe, None) for the [S, layers_per_stage, ...] stacking dims.
_TP_SUFFIX = [
    (("attn", "qkv", "kernel"), (None, None, AXIS_MODEL, None)),
    (("attn", "qkv", "bias"), (None, AXIS_MODEL, None)),
    # GQA splits qkv into separate q and kv projections
    # (models/transformer.py SelfAttention): q shards its head dim like
    # qkv; the NARROW kv kernels stay replicated by design there too
    # (n_kv_heads is typically smaller than the TP axis) — so no kv
    # entry here, matching the non-pipelined layout exactly.
    (("attn", "q", "kernel"), (None, AXIS_MODEL, None)),
    (("attn", "q", "bias"), (AXIS_MODEL, None)),
    (("attn", "out", "kernel"), (AXIS_MODEL, None, None)),
    (("mlp", "up", "kernel"), (None, AXIS_MODEL)),
    (("mlp", "up", "bias"), (AXIS_MODEL,)),
    (("mlp", "gate", "kernel"), (None, AXIS_MODEL)),  # swiglu
    (("mlp", "gate", "bias"), (AXIS_MODEL,)),
    (("mlp", "down", "kernel"), (AXIS_MODEL, None)),
    # MoE expert weights: expert-parallel over the same axis
    # (models/moe.py's default expert_axis).
    (("moe_mlp", "wi"), (AXIS_MODEL, None, None)),
    (("moe_mlp", "wo"), (AXIS_MODEL, None, None)),
]


def _tp_names(path, ndim, lead=2):
    """TP axis names for a stacked leaf's ORIGINAL dims; ``lead`` is
    how many stacking dims were prepended ([S, lps] plain, [S, V, lps]
    interleaved)."""
    keys = path_key(path)
    for suffix, names in _TP_SUFFIX:
        if keys[-len(suffix):] == suffix:
            assert len(names) == ndim - lead, (keys, names, ndim)
            return names
    return (None,) * (ndim - lead)


class _Shell(nn.Module):
    """Embeddings + final LN + LM head — everything outside the pipe."""

    cfg: TransformerConfig
    extra_vocab: int = 0

    def setup(self):
        cfg = self.cfg
        self.tok_emb = nn.Embed(cfg.vocab_size + self.extra_vocab,
                                cfg.d_model, embedding_init=_dense_init(),
                                name="tok_emb")
        if cfg.pos_emb == "learned":
            # rope has no additive table — q/k rotate inside each block.
            self.pos_emb = nn.Embed(cfg.max_len, cfg.d_model,
                                    embedding_init=_dense_init(),
                                    name="pos_emb")
        self.ln_f = _norm(cfg, "ln_f")
        if not cfg.tie_embeddings:
            # Tied: the head IS tok_emb (both live in this one shell
            # module, so tying is shell-local — same scheme as
            # models/transformer.py's TransformerLM). _LmHead is the
            # Dense-compatible head that can hand out its kernel/bias
            # without computing logits (the fused-CE path).
            self.lm_head = _LmHead(cfg.d_model, cfg.vocab_size,
                                   _dense_init(),
                                   cfg.compute_dtype,
                                   name="lm_head")

    def embed(self, tokens: jax.Array) -> jax.Array:
        L = tokens.shape[1]
        x = self.tok_emb(tokens)
        if self.cfg.pos_emb == "learned":
            x = x + self.pos_emb(jnp.arange(L)[None, :])
        return x.astype(self.cfg.compute_dtype)

    def head(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = self.ln_f(x).astype(cfg.compute_dtype)
        if cfg.tie_embeddings:
            # Shared-table logits in compute dtype (bf16 MXU path),
            # sentinel rows sliced off — matching TransformerLM's tied
            # head exactly so cross-family parity is bitwise-testable.
            table = self.tok_emb.embedding.astype(cfg.compute_dtype)
            logits = jnp.einsum("...d,vd->...v", x, table)
            return logits[..., :cfg.vocab_size].astype(jnp.float32)
        return self.lm_head(x).astype(jnp.float32)

    def head_pieces(self, x: jax.Array):
        """(features, head matrix, bias, vocab axis) — the fused-CE
        contract (same as TransformerLM's features_only mode): the
        head matmul runs inside the loss, chunk by chunk, so the
        [mb, L, V] logits never materialize at the last stage."""
        cfg = self.cfg
        x = self.ln_f(x).astype(cfg.compute_dtype)
        if cfg.tie_embeddings:
            return x, self.tok_emb.embedding[:cfg.vocab_size], None, 0
        kernel, bias = self.lm_head(None)
        return x, kernel, bias, 1

    def __call__(self, tokens: jax.Array) -> jax.Array:  # init path only
        return self.head(self.embed(tokens))


class PipelinedLM:
    """Decoder/encoder LM with the block stack pipeline-parallel."""

    def __init__(self, cfg: TransformerConfig, mesh: Mesh,
                 num_microbatches: int = 4, extra_vocab: int = 0,
                 virtual_stages: int = 1):
        if cfg.tp_partitioning:
            raise ValueError(
                "pipelined variant needs tp_partitioning=False (flax "
                "DenseGeneral re-applies the TP constraint inside the "
                "pipe shard_map; see TransformerConfig.tp_partitioning)"
                " — TP names are re-attached to the stacked leaves by "
                "init() instead")
        if mesh.shape[AXIS_SEQ] > 1 and not cfg.causal:
            raise ValueError(
                "pipelined variant with mesh.seq > 1 needs causal=True"
                " (ring attention supports only the causal mask on a "
                "sharded seq axis; parallel.ring_attention)")
        if dict(mesh.shape).get("expert", 1) != 1:
            raise ValueError(
                "pipelined variant: mesh expert must be 1 — the "
                "stacked-leaf TP name table (_TP_SUFFIX) pins expert "
                "weights to the \"model\" axis; use mesh.model for EP "
                "with the pipeline")
        S = mesh.shape[AXIS_PIPE]
        if virtual_stages < 1:
            raise ValueError(
                f"virtual_stages must be >= 1, got {virtual_stages}")
        if cfg.n_layers % (S * virtual_stages):
            raise ValueError(
                f"{cfg.n_layers} layers not divisible by {S} stages"
                + (f" x {virtual_stages} virtual chunks"
                   if virtual_stages > 1 else ""))
        self.cfg = cfg
        self.mesh = mesh
        self.num_microbatches = num_microbatches
        self.virtual_stages = virtual_stages
        self._shell = _Shell(cfg, extra_vocab)
        # use_flash=True: the Block keeps the mesh so the attention
        # dispatcher (ops.flash_attention.attention) can wrap the
        # Mosaic kernel in its own NESTED shard_map over the remaining
        # auto axes (data/model) — the pipe shard_map manualizes only
        # {"pipe"}, and a Mosaic call needs fully-manual axes. With
        # use_flash=False the Block sees no mesh and the XLA attention
        # path partitions under GSPMD as before. mesh.seq > 1 ALSO
        # needs the mesh regardless of flash: the Block's dispatch
        # routes seq-sharded activations to ring attention, whose own
        # shard_map nests over the remaining auto axes the same way
        # (parallel.ring_attention — the pipe x ring composition,
        # round-4 review item 3).
        self._block = Block(cfg, mesh if (cfg.use_flash or
                                          mesh.shape[AXIS_SEQ] > 1)
                            else None)

    # -- flax-compatible surface -----------------------------------------

    def init(self, key: jax.Array, tokens: jax.Array,
             train: bool = False) -> Any:
        del train
        cfg = self.cfg
        k_shell, k_blocks = jax.random.split(key)
        shell_params = self._shell.init(k_shell, tokens)["params"]
        x = jnp.zeros((tokens.shape[0], tokens.shape[1], cfg.d_model),
                      cfg.compute_dtype)
        layer_keys = jax.random.split(k_blocks, cfg.n_layers)
        # Unbox inside the vmap: Block's TP partition metadata (rank-N
        # names) would be stale on the rank-N+2 stacked leaves — the
        # pipelined variant enforces model=seq=1, so dropping it is
        # sound; pipe-axis boxes are added below with full-rank names.
        pos = (jnp.arange(tokens.shape[1])[None, :]
               if cfg.pos_emb == "rope" else None)
        stacked = jax.vmap(lambda k: nn.meta.unbox(
            self._block.init(k, x, False,
                             positions=pos)["params"]))(layer_keys)
        staged = stack_stage_params(stacked,
                                    self.mesh.shape[AXIS_PIPE],
                                    virtual=self.virtual_stages)
        lead = 2 if self.virtual_stages == 1 else 3
        boxed = jax.tree_util.tree_map_with_path(
            lambda path, p: nn.Partitioned(
                p, names=(AXIS_PIPE,) + (None,) * (lead - 1)
                + _tp_names(path, p.ndim, lead)),
            staged)
        return {"params": {"shell": shell_params, "blocks": boxed}}

    def make_stage_fn(self, train: bool, with_rng: bool,
                      with_aux: bool = False):
        """The per-stage compute: scan this stage's blocks in order,
        folding the (mb, stage)-scoped key per layer so every
        (mb, stage, layer) dropout mask is distinct. Shared by the
        GPipe apply() and the 1F1B train step (train.pipeline_step).

        ``with_aux``: collect each MoE block's sown "moe_aux" values
        (models/moe.py AUX_NAMES) and return ``(y, aux_sums)`` — the
        pipeline schedules mask bubble ticks and total these across
        (stage, microbatch); without it the sows are silently dropped
        (flax no-ops sow on immutable collections), which is exactly
        the router-collapse trap this flag exists to close."""
        from tensorflow_distributed_tpu.models.moe import (
            AUX_NAMES, collect_aux)

        def stage_fn(stage_params, x_mb, key=None):
            lps = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
            # RoPE positions are microbatch-INVARIANT: microbatches
            # slice the batch dim, never the sequence, so every
            # (stage, microbatch) sees the same arange(L) — derivable
            # right here from the activation shape, no threading
            # through the schedule needed.
            pos = (jnp.arange(x_mb.shape[1])[None, :]
                   if self.cfg.pos_emb == "rope" else None)

            def one_layer(carry, xs):
                x, aux = carry
                layer_p, li = xs
                r = ({"dropout": jax.random.fold_in(key, li)}
                     if with_rng else None)
                if with_aux:
                    y, mut = self._block.apply(
                        {"params": layer_p}, x, train, rngs=r,
                        positions=pos, mutable=["moe_aux"])
                    layer_aux = collect_aux(mut["moe_aux"])
                    aux = {k: aux[k] + jnp.asarray(layer_aux[k],
                                                   jnp.float32)
                           for k in AUX_NAMES}
                else:
                    y = self._block.apply({"params": layer_p}, x, train,
                                          rngs=r, positions=pos)
                return (y, aux), None
            if self.cfg.remat:
                # --remat for the pipelined family: rematerialize each
                # block on backward (cfg.remat_policy as in
                # models/transformer.py), so activation memory per stage
                # is O(1) blocks instead of O(layers_per_stage).
                one_layer = jax.checkpoint(
                    one_layer,
                    policy=resolve_remat_policy(self.cfg.remat_policy))
            aux0 = ({k: jnp.zeros((), jnp.float32) for k in AUX_NAMES}
                    if with_aux else ())
            (y, aux), _ = jax.lax.scan(one_layer, (x_mb, aux0),
                                       (stage_params, jnp.arange(lps)))
            return (y, aux) if with_aux else y

        return stage_fn

    def embed(self, shell_params: Any, tokens: jax.Array) -> jax.Array:
        return self._shell.apply({"params": shell_params}, tokens,
                                 method="embed")

    def head(self, shell_params: Any, x: jax.Array) -> jax.Array:
        return self._shell.apply({"params": shell_params}, x,
                                 method="head")

    def head_pieces(self, shell_params: Any, x: jax.Array):
        return self._shell.apply({"params": shell_params}, x,
                                 method="head_pieces")

    def apply(self, variables: Any, tokens: jax.Array, *,
              train: bool = False, rngs: Optional[Any] = None,
              mutable: Any = (), features_only: bool = False):
        """Forward pass. ``mutable=["moe_aux"]`` (the flax collection
        surface train.tasks.make_moe_loss speaks) additionally returns
        the router losses collected THROUGH the pipeline schedule —
        normalized to per-layer-per-microbatch means so they compare
        exactly with the non-pipelined families' sown values.
        ``"health"`` (which train.step.apply_model opens on every
        training pass) is accepted and comes back empty: the stage
        function has no activation taps, and a flax model without taps
        sows nothing either."""
        # Normalize the flax-style mutable forms: str | bool | iterable.
        if isinstance(mutable, str):
            mutable = (mutable,)
        elif isinstance(mutable, bool):
            mutable = ("moe_aux",) if mutable else ()
        mutable = tuple(mutable)
        unsupported = set(mutable) - {"moe_aux", "health"}
        if unsupported:
            # Fail fast: silently returning a bare array would make a
            # flax-style `out, mut = apply(...)` unpack split the batch
            # dim instead of erroring.
            raise ValueError(
                f"PipelinedLM.apply supports mutable=['moe_aux', "
                f"'health'] only; got {sorted(unsupported)}")
        want_aux = "moe_aux" in mutable
        p = variables["params"]
        x = self.embed(p["shell"], tokens)
        use_dropout = bool(train and self.cfg.dropout_rate
                           and rngs and "dropout" in rngs)
        if want_aux and self.cfg.moe_experts <= 0:
            raise ValueError("mutable=['moe_aux'] needs moe_experts > 0")
        stage_fn = self.make_stage_fn(train, use_dropout,
                                      with_aux=want_aux)
        rng = rngs["dropout"] if use_dropout else None
        out = (self.head_pieces if features_only else self.head)
        V = self.virtual_stages
        # Interleaved layout ([S, V, lps, ...]): chunk group v is a
        # contiguous depth-S segment laid out one-chunk-per-device, so
        # the forward is V chained plain pipeline passes — correct for
        # eval/GPipe (the bubble-overlapped single-scan schedule lives
        # in interleaved_pipeline_value_and_grad, 1F1B only). Keys
        # fold per pass so no (mb, stage) pair repeats across chunks.
        groups = ([p["blocks"]] if V == 1 else
                  [jax.tree_util.tree_map(lambda q: q[:, v], p["blocks"])
                   for v in range(V)])
        if want_aux:
            aux_tot = None
            for v, gp in enumerate(groups):
                rv = (jax.random.fold_in(rng, v)
                      if rng is not None and V > 1 else rng)
                x, aux_sums = pipeline_apply(
                    stage_fn, gp, x, self.mesh,
                    self.num_microbatches, rng=rv, stage_aux=True)
                aux_tot = aux_sums if aux_tot is None else (
                    jax.tree_util.tree_map(lambda a, b: a + b, aux_tot,
                                           aux_sums))
            denom = self.cfg.n_layers * self.num_microbatches
            mut = {"moe_aux": {"pipeline": {
                k: (v / denom,) for k, v in aux_tot.items()}}}
            return out(p["shell"], x), mut
        for v, gp in enumerate(groups):
            rv = (jax.random.fold_in(rng, v)
                  if rng is not None and V > 1 else rng)
            x = pipeline_apply(stage_fn, gp, x, self.mesh,
                               self.num_microbatches, rng=rv)
        y = out(p["shell"], x)
        # Any mutable request gets the flax (out, mutated) pair; an
        # unwritten collection is absent from it, as in flax.
        return (y, {}) if mutable else y


# The layer count pipelined_lm bumps tiny_config's n_layers=2 up to,
# so common stage counts (2, 4) divide it. Named so the auto-layout
# planner's model facts (analysis/planner/candidates.model_facts)
# prune pipe-axis shapes against the SAME number the scorer's real
# build slices into stages.
PIPELINED_TINY_LAYERS = 4


def pipelined_lm(mesh: Mesh, size: str = "tiny", causal: bool = True,
                 num_microbatches: int = 4, virtual_stages: int = 1,
                 **overrides) -> PipelinedLM:
    """Registry factory ("pipelined_lm"). Sizes: "tiny" (tests/CI) or
    "small" (GPT-2-small: 12L x 768d x 12H — the flagship config, run
    pipelined). ``num_microbatches`` is CLI-exposed as
    --pipeline-microbatches; ``virtual_stages`` as
    --pipeline-virtual-stages (config.TrainConfig)."""
    overrides["causal"] = causal
    overrides["tp_partitioning"] = False  # see TransformerConfig notes
    # Pallas flash attention works inside the pipe via a nested
    # shard_map (see PipelinedLM.__init__); default on like the rest
    # of the GPT family, opt out with use_flash=False.
    overrides.setdefault("use_flash", True)
    if size == "tiny":
        # tiny default (2) < common stage counts
        overrides.setdefault("n_layers", PIPELINED_TINY_LAYERS)
        cfg = tiny_config(**overrides)
    else:
        from tensorflow_distributed_tpu.models.transformer import (
            GPT2_SIZES, gpt2_small_config)
        if size not in GPT2_SIZES:
            raise ValueError(
                f"pipelined_lm size {size!r}; have "
                f"(tiny, {', '.join(GPT2_SIZES)})")
        cfg = gpt2_small_config(**{**GPT2_SIZES[size], **overrides})
    return PipelinedLM(cfg, mesh, num_microbatches,
                       virtual_stages=virtual_stages)
