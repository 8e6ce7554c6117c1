"""Mixture-of-Experts MLP with expert parallelism (GShard-style).

The reference has no expert/routing code (SURVEY.md §2b checklist:
"Expert parallel: NO") — beyond-reference capability, built the
TPU-native way: expert weights carry a leading expert dim partitioned
over a mesh axis, so XLA's SPMD partitioner derives the token
all_to_alls from sharding propagation — nobody writes a collective by
hand. Token movement has two interchangeable formulations sharing one
routing computation (``dispatch`` knob):
- "dense" (default): one-hot dispatch/combine einsums (the
  Mesh-TensorFlow/GShard formulation) — pure batched einsums on the
  MXU, no gather/scatter HLOs, the layout EP sharding is proven on.
- "scatter": the same assignments as a slot scatter-add into the
  expert buffers and a gather back — emits real scatter/gather HLOs,
  moves O(K) rows per token instead of spending O(E*C) einsum FLOPs
  per token, and never materializes the [S, E, C] one-hot tensors.
Identical masks, positions, capacity drops, gates, and aux sows either
way (gradient-level parity pinned in tests/test_moe.py, including an
EP-sharded train-step A/B).

Mechanics (top-2, capacity-factor c):
- gate logits [G, S, E] in f32; top-1 and top-2 assignments become
  one-hot masks; per-expert positions come from cumsums; tokens beyond
  the expert's capacity C = ceil(c * k * S / E) are dropped (their
  combine weight is 0, so they pass through the residual unchanged).
- dispatch [G, S, E, C] (0/1) routes tokens to expert buffers
  [G, E, C, M]; experts apply their own MLP weights [E, M, H]/[E, H, M];
  combine (dispatch * gate prob) returns them to [G, S, M].

Observability / losses, sown into the "moe_aux" collection (the MoE
loss collects them with ``collect_aux`` and weights the first two into
the objective; see train.tasks.make_moe_loss):
- "load_balance": E * sum_e f_e * p_e over ALL top-k assignments
  (f_e = routed fraction / K, so sum_e f_e == 1 and a uniform router
  scores exactly 1.0) — the Switch loss when K == 1, the
  DeepSeek/Mixtral-style generalization when K > 1.
- "z_loss": mean (logsumexp of router logits)^2 — the ST-MoE router
  z-loss that keeps gate logits from drifting to magnitudes where
  softmax saturates (weight 0 by default; a TrainConfig knob).
- "dropped_fraction": fraction of (token, k) routing slots past
  expert capacity — drops are silent passthroughs in the math, so
  this is the ONLY place overflow is visible. Reported as a train
  metric, never part of the objective.

Expert axis: "model" by default — expert parallelism composes with the
existing mesh without a fifth axis; a dedicated "expert" mesh axis
(MeshConfig.expert) is supported via the ``expert_axis`` knob.

Scale envelope (closed form; the round-4 chip readings are not
re-measured, PERF.md "Before the benchmark").
The dense [G, S, E, C] dispatch/combine tensors are O(S * E * C) f32
each with C = ceil(c*K*S/E), i.e. O(c*K*S^2) PER GROUP at any E —
quadratic in sequence length at fixed capacity factor:

    seq  1024:   20 MiB/group   (measured on chip: 65k tok/s, 37.6%
    seq  4096:  320 MiB/group    active-param MFU, E=8 d768 L12;
    seq  8192: 1.25 GiB/group    dispatch einsums are ~25% of step
    seq 32768:   20 GiB/group    FLOPs at seq 1024 — C grows with S,
                                 so this share grows too)

Inside the envelope (seq <= ~4k per group on a 16G chip, any E) the
formulation is the right TPU trade: pure batched einsums on the MXU,
zero gather/scatter, and GSPMD-derived all_to_alls. Past it, set
``group_len`` (``--moe-group-len``): each row's sequence splits into
independent routing groups of that length, so capacity — and with it
BOTH the dispatch tensors AND the dispatch-einsum FLOPs (each is
O(C) per token) — scales with the GROUP length, not the full
sequence: seq 32768 at group_len 1024 costs 32 x 20 MiB instead of
one 20 GiB tensor, and the round-4 seq-4096 win (1.28x tokens/s,
not re-measured) is mostly those saved einsum FLOPs. Or combine with sequence parallelism so each seq shard routes
its own slice. A sorted/ragged (megablocks-style) dispatch would need
a Pallas grouped-matmul kernel with scalar-prefetch block indexing to
beat this on TPU; not implemented — the group-length knob covers the
practical range first.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflow_distributed_tpu.parallel.mesh import AXIS_MODEL

# Every MoeMlp sows exactly these names (in this order) per apply.
AUX_NAMES = ("load_balance", "z_loss", "dropped_fraction")


def collect_aux(col) -> dict:
    """Mean per sow-name over every MoE layer in a "moe_aux" collection.

    ``col`` is the (possibly nested) dict flax returns for the mutable
    "moe_aux" collection: {layer_path...: {name: (value, ...)}}. Returns
    {name: scalar} with each layer's sown values averaged — the shape
    the MoE objective and train metrics consume (train.tasks).
    """
    acc: dict = {}

    def walk(node):
        for k, v in node.items():
            if isinstance(v, dict):
                walk(v)
            else:  # a tuple of sown values (one per sow call)
                vals = list(v) if isinstance(v, (tuple, list)) else [v]
                acc.setdefault(k, []).extend(vals)

    walk(col)
    return {k: sum(v) / len(v) for k, v in acc.items()}


class MoeMlp(nn.Module):
    """Drop-in replacement for the dense MLP inside a Block."""

    d_model: int
    d_ff: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    compute_dtype: Any = jnp.bfloat16
    expert_axis: str = AXIS_MODEL
    partitioned: bool = True  # False inside manual shard_maps (pipeline)
    # Routing-group length: 0 = the whole sequence is one group (GShard
    # default). Setting S' < S splits each row's sequence into S/S'
    # contiguous groups routed independently — capacity AND the
    # [.., S', E, C'] dispatch tensors scale with S' (C' = c*K*S'/E),
    # which is the in-formulation answer to the O(S^2) envelope above.
    # Load-balance pressure becomes per-chunk (stricter, same optimum).
    group_len: int = 0
    # Token movement formulation. "dense" (GShard): one-hot [S, E, C]
    # dispatch/combine einsums — pure MXU, but O(E*C) FLOPs per token
    # (~25% of an E=8 step in round 4, not re-measured) and O(S*E*C)
    # memory. "scatter": the SAME routing (identical masks, positions,
    # capacity drops, aux losses) expressed as a scatter-add into the
    # [E, C, M] expert buffers and a gather back — O(K) moved rows per
    # token, no one-hot tensors at all. Expert matmuls are identical
    # einsums either way. Dense stays the default: its E-dim einsum
    # operands are what GSPMD's expert-axis all_to_all derivation is
    # proven on; scatter is the measured-faster single-replica path.
    dispatch: str = "dense"  # dense | scatter

    def _winit(self, names):
        init = nn.initializers.normal(stddev=0.02)
        return nn.with_partitioning(init, names) if self.partitioned else init

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        G0, S0, M0 = x.shape
        # Sequences at or below group_len route as one group — decode
        # (S0 == 1) and short prefills must not crash on a knob meant
        # for long training sequences.
        if self.group_len and S0 > self.group_len:
            if S0 % self.group_len:
                raise ValueError(
                    f"seq {S0} not divisible by group_len "
                    f"{self.group_len}")
            x = x.reshape(G0 * (S0 // self.group_len), self.group_len,
                          M0)
        G, S, M = x.shape
        E, K = self.num_experts, self.top_k
        if K > E:
            # The routing loop would argmax an exhausted mask and pick
            # expert 0 with full gate weight on the extra iterations —
            # silent degradation; refuse instead (config.validate
            # catches the CLI path; this guards direct construction
            # and family-default expert counts).
            raise ValueError(f"top_k {K} > num_experts {E}")
        if self.dispatch not in ("dense", "scatter"):
            raise ValueError(f"dispatch {self.dispatch!r}; "
                             "have ('dense', 'scatter')")
        C = max(1, math.ceil(self.capacity_factor * K * S / E))

        gate_w = self.param("gate", self._winit((None, None)), (M, E),
                            jnp.float32)
        logits = x.astype(jnp.float32) @ gate_w            # [G, S, E]
        probs = jax.nn.softmax(logits, axis=-1)

        # Top-k one-hot masks + gates, built iteratively (K is 1 or 2).
        masks, gates = [], []
        remaining = probs
        for _ in range(K):
            idx = jnp.argmax(remaining, axis=-1)           # [G, S]
            mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)
            gates.append(jnp.sum(probs * mask, axis=-1))   # [G, S]
            masks.append(mask)
            remaining = remaining * (1.0 - mask)

        # Positions within each expert's buffer: cumulative count of
        # prior assignments (top-1 first, then top-2 after all top-1).
        pos, used = [], jnp.zeros((G, 1, E), jnp.float32)
        for mask in masks:
            cum = jnp.cumsum(mask, axis=1) - mask + used   # [G, S, E]
            pos.append(jnp.sum(cum * mask, axis=-1))       # [G, S]
            used = used + jnp.sum(mask, axis=1, keepdims=True)

        # Load-balancing aux loss over ALL top-k assignments: f_e is the
        # routed fraction across every (token, k) slot divided by K, so
        # sum_e f_e == 1 and a perfectly uniform router scores exactly
        # 1.0 for any K. Reduces to Switch Transformer eq. 4-6 at K=1;
        # the K>1 form is the DeepSeek/Mixtral-style generalization.
        f = jnp.mean(sum(masks), axis=(0, 1)) / K          # [E]
        p = jnp.mean(probs, axis=(0, 1))                   # [E]
        self.sow("moe_aux", "load_balance", E * jnp.sum(f * p))
        # ST-MoE router z-loss: mean squared logsumexp of the gate
        # logits — bounds logit magnitudes so the routing softmax stays
        # in a trainable regime. Objective weight is a config knob
        # (train.tasks.make_moe_loss); 0 disables it.
        z = jax.nn.logsumexp(logits, axis=-1)              # [G, S]
        self.sow("moe_aux", "z_loss", jnp.mean(jnp.square(z)))

        wi = self.param("wi", self._winit((self.expert_axis, None, None)),
                        (E, M, self.d_ff), jnp.float32)
        wo = self.param("wo", self._winit((self.expert_axis, None, None)),
                        (E, self.d_ff, M), jnp.float32)
        dt = self.compute_dtype

        # Per-(token, k) keep flag, normalized gate, and expert-buffer
        # slot, shared by both formulations so routing/drop semantics
        # are identical by construction.
        denom = sum(gates) if K > 1 else None
        gks = [g / jnp.maximum(denom, 1e-9) if denom is not None else g
               for g in gates]
        withins = [(ps < C).astype(jnp.float32) * jnp.sum(mask, -1)
                   for mask, ps in zip(masks, pos)]
        kept = sum(jnp.sum(w) for w in withins) / (G * S * K)
        # Overflowed routing slots are silent zeros in the math (the
        # token passes through the residual unchanged) — surface them.
        self.sow("moe_aux", "dropped_fraction",
                 jax.lax.stop_gradient(1.0 - kept))

        if self.dispatch == "scatter":
            # Slot d = e*C + pos for kept (token, k) pairs; dropped
            # pairs target the dump row E*C. One scatter-add fills the
            # expert buffers (slots are unique by construction: pos is
            # a per-expert running count), one gather + gate-weighted
            # sum brings expert outputs home. AD gives the transposes
            # (gather <-> scatter) for free.
            gidx = jnp.arange(G)[:, None]                  # [G, 1]
            buf = jnp.zeros((G, E * C + 1, M), dt)
            ds_ = []
            for mask, ps, within in zip(masks, pos, withins):
                e_id = jnp.argmax(mask, axis=-1)           # [G, S]
                d = jnp.where(within > 0,
                              e_id * C + ps.astype(jnp.int32), E * C)
                buf = buf.at[gidx, d].add(
                    x.astype(dt) * within[..., None].astype(dt))
                ds_.append(d)
            xin = buf[:, :E * C].reshape(G, E, C, M)       # [G, E, C, M]
            h = jax.nn.gelu(
                jnp.einsum("gecm,emf->gecf", xin, wi.astype(dt)))
            out = jnp.einsum("gecf,efm->gecm", h, wo.astype(dt))
            out_pad = jnp.concatenate(
                [out.reshape(G, E * C, M), jnp.zeros((G, 1, M), dt)], 1)
            y = sum(out_pad[gidx, d] * gk[..., None].astype(dt)
                    for d, gk in zip(ds_, gks))
            return y.astype(x.dtype).reshape(G0, S0, M0)

        # dispatch/combine [G, S, E, C]; tokens past capacity drop out.
        dispatch = jnp.zeros((G, S, E, C), jnp.float32)
        combine = jnp.zeros((G, S, E, C), jnp.float32)
        for mask, gk, ps, within in zip(masks, gks, pos, withins):
            loc = jax.nn.one_hot(ps.astype(jnp.int32), C,
                                 dtype=jnp.float32)        # [G, S, C]
            sel = mask[..., None] * loc[..., None, :]      # [G, S, E, C]
            sel = sel * within[..., None, None]
            dispatch = dispatch + sel
            combine = combine + sel * gk[..., None, None]

        # Token shuffle in, expert MLPs, shuffle out — the einsums whose
        # E-dim sharding makes GSPMD emit the all_to_alls.
        xin = jnp.einsum("gsec,gsm->egcm", dispatch.astype(dt),
                         x.astype(dt))                     # [E, G, C, M]
        h = jax.nn.gelu(jnp.einsum("egcm,emf->egcf", xin, wi.astype(dt)))
        out = jnp.einsum("egcf,efm->egcm", h, wo.astype(dt))
        y = jnp.einsum("gsec,egcm->gsm", combine.astype(dt), out)
        return y.astype(x.dtype).reshape(G0, S0, M0)
