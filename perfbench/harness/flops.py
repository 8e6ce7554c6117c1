"""Operations and bytes a kernel needs, computed from its shapes alone.

What belongs to one model (its parameter counts, a training step's
operations, a decode step's bytes) stands in that model's file under
``perfbench/models/``, beside the reference it describes. A multiply-add
counts as two operations. Recomputed operations do not count.
"""

from __future__ import annotations


def flash_attention_cost(batch: int, heads: int, seq_len: int,
                         head_dim: int, kind: str,
                         bytes_per_el: int = 2) -> tuple:
    """(operations, bytes) one causal flash-attention kernel call needs.

    ``kind``: "fwd" (QK^T, PV), "dq" (recompute S, dP = dO V^T,
    dQ = dS K) or "dkv" (recompute S, dP, dV = P^T dO, dK = dS^T Q).
    Each matmul is 2 * L * L * Dh per head over the full square, halved
    for the causal mask. The recomputation of S inside the backward
    kernels IS counted: it is what the flash algorithm needs (it never
    stores S), unlike a remat the program chose.
    Bytes: each operand read once and each result written once."""
    sq = 2.0 * batch * heads * seq_len * seq_len * head_dim / 2.0
    tensor = batch * heads * seq_len * head_dim * bytes_per_el
    stats = batch * heads * seq_len * 4            # f32 row statistics
    if kind == "fwd":
        return 2 * sq, 4 * tensor + stats          # q k v -> o, lse
    if kind == "dq":
        return 3 * sq, 5 * tensor + 2 * stats      # q k v do -> dq
    if kind == "dkv":
        return 4 * sq, 6 * tensor + 2 * stats      # q k v do -> dk dv
    raise ValueError(f"flash kind {kind!r}; have fwd, dq, dkv")
