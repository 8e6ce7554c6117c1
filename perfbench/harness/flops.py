"""Operations and bytes the algorithm needs, computed from shapes.

The benchmark's own arithmetic (the program has a copy of the first
formula in ``observe/mfu.py``; later PRs may change the program, never
this file). A multiply-add counts as two operations. Recomputed
operations do not count.
"""

from __future__ import annotations


def matmul_params(d_model: int, n_layers: int, d_ff: int, vocab: int) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: qkv, out, up, down per layer, and the (tied) head. Embedding
    lookups, biases and norms are not multiplications."""
    per_layer = 3 * d_model * d_model + d_model * d_model \
        + 2 * d_model * d_ff
    return n_layers * per_layer + vocab * d_model


def train_flops_per_token(d_model: int, n_layers: int, d_ff: int,
                          vocab: int, seq_len: int) -> float:
    """Forward plus backward, per trained token: 6 per matmul parameter
    (2 forward, 4 backward) plus causal attention. Attention forward is
    QK^T and PV, 2 * 2 * L * d_model per token per layer over the full
    square; the causal half is what the algorithm needs; backward is
    twice the forward."""
    dense = 6.0 * matmul_params(d_model, n_layers, d_ff, vocab)
    attn_fwd = 4.0 * seq_len * d_model * n_layers / 2.0
    return dense + 3.0 * attn_fwd


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


def decode_step_bytes(param_bytes: int, n_layers: int, d_model: int,
                      slots: int, max_len: int, kv_bytes_per_el: int = 2
                      ) -> float:
    """Bytes one decode step must read: every parameter as stored, once,
    plus the keys and values the program attends over. The dense slot
    engine attends over the whole ``[slots, max_len]`` cache whatever
    each slot's depth, so that is what is counted (ROADMAP A4)."""
    kv = 2.0 * n_layers * d_model * kv_bytes_per_el * slots * max_len
    return param_bytes + kv


def gpt2_param_count(d_model: int, n_layers: int, d_ff: int, vocab: int,
                     max_len: int) -> int:
    """All parameters of a tied GPT-2: embeddings, positions, per-layer
    kernels, biases and norms, final norm."""
    per_layer = (3 * d_model * d_model + 3 * d_model      # qkv
                 + d_model * d_model + d_model            # out
                 + d_model * d_ff + d_ff                  # up
                 + d_ff * d_model + d_model               # down
                 + 4 * d_model)                           # two norms
    return (vocab * d_model + max_len * d_model
            + n_layers * per_layer + 2 * d_model)
