"""Pallas TPU flash attention: fused, blockwise, O(L) memory.

The reference computes no attention at all (its model is a LeNet CNN,
mnist_python_m.py:104-128) and leaves every op kernel to stock
TensorFlow C++ (SURVEY.md N11). This framework's sequence family
(models/transformer.py) is TPU-first, and attention is its hot op —
so it gets a hand-written Pallas kernel rather than leaning on XLA's
generic fusion:

- **Forward**: one `pallas_call` over a (batch*heads, Lq/bq, Lk/bk)
  grid. K/V blocks stream through VMEM while a running
  (max, sum, weighted-V) streaming-softmax accumulator lives in VMEM
  scratch — the full [L, L] score matrix never exists in HBM.
  Softmax statistics in f32; both matmuls hit the MXU with
  `preferred_element_type=f32`.
- **Backward**: custom VJP with two more Pallas kernels (dq over the
  q-block grid; dk/dv over the k-block grid) that recompute scores
  blockwise from the saved logsumexp instead of storing probabilities
  — the standard flash-attention memory trade, expressed natively.
- TPU grids execute sequentially with the last axis fastest, which is
  what makes scratch accumulation across the inner K (resp. Q) axis
  sound.

On non-TPU backends the kernels run under `interpret=True` (tests) or
callers use `parallel.ring_attention.full_attention` (the XLA oracle).
Causal masking is applied in-kernel, and fully-masked blocks are
SKIPPED: TPU grids are rectangular and execute every step, so the
skip is expressed as (a) a `pl.when` predicate around the compute body
— Mosaic emits real branches, the MXU never sees the masked block —
and (b) an index_map that re-points the skipped step's K/V (resp.
Q/dO) BlockSpec at an already-visited block, so the pipeline issues no
DMA for it either. Net: causal attention pays ~half the full-grid
FLOPs (the lower triangle plus the diagonal blocks), in all three
kernels (fwd, dq, dk/dv).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-finite: avoids inf-inf=nan in masked rows


def window_keep(rows, cols, window=0):
    """THE (row - window, row] causal-band predicate — the single
    construction shared by the kernel mask below, the XLA-oracle
    dispatcher path (attention()), and the decode-cache mask
    (models/transformer.py). window 0 = unlimited history."""
    keep = cols <= rows
    if window:
        keep = jnp.logical_and(keep, cols > rows - window)
    return keep


def window_bias(rows, cols, window=0):
    """Additive-bias form of window_keep ([1, Lq, Lk]-broadcastable,
    NEG_INF outside the band) — the one bias construction shared by
    the XLA-oracle dispatcher path and the decode-cache mask."""
    return jnp.where(window_keep(rows, cols, window), 0.0,
                     float(NEG_INF))[None]


def _causal_mask(s, i_q, i_k, bq, bk, window=0):
    """Causal mask, optionally sliding-window (window_keep)."""
    rows = i_q * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = i_k * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(window_keep(rows, cols, window), s, NEG_INF)


# Causal block-skip helpers. A (q-block i, k-block j) pair is needed iff
# its mask isn't all-False: the q block's last row i*bq + bq - 1 must
# reach the k block's first column j*bk — and under a sliding window
# the k block's last column j*bk + bk - 1 must still be inside the
# OLDEST row's window (row i*bq sees columns > i*bq - window). The
# index_map twins re-point skipped steps at a needed block so the
# revisit costs no DMA (Pallas only copies when the block index
# changes); under a window the inner index clamps into the needed
# band [lo, hi] — steps before lo prefetch block lo, steps after hi
# hold block hi.

def _kv_needed(i, j, bq, bk, window=0):
    need = j * bk <= i * bq + (bq - 1)
    if window:
        # Newest row of the q block is i*bq + bq - 1; its window spans
        # cols > i*bq + bq - 1 - window... but the OLDEST surviving
        # col across the block's rows comes from the oldest row i*bq:
        # cols > i*bq - window.
        need = jnp.logical_and(need, j * bk + (bk - 1) > i * bq - window)
    return need


def _causal_kv_map(bq, bk, window=0):
    def imap(b, i, j):
        hi = (i * bq + bq - 1) // bk
        if window:
            lo = jnp.maximum(i * bq - window + 1, 0) // bk
            return (b, jnp.clip(j, lo, hi), 0)
        return (b, jnp.minimum(j, hi), 0)
    return imap


def _q_needed(i, j, bq, bk, window=0):
    """dkv grid: i is the k-block index, j the q-block index."""
    need = j * bq + (bq - 1) >= i * bk
    if window:
        # Oldest col of this k block is i*bk; rows that still see it
        # satisfy row < i*bk + window — the newest such row bounds the
        # needed q blocks from above via the block's oldest row j*bq.
        need = jnp.logical_and(need,
                               j * bq < i * bk + (bk - 1) + window)
    return need


def _causal_q_map(bq, bk, window=0):
    def imap(b, i, j):
        lo = (i * bk) // bq
        if window:
            hi = (i * bk + bk - 2 + window) // bq
            return (b, jnp.clip(j, lo, hi), 0)
        return (b, jnp.maximum(j, lo), 0)
    return imap


# ---------------------------------------------------------------- forward

def _stream_softmax_step(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                         i, j, scale, causal, bq, bk, window=0):
    """One K,V block folded into the (m, l, acc) VMEM accumulators —
    the streaming-softmax body shared by the normalized and partial
    forward kernels. Runs under the causal block-skip predicate."""

    def compute():
        q = q_ref[0]                               # [bq, D]
        k = k_ref[0]                               # [bk, D]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, i, j, bq, bk, window)

        m_prev = m_scr[:, :1]                      # [bq, 1] f32
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)                     # [bq, bk] f32
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # Skip fully-masked K blocks (above the diagonal, and past
        # the window horizon) — a real branch, not predicated
        # arithmetic: the MXU work is not done.
        pl.when(_kv_needed(i, j, bq, bk, window))(compute)
    else:
        compute()


def _p_and_ds(q, k, v, do, row_sub, row_add, i_q, i_k, scale, causal,
              bq, bk, window=0):
    """Backward-pass block math shared by all four bwd kernels:
    p = exp(s - row_sub) and ds = p * (do.v^T + row_add) * scale.
    Normalized kernels pass (lse, -delta); partial kernels (m, +dl)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        s = _causal_mask(s, i_q, i_k, bq, bk, window)
    p = jnp.exp(s - row_sub)                       # [bq, bk]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp + row_add) * scale


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, bq, bk,
                window=0):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    _stream_softmax_step(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                         i, j, scale, causal, bq, bk, window)

    @pl.when(j == nk - 1)
    def _():
        l_final = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / l_final).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(l_final)      # [bq, 1]
        # lse rides in a [BH, L, 8] buffer: Mosaic requires the last two
        # block dims to divide (8, 128) or equal the array dims, so a
        # flat [BH, L] row output is unmappable; 8 lanes of replication
        # is the cheapest legal layout (the stock jax kernel uses 128).
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _fwd(q, k, v, causal, bq, bk, interpret, window=0):
    BH, L, D = q.shape
    Lk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    grid = (BH, L // bq, Lk // bk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, window=window)
    kv_map = _causal_kv_map(bq, bk, window) if causal else (
        lambda b, i, j: (b, j, 0))
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 8), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, D), q.dtype),
            jax.ShapeDtypeStruct((BH, L, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------- backward

def _delta(do, out):
    """rowsum(dO * O) recomputed blockwise — cheaper than materializing
    a lane-replicated [BH, L, 8] delta buffer in HBM."""
    return jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1, keepdims=True)        # [bq, 1]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
               dq_scr, *, scale, causal, bq, bk, window=0):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        _, ds = _p_and_ds(q, k, v, do, lse_ref[0][:, :1],
                          -_delta(do, o_ref[0]), i, j, scale, causal,
                          bq, bk, window)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_kv_needed(i, j, bq, bk, window))(compute)
    else:
        compute()

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                bq, bk, window=0):
    i = pl.program_id(1)                           # k-block index
    j = pl.program_id(2)                           # q-block index (inner)
    nq = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        p, ds = _p_and_ds(q, k, v, do, lse_ref[0][:, :1],
                          -_delta(do, o_ref[0]), j, i, scale, causal,
                          bq, bk, window)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # Skip q blocks strictly above this k block's diagonal (and
        # past the window horizon below it).
        pl.when(_q_needed(i, j, bq, bk, window))(compute)
    else:
        compute()

    @pl.when(j == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(q, k, v, out, lse, do, causal, bq, bk, interpret, window=0):
    BH, L, D = q.shape
    Lk = k.shape[1]
    scale = 1.0 / (D ** 0.5)

    kv_map = _causal_kv_map(bq, bk, window) if causal else (
        lambda b, i, j: (b, j, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, window=window),
        grid=(BH, L // bq, Lk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 8), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, out, lse)

    q_map = _causal_q_map(bq, bk, window) if causal else (
        lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, window=window),
        grid=(BH, Lk // bk, L // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bq, 8), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Lk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, out, lse)
    return dq, dk, dv


# ----------------------------------------------- partial-softmax variant
# Ring attention's building block (parallel.ring_attention): one Q-block
# vs one K,V-block PARTIAL attention returning the streaming-softmax
# triple (m = row max, l = exp-sum, o = unnormalized weighted V) that
# the ring merges across steps. Same blocking/VMEM scheme as the main
# kernel; the only differences are (a) o is written UNnormalized in f32
# and (b) m and l are emitted instead of the folded lse.
#
# VJP convention: m is the numerical stabilizer of the streaming
# softmax — the merged result is invariant to it — so it is treated as
# stop-gradient (exactly like jax.nn.softmax's max-shift). With
# p = exp(s - m):   dl/ds_ij = p_ij,   do_i/ds_ij = p_ij * v_j
# =>  ds_ij = p_ij * (do_i . v_j + dl_i),  dq = scale * ds @ k,
#     dk = scale * ds^T @ q,  dv = p^T @ do.
# These mirror _dq_kernel/_dkv_kernel with rowsum(do*o) replaced by
# the incoming -dl cotangent (delta there IS the normalized-case dl).


def _fwd_partial_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                        m_scr, l_scr, acc_scr, *, scale, causal, bq, bk):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    _stream_softmax_step(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                         i, j, scale, causal, bq, bk)

    @pl.when(j == nk - 1)
    def _():
        o_ref[0] = acc_scr[:]                      # UNnormalized, f32
        m_ref[0] = jnp.broadcast_to(m_scr[:, :1], m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(l_scr[:, :1], l_ref.shape[1:])


def _fwd_partial(q, k, v, causal, bq, bk, interpret):
    BH, L, D = q.shape
    Lk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    kv_map = _causal_kv_map(bq, bk) if causal else (
        lambda b, i, j: (b, j, 0))
    return pl.pallas_call(
        functools.partial(_fwd_partial_kernel, scale=scale,
                          causal=causal, bq=bq, bk=bk),
        grid=(BH, L // bq, Lk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 8), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 8), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, L, 8), jnp.float32),
            jax.ShapeDtypeStruct((BH, L, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd_partial",
    )(q, k, v)


def _dq_partial_kernel(q_ref, k_ref, v_ref, do_ref, dl_ref, m_ref,
                       dq_ref, dq_scr, *, scale, causal, bq, bk):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        _, ds = _p_and_ds(q, k, v, do, m_ref[0][:, :1],
                          dl_ref[0][:, :1], i, j, scale, causal, bq, bk)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_kv_needed(i, j, bq, bk))(compute)
    else:
        compute()

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_partial_kernel(q_ref, k_ref, v_ref, do_ref, dl_ref, m_ref,
                        dk_ref, dv_ref, dk_scr, dv_scr, *,
                        scale, causal, bq, bk):
    i = pl.program_id(1)                           # k-block index
    j = pl.program_id(2)                           # q-block index
    nq = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        p, ds = _p_and_ds(q, k, v, do, m_ref[0][:, :1],
                          dl_ref[0][:, :1], j, i, scale, causal, bq, bk)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_q_needed(i, j, bq, bk))(compute)
    else:
        compute()

    @pl.when(j == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_partial(q, k, v, m, do, dl, causal, bq, bk, interpret):
    BH, L, D = q.shape
    Lk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    kv_map = _causal_kv_map(bq, bk) if causal else (
        lambda b, i, j: (b, j, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_partial_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(BH, L // bq, Lk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 8), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 8), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_dq_partial",
    )(q, k, v, do, dl, m)

    q_map = _causal_q_map(bq, bk) if causal else (
        lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_partial_kernel, scale=scale,
                          causal=causal, bq=bq, bk=bk),
        grid=(BH, Lk // bk, L // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bq, 8), q_map),
            pl.BlockSpec((1, bq, 8), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Lk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv_partial",
    )(q, k, v, do, dl, m)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_partial(q, k, v, causal, bq, bk, interpret):
    return _fwd_partial(q, k, v, causal, bq, bk, interpret)


def _flash_partial_fwd(q, k, v, causal, bq, bk, interpret):
    o, m, l = _fwd_partial(q, k, v, causal, bq, bk, interpret)
    return (o, m, l), (q, k, v, m)


def _flash_partial_bwd(causal, bq, bk, interpret, res, cots):
    q, k, v, m = res
    do, _dm, dl = cots  # m is the stop-grad stabilizer (see above)
    return _bwd_partial(q, k, v, m, do.astype(jnp.float32), dl, causal,
                        bq, bk, interpret)


_flash_partial.defvjp(_flash_partial_fwd, _flash_partial_bwd)


def flash_attention_partial(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            causal: bool = False, block_q: int = 1024,
                            block_k: int = 1024,
                            interpret: Optional[bool] = None):
    """Partial (unnormalized) blockwise attention for the ring path.

    q: [B, Lq, H, D]; k, v: [B, Lk, H, D]. Returns the streaming-
    softmax partials in ``parallel.ring_attention._block_attend``'s
    layout: (m [B,H,Lq] f32, l [B,H,Lq] f32, o [B,Lq,H,D] f32 —
    UNnormalized weighted V). Differentiable (custom VJP, Pallas both
    ways). ``causal=True`` applies the in-block triangular mask (the
    ring's diagonal blocks, where q and k share global offsets).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, L, H, D = q.shape
    Lk = k.shape[1]
    bq, bk = min(block_q, L), min(block_k, Lk)
    if L % bq or Lk % bk:
        raise ValueError(
            f"flash_attention_partial: seq lens ({L}, {Lk}) must "
            f"divide the clamped blocks ({bq}, {bk}); see supported()")

    def pack(x):
        n = x.shape[1]
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, n,
                                                      x.shape[3])

    o, m, l = _flash_partial(pack(q), pack(k), pack(v), causal, bq, bk,
                             interpret)
    o = jnp.transpose(o.reshape(B, H, L, D), (0, 2, 1, 3))
    return m[..., 0].reshape(B, H, L), l[..., 0].reshape(B, H, L), o


# ------------------------------------------------------------ public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, bq, bk, interpret, window):
    out, _ = _fwd(q, k, v, causal, bq, bk, interpret, window)
    return out


def _flash_fwd(q, k, v, causal, bq, bk, interpret, window):
    out, lse = _fwd(q, k, v, causal, bq, bk, interpret, window)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, bq, bk, interpret, window, res, do):
    q, k, v, out, lse = res
    return _bwd(q, k, v, out, lse, do, causal, bq, bk, interpret,
                window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, window: int = 0,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused blockwise attention. q,k,v: [B, L, H, D] -> [B, L, H, D].

    Differentiable (custom VJP, Pallas both ways). Block sizes clamp to
    the sequence lengths; lengths must divide the (clamped) blocks —
    `supported()` gates the dispatcher. Defaults (1024, 1024) won a
    block-size sweep on one v5e chip (B=4 H=8 D=64 bf16, L=1k..8k) for
    both causal and full; with the causal block skip they measure
    1.20x/1.42x faster than the full-grid kernel at L=4096/8192 fwd
    (1.28x/1.50x fwd+bwd), trending to the asymptotic 2x as L grows
    (round-4 readings under jax 0.4.37, not re-measured; what the
    kernel does in a whole step today is PERF.md's
    ``train.flash_roofline_share``).
    `interpret=None` auto-selects interpreter mode off-TPU so the same
    kernel is testable on the 8-device CPU mesh (SURVEY.md §4).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if window and not causal:
        raise ValueError("window attention requires causal=True "
                         "(sliding window over past positions)")
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    B, L, H, D = q.shape
    Lk = k.shape[1]
    block_q = min(block_q, L)
    block_k = min(block_k, Lk)
    if L % block_q or Lk % block_k:
        # The grid would silently skip the ragged tail rows (whose
        # output buffer is uninitialized memory) — refuse instead.
        raise ValueError(
            f"flash_attention: seq lens ({L}, {Lk}) must divide the "
            f"clamped blocks ({block_q}, {block_k}); see supported()")

    def pack(x):
        n = x.shape[1]
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, n, x.shape[3])

    out = _flash(pack(q), pack(k), pack(v), causal, block_q, block_k,
                 interpret, window)
    return jnp.transpose(out.reshape(B, H, L, D), (0, 2, 1, 3))


def supported(L: int, Lk: int, D: int, block_q: int = 1024,
              block_k: int = 1024) -> bool:
    """Whether the Pallas kernel handles these shapes (else use the
    XLA path, parallel.ring_attention.full_attention)."""
    bq, bk = min(block_q, L), min(block_k, Lk)
    return (L % bq == 0 and Lk % bk == 0 and bq % 8 == 0 and bk % 8 == 0
            and D <= 256 and D % 8 == 0)


def use_flash(L: int, Lk: int, D: int) -> bool:
    """The ONE flash-dispatch gate, shared by the single-shard
    dispatcher (attention) and the ring path (_partial_attend): TPU
    backend (or TFD_FLASH_INTERPRET=1 forcing interpreter mode
    off-TPU, for CPU-mesh tests of the exact TPU code path) and
    kernel-supported shapes."""
    import os

    on_tpu = jax.default_backend() == "tpu"
    force = os.environ.get("TFD_FLASH_INTERPRET", "") == "1"
    return (on_tpu or force) and supported(L, Lk, D)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              mask: Optional[jax.Array] = None, *,
              causal: bool = False, window: int = 0, mesh=None,
              allow_flash: bool = True) -> jax.Array:
    """Dispatcher for the single-shard attention path: the Pallas
    kernel on TPU when shapes allow, the XLA oracle otherwise.
    (Ring attention owns the seq-sharded path.)

    ``mesh``: when the surrounding step is GSPMD-partitioned over a
    multi-device mesh, the Mosaic custom call has no partitioning rule
    of its own, so the kernel is wrapped in a shard_map over the
    (batch="data", heads="model") axes (+ "expert", where activations
    are replicated) — each device runs the kernel on its local shard;
    no cross-device comms are needed because batch and heads are
    embarrassingly parallel in attention. The shard_map names only
    those axes, NOT "pipe": inside the pipelined family's pipe-manual
    shard_map this nests as a partial manualization of the remaining
    auto axes, which is what lets the Mosaic kernel run inside the
    pipeline ("seq" stays auto and is 1 on every path that reaches
    flash — ring attention owns seq > 1).

    Setting TFD_FLASH_INTERPRET=1 forces this flash path off-TPU with
    the interpreter, so tests can exercise the full nested-shard_map
    structure on the 8-device CPU mesh.
    """
    import os

    from tensorflow_distributed_tpu.parallel.mesh import (
        AXIS_DATA, AXIS_EXPERT, AXIS_MODEL)
    from tensorflow_distributed_tpu.parallel.ring_attention import (
        full_attention)
    if window and not causal:
        # Same check flash_attention() makes — the XLA path must not
        # silently drop the window for non-causal configs.
        raise ValueError("window attention requires causal=True "
                         "(sliding window over past positions)")
    B, L, H, D = q.shape
    if allow_flash and mask is None and use_flash(L, k.shape[1], D):
        from jax.sharding import PartitionSpec as P
        spec = P(AXIS_DATA, None, AXIS_MODEL, None)
        kernel = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=causal, window=window)
        ctx = jax.sharding.get_abstract_mesh()
        if ctx.manual_axes:
            # Inside an enclosing shard_map (the pipelined family's
            # pipe-manual region): Mosaic refuses to lower while ANY
            # axis is still auto — even a size-1 one — so nest a
            # shard_map over every remaining auto axis, handing it the
            # CONTEXT abstract mesh (the one whose "pipe" is already
            # Manual), not the concrete mesh. "seq" is always 1 on the
            # flash path (ring attention owns seq > 1), so leaving it
            # out of the specs replicates correctly.
            remaining = set(ctx.axis_names) - set(ctx.manual_axes)
            return jax.shard_map(
                kernel, mesh=ctx, in_specs=(spec, spec, spec),
                out_specs=spec, axis_names=remaining,
                check_vma=False)(q, k, v)
        if mesh is None or all(
                mesh.shape[a] == 1
                for a in (AXIS_DATA, AXIS_MODEL, AXIS_EXPERT)):
            return flash_attention(q, k, v, causal=causal,
                                   window=window)
        # GSPMD-partitioned step: fully-manual shard_map over the mesh;
        # batch and heads are embarrassingly parallel, no comms.
        return jax.shard_map(
            kernel, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False)(q, k, v)
    if causal:
        cmask = window_bias(jnp.arange(L)[:, None],
                            jnp.arange(k.shape[1])[None, :], window)
        mask = cmask if mask is None else mask + cmask
    return full_attention(q, k, v, mask)
