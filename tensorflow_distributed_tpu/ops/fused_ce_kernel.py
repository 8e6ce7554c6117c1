"""Pallas TPU fused linear+cross-entropy ("flash CE").

The scan formulation (ops/fused_ce.py) already avoids the full
[T, V] logits tensor, but each [T, chunk] chunk still round-trips HBM:
the chunk matmul materializes, the reductions read it back, and the
backward recomputes it into another materialized chunk. This module is
the kernel form of the same math — the flash-attention treatment
applied to the vocabulary axis:

- **Forward**: one `pallas_call` over a (T/bt, V/bv) grid. Head-matrix
  blocks stream through VMEM while running (max, normalizer, gold
  logit, logit sum, argmax) accumulators live in VMEM scratch — a
  logits block exists only as an MXU output in VMEM, never in HBM.
  Emits per-token (ce, correct, lse); the [bt, bv] logits block is the
  only logits object that ever exists.
- **Backward**: custom VJP with two more kernels that recompute the
  logits block from the saved per-token lse — dx over the (T/bt, V/bv)
  grid accumulating across vocab blocks, dw/db over the transposed
  (V/bv, T/bt) grid accumulating across token blocks — exactly the
  dq / dkv split of the attention backward (ops/flash_attention.py).
- TPU grids execute sequentially with the last axis fastest, which is
  what makes scratch accumulation across the inner axis sound (same
  property the attention kernels rely on).
- Per-token vectors (targets, lse, coef, and the ce/correct/lse
  outputs) ride in [T, 8] buffers — tokens on the sublane axis, 8
  replicated lanes — the same layout trick the attention kernels use
  for lse: a flat [T] row is unmappable to a legal Mosaic tile.

Semantics match ops.losses.masked_ce_sums / ops.fused_ce.fused_ce_sums
(f32 statistics, first-max argmax, smoothing as the (1-eps)/eps-uniform
mixture); parity is pinned in tests/test_fused_ce_kernel.py,
interpret-mode on CPU like the other Pallas tests. No reference
counterpart: the reference's output layer is 10 classes
(mnist_python_m.py:196,205) — this exists for the LM families' 50k-row
heads.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflow_distributed_tpu.ops.fused_ce import _zeros_cotangent

NEG_INF = -1e30  # large-finite; matches ops/flash_attention.py
INT_BIG = 2 ** 30
LANES = 8        # replication width for per-token rows (see docstring)


def _block_logits(x_ref, w_ref, b_ref, j, bv, vocab_size, w_vocab_axis):
    """Raw f32 logits for this (token, vocab) block pair + the column
    ids and the valid-column mask (cols past the real vocab are pad)."""
    x = x_ref[...]                                   # [bt, D]
    w = w_ref[...]                                   # [bv, D] or [D, bv]
    dims = ((((1,), (1,)), ((), ())) if w_vocab_axis == 0
            else (((1,), (0,)), ((), ())))
    logits = jax.lax.dot_general(x, w, dims,
                                 preferred_element_type=jnp.float32)
    logits = logits + b_ref[:1, :].astype(jnp.float32)
    colid = j * bv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    return logits, colid, colid < vocab_size


def _dlogits(logits, colid, valid, lse_col, t_col, coef_col, vocab_size,
             label_smoothing):
    """coef * (softmax - smoothed_onehot) for one block — the backward
    block math shared by the dx and dw kernels. lse/t/coef arrive as
    [bt, 1] columns."""
    s = jnp.where(valid, logits, NEG_INF)
    p = jnp.exp(s - lse_col)                         # pad cols -> 0
    onehot = (colid == t_col).astype(jnp.float32)
    d = p - (1.0 - label_smoothing) * onehot
    if label_smoothing:
        d = d - (label_smoothing / vocab_size) * valid.astype(jnp.float32)
    return d * coef_col


def _fwd_kernel(x_ref, w_ref, b_ref, t_ref, ce_ref, corr_ref, lse_ref,
                m_scr, l_scr, gold_scr, lsum_scr, bv_scr, bi_scr, *,
                bv, vocab_size, label_smoothing, w_vocab_axis):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        gold_scr[:] = jnp.zeros_like(gold_scr)
        lsum_scr[:] = jnp.zeros_like(lsum_scr)
        bv_scr[:] = jnp.full_like(bv_scr, NEG_INF)
        bi_scr[:] = jnp.full_like(bi_scr, -1)

    logits, colid, valid = _block_logits(x_ref, w_ref, b_ref, j, bv,
                                         vocab_size, w_vocab_axis)
    t_col = t_ref[:, :1]                             # [bt, 1] int32
    s = jnp.where(valid, logits, NEG_INF)

    # Online logsumexp over vocab blocks (the flash recurrence).
    m_prev = m_scr[:, :1]
    bmax = jnp.max(s, axis=-1, keepdims=True)        # [bt, 1]
    m_cur = jnp.maximum(m_prev, bmax)
    l_new = (l_scr[:, :1] * jnp.exp(m_prev - m_cur)
             + jnp.sum(jnp.exp(s - m_cur), axis=-1, keepdims=True))
    m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # Gold logit: at most one column matches the target.
    eq = jnp.logical_and(colid == t_col, valid)
    gold_new = gold_scr[:, :1] + jnp.sum(jnp.where(eq, logits, 0.0),
                                         axis=-1, keepdims=True)
    gold_scr[:] = jnp.broadcast_to(gold_new, gold_scr.shape)
    if label_smoothing:
        lsum_new = lsum_scr[:, :1] + jnp.sum(
            jnp.where(valid, logits, 0.0), axis=-1, keepdims=True)
        lsum_scr[:] = jnp.broadcast_to(lsum_new, lsum_scr.shape)

    # First-max argmax across blocks: strict > keeps the earlier
    # block's winner; within a block, the smallest max column wins.
    is_max = jnp.logical_and(s == bmax, valid)
    bidx = jnp.min(jnp.where(is_max, colid, INT_BIG), axis=-1,
                   keepdims=True)
    take = bmax > bv_scr[:, :1]
    bi_scr[:] = jnp.broadcast_to(jnp.where(take, bidx, bi_scr[:, :1]),
                                 bi_scr.shape)
    bv_scr[:] = jnp.broadcast_to(jnp.where(take, bmax, bv_scr[:, :1]),
                                 bv_scr.shape)

    @pl.when(j == nv - 1)
    def _():
        lse = m_scr[:, :1] + jnp.log(l_scr[:, :1])   # [bt, 1]
        gold = gold_scr[:, :1]
        if label_smoothing:
            gold = ((1.0 - label_smoothing) * gold
                    + (label_smoothing / vocab_size) * lsum_scr[:, :1])
        ce_ref[...] = jnp.broadcast_to(lse - gold, ce_ref.shape)
        corr_ref[...] = jnp.broadcast_to(
            (bi_scr[:, :1] == t_col).astype(jnp.float32), corr_ref.shape)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _dx_kernel(x_ref, w_ref, b_ref, t_ref, lse_ref, coef_ref, dx_ref,
               dx_scr, *, bv, vocab_size, label_smoothing,
               w_vocab_axis):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        dx_scr[:] = jnp.zeros_like(dx_scr)

    logits, colid, valid = _block_logits(x_ref, w_ref, b_ref, j, bv,
                                         vocab_size, w_vocab_axis)
    d = _dlogits(logits, colid, valid, lse_ref[:, :1], t_ref[:, :1],
                 coef_ref[:, :1], vocab_size, label_smoothing)
    w = w_ref[...]
    dims = ((((1,), (0,)), ((), ())) if w_vocab_axis == 0
            else (((1,), (1,)), ((), ())))
    dx_scr[:] += jax.lax.dot_general(d.astype(w.dtype), w, dims,
                                     preferred_element_type=jnp.float32)

    @pl.when(j == nv - 1)
    def _():
        dx_ref[...] = dx_scr[:].astype(dx_ref.dtype)


def _dw_kernel(x_ref, w_ref, b_ref, t_ref, lse_ref, coef_ref,
               dw_ref, db_ref, dw_scr, db_scr, *, bv, vocab_size,
               label_smoothing, w_vocab_axis):
    i = pl.program_id(0)                             # vocab block
    j = pl.program_id(1)                             # token block (inner)
    nt = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    logits, colid, valid = _block_logits(x_ref, w_ref, b_ref, i, bv,
                                         vocab_size, w_vocab_axis)
    d = _dlogits(logits, colid, valid, lse_ref[:, :1], t_ref[:, :1],
                 coef_ref[:, :1], vocab_size, label_smoothing)
    x = x_ref[...]
    if w_vocab_axis == 0:                            # dw [bv, D]
        dw_scr[:] += jax.lax.dot_general(
            d.astype(x.dtype), x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:                                            # dw [D, bv]
        dw_scr[:] += jax.lax.dot_general(
            x, d.astype(x.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    # Every sublane row accumulates the same [1, bv] sum; read row 0.
    db_scr[:] += jnp.broadcast_to(
        jnp.sum(d, axis=0, keepdims=True), db_scr.shape)

    @pl.when(j == nt - 1)
    def _():
        dw_ref[...] = dw_scr[:].astype(dw_ref.dtype)
        db_ref[...] = db_scr[:]


def _pad_vocab_dim(w, bias, vocab_size, bv, w_vocab_axis):
    pad = (-vocab_size) % bv
    if pad:
        widths = [(0, 0)] * w.ndim
        widths[w_vocab_axis] = (0, pad)
        w = jnp.pad(w, widths)
        bias = jnp.pad(bias, (0, pad))
    return w, bias, vocab_size + pad


def _w_spec(D, bv, w_vocab_axis, outer="v"):
    """BlockSpec for the head matrix in either orientation. ``outer``
    names which grid axis walks the vocab blocks (fwd/dx grids are
    (token, vocab); the dw grid is (vocab, token))."""
    pick = (lambda i, j: j) if outer == "v" else (lambda i, j: i)
    if w_vocab_axis == 0:
        return pl.BlockSpec((bv, D), lambda i, j: (pick(i, j), 0))
    return pl.BlockSpec((D, bv), lambda i, j: (0, pick(i, j)))


def _lanes(v):
    """[T] -> [T, LANES] replicated (the mappable per-token layout)."""
    return jnp.broadcast_to(v[:, None], (v.shape[0], LANES))


# A kernel's blocks and scratch live on Mosaic's scoped-VMEM stack,
# 16 MiB on the v5e. The dw kernel is the greedy one: the head block
# twice (input double buffer), its gradient block twice (output double
# buffer) and an f32 accumulator. At GPT-2-small width with f32 params
# that is 30 MiB at bv=2048, and the compiler refuses the whole train
# step ("exceeded scoped vmem limit"); the budget leaves room for the
# token-side blocks.
_DW_VMEM_BUDGET = 12 * 2 ** 20


def _dw_vocab_block(bv, D, w_dtype):
    """The dw kernel's own vocab block: ``bv`` halved until its three
    head-shaped buffers fit (halves keep dividing the padded vocab and
    stay lane-aligned)."""
    per_col = D * (4 * np.dtype(w_dtype).itemsize + 4)
    while bv * per_col > _DW_VMEM_BUDGET and (bv // 2) % 128 == 0:
        bv //= 2
    return bv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def fused_ce_tokens(x, w, bias, targets, mask, vocab_size, bt, bv,
                    label_smoothing, w_vocab_axis, interpret):
    """Per-token (ce, correct) via the Pallas kernels.

    x: [T, D] (T % bt == 0, D % 128 == 0); w: head matrix, vocab dim on
    ``w_vocab_axis``; bias: [V] (callers pass zeros when the head has
    none — the kernel always adds it); targets/mask: [T]. Returns
    (ce [T] f32, correct [T] f32); reduce with the mask outside.
    Differentiable wrt x, w, bias — the cotangent of ce[t] (which the
    mask rides when the caller reduces sum(ce * mask)) scales that
    token's dlogits row.
    """
    ce, corr, _ = _fwd(x, w, bias, targets, vocab_size, bt, bv,
                       label_smoothing, w_vocab_axis, interpret)
    return ce, corr


def _fwd(x, w, bias, targets, vocab_size, bt, bv, label_smoothing,
         w_vocab_axis, interpret):
    T, D = x.shape
    wp, bp, vp = _pad_vocab_dim(w, bias, vocab_size, bv, w_vocab_axis)
    grid = (T // bt, vp // bv)
    kernel = functools.partial(
        _fwd_kernel, bv=bv, vocab_size=vocab_size,
        label_smoothing=label_smoothing, w_vocab_axis=w_vocab_axis)
    row = pl.BlockSpec((bt, LANES), lambda i, j: (i, 0))
    ce, corr, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, D), lambda i, j: (i, 0)),
            _w_spec(D, bv, w_vocab_axis),
            pl.BlockSpec((LANES, bv), lambda i, j: (0, j)),
            row,
        ],
        out_specs=[row, row, row],
        out_shape=[jax.ShapeDtypeStruct((T, LANES), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((bt, 128), jnp.float32)] * 5
        + [pltpu.VMEM((bt, 128), jnp.int32)],
        interpret=interpret,
        name="fused_ce_fwd",
    )(x, wp, jnp.broadcast_to(bp[None], (LANES, vp)),
      _lanes(targets.astype(jnp.int32)))
    return ce[:, 0], corr[:, 0], lse[:, 0]


def _fused_ce_tokens_fwd(x, w, bias, targets, mask, vocab_size, bt, bv,
                         label_smoothing, w_vocab_axis, interpret):
    ce, corr, lse = _fwd(x, w, bias, targets, vocab_size, bt, bv,
                         label_smoothing, w_vocab_axis, interpret)
    return (ce, corr), (x, w, bias, targets, mask, lse)


def _fused_ce_tokens_bwd(vocab_size, bt, bv, label_smoothing,
                         w_vocab_axis, interpret, res, cots):
    x, w, bias, targets, mask, lse = res
    g_ce, _ = cots                                   # correct: metric only
    T, D = x.shape
    wp, bp, vp = _pad_vocab_dim(w, bias, vocab_size, bv, w_vocab_axis)
    row = pl.BlockSpec((bt, LANES), lambda i, j: (i, 0))
    common = dict(bv=bv, vocab_size=vocab_size,
                  label_smoothing=label_smoothing,
                  w_vocab_axis=w_vocab_axis)
    args = (x, wp, jnp.broadcast_to(bp[None], (LANES, vp)),
            _lanes(targets.astype(jnp.int32)), _lanes(lse),
            _lanes(g_ce.astype(jnp.float32)))

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, **common),
        grid=(T // bt, vp // bv),
        in_specs=[
            pl.BlockSpec((bt, D), lambda i, j: (i, 0)),
            _w_spec(D, bv, w_vocab_axis),
            pl.BlockSpec((LANES, bv), lambda i, j: (0, j)),
            row, row, row,
        ],
        out_specs=pl.BlockSpec((bt, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, D), jnp.float32)],
        interpret=interpret,
        name="fused_ce_dx",
    )(*args)

    # Transposed grid: vocab outer, tokens inner (the dkv pattern),
    # walking the dw kernel's own VMEM-sized vocab block.
    bv = _dw_vocab_block(bv, D, w.dtype)
    rowT = pl.BlockSpec((bt, LANES), lambda i, j: (j, 0))
    dw_shape = ((vp, D) if w_vocab_axis == 0 else (D, vp))
    dw_block = ((bv, D) if w_vocab_axis == 0 else (D, bv))
    dw_map = ((lambda i, j: (i, 0)) if w_vocab_axis == 0
              else (lambda i, j: (0, i)))
    dw, db = pl.pallas_call(
        functools.partial(_dw_kernel, **dict(common, bv=bv)),
        grid=(vp // bv, T // bt),
        in_specs=[
            pl.BlockSpec((bt, D), lambda i, j: (j, 0)),
            _w_spec(D, bv, w_vocab_axis, outer="i"),
            pl.BlockSpec((LANES, bv), lambda i, j: (0, i)),
            rowT, rowT, rowT,
        ],
        out_specs=[pl.BlockSpec(dw_block, dw_map),
                   pl.BlockSpec((LANES, bv), lambda i, j: (0, i))],
        out_shape=[jax.ShapeDtypeStruct(dw_shape, w.dtype),
                   jax.ShapeDtypeStruct((LANES, vp), jnp.float32)],
        scratch_shapes=[pltpu.VMEM(dw_block, jnp.float32),
                        pltpu.VMEM((LANES, bv), jnp.float32)],
        interpret=interpret,
        name="fused_ce_dw",
    )(*args)

    if w_vocab_axis == 0:
        dw = dw[:vocab_size]
    else:
        dw = dw[:, :vocab_size]
    db = db[0, :vocab_size].astype(bias.dtype)
    return (dx, dw.astype(w.dtype), db,
            np.zeros(targets.shape, jax.dtypes.float0),
            _zeros_cotangent(mask))


fused_ce_tokens.defvjp(_fused_ce_tokens_fwd, _fused_ce_tokens_bwd)


DEFAULT_BT = 256
DEFAULT_BV = 2048


def kernel_supported(T: int, D: int, bt: int = DEFAULT_BT,
                     bv: int = DEFAULT_BV) -> bool:
    """Shape gate for the kernel path (else use the scan formulation,
    ops/fused_ce.py — same math, all shapes). D rides as a full block
    dim (legal at any size by dim-equality; 128 multiples are the
    fast layouts), so only sublane alignment constrains it."""
    bt = min(bt, T)
    return T % bt == 0 and bt % 8 == 0 and D % 8 == 0 and bv % 128 == 0


def fused_ce_sums_kernel(x: jax.Array, w: jax.Array,
                         bias: Optional[jax.Array], targets: jax.Array,
                         mask: jax.Array, vocab_size: int, *,
                         bt: int = DEFAULT_BT, bv: int = DEFAULT_BV,
                         label_smoothing: float = 0.0,
                         w_vocab_axis: int = 0,
                         interpret: Optional[bool] = None):
    """Drop-in for ops.fused_ce.fused_ce_sums on kernel-supported
    shapes: (ce_sum, correct, mask_sum), differentiable wrt x/w/bias.

    x: [..., D] — leading dims flatten to the token axis.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    D = x.shape[-1]
    T = x.size // D
    bt = min(bt, T)
    if not kernel_supported(T, D, bt, bv):
        raise ValueError(
            f"fused_ce kernel unsupported for T={T}, D={D} "
            f"(bt={bt}, bv={bv}); use ops.fused_ce.fused_ce_sums")
    xf = x.reshape(T, D)
    tf_ = targets.reshape(T).astype(jnp.int32)
    mf = mask.reshape(T).astype(jnp.float32)
    if bias is None:
        bias = jnp.zeros((vocab_size,), jnp.float32)
    ce, corr = fused_ce_tokens(xf, w, bias, tf_, mf, vocab_size, bt,
                               bv, label_smoothing, w_vocab_axis,
                               interpret)
    return jnp.sum(ce * mf), jnp.sum(corr * mf), jnp.sum(mf)
