"""Pallas TPU flash attention: fused, tiled, O(L) memory.

The reference computes no attention at all (its model is a LeNet CNN,
mnist_python_m.py:104-128) and leaves every op kernel to stock
TensorFlow C++ (SURVEY.md N11). This framework's sequence family
(models/transformer.py) is TPU-first, and attention is its hot op —
so it gets a hand-written Pallas kernel rather than leaning on XLA's
generic fusion:

- **Forward**: one `pallas_call` over a (batch*heads, Lq/bq, Lk/bk)
  grid; the full [L, L] score matrix never exists in HBM. Softmax
  statistics in f32; both matmuls hit the MXU with
  `preferred_element_type=f32`.
- **Backward**: custom VJP with two more Pallas kernels (dq over the
  q-block grid; dk/dv over the k-block grid) that recompute scores
  from the saved logsumexp instead of storing probabilities — the
  standard flash-attention memory trade, expressed natively.
- **Two levels** (`flash_plan`). Where one head's q, K and V fit VMEM
  together (L up to 1024: every training shape the benchmark has), a
  head is ONE grid step and the kernels tile INSIDE it: a q tile
  (dkv: a k tile) takes the slabs of its causal band — its loops end
  at the diagonal and start at the window's horizon — with extents
  that are Python ints, so the softmax of a tile's rows is one pass
  (one row max, one exp, one row sum, no running rescale) and the
  result is written at once. Only slabs the diagonal or the window's
  edge crosses are masked. Past that (long Lk) the grid gets a k-major
  axis, a tile is a whole block, and K/V blocks stream through VMEM
  under a running (max, sum, weighted-V) accumulator in VMEM scratch;
  TPU grids execute sequentially with the last axis fastest, which is
  what makes scratch accumulation across the inner axis sound.
- **The forward's options** (``ops/latent_attention.py::prefill_attend``
  uses them all): a value width that is not the keys', an explicit
  softmax scale, a selection ``keep [L, Lk]`` streamed as an int8 tile
  beside each K / V block in place of the positions' compare, no row
  statistics (``stats=False``: nothing differentiates that call), and a
  name of the call's own.

On non-TPU backends the kernels run under `interpret=True` (tests) or
callers use `parallel.ring_attention.full_attention` (the XLA oracle).
Causal masking is applied in-kernel and the masked part of the square
is SKIPPED, at whichever level walks the band (`_band` is the one
statement of it). Inside a grid step the skipped tiles are simply not
in the unrolled body. On a k-major grid, which is rectangular and
executes every step, the skip is (a) a `pl.when` predicate around the
compute body — Mosaic emits real branches, the MXU never sees the
masked block — and (b) an index_map that re-points the skipped step's
K/V (resp. Q/dO) BlockSpec at a block inside the band, so the pipeline
issues no DMA for it either. Under a WINDOW the forward's key axis is
not the context's blocks but the band's (`_band_steps`: the most key
blocks a query block's band touches, counted from each band's first), so
the steps a rectangular grid would execute only to skip are not there. What causal attention pays of the
full-grid FLOPs is `FlashPlan.tiles_computed / tiles_total`: 10/16 at
L = 1024 under 256-tiles (the lower triangle plus the diagonal tiles;
the kernels' device time fell to 0.62 of the whole-square kernels'),
tending to a half as L grows.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

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


def _causal_mask(s, r0, c0, window=0):
    """Mask one score tile whose first row / column sit at r0 / c0
    (window_keep over the tile's absolute positions)."""
    rows = r0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = c0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(window_keep(rows, cols, window), s, NEG_INF)


# ------------------------------------------------------- the causal band
# ONE statement of which tiles the band (row - window, row] touches,
# asked by the three kernels' loop bounds, the k-major index maps and
# the plan's static counts. It is written for either walk: a kernel
# holds a tile of one axis FIXED (fwd and dq a q tile, dkv a k tile)
# and WALKS tiles of the other, and index ``a`` of the fixed axis sees
# the walked indices [a + lo, a + hi) (None = unbounded):
#   walking keys from query row r:      (r - window, r]   -> lo = 1 - window, hi = 1
#   walking queries from key column c:  [c, c + window)   -> lo = 0, hi = window
# Over a fixed tile [a0, a0 + na) the UNION of those intervals is what
# must be computed and their INTERSECTION is what needs no mask.

def _offsets(causal, window, walk_keys):
    if not causal:
        return None, None
    if walk_keys:
        return (1 - window if window else None), 1
    return 0, (window or None)


def _int_or_traced(on_ints, on_traced):
    return lambda a, b: (on_ints(a, b) if isinstance(a, int)
                         and isinstance(b, int) else on_traced(a, b))


_imax = _int_or_traced(max, jnp.maximum)
_imin = _int_or_traced(min, jnp.minimum)


def _band(a0, na, t, lo, hi, w0, nw):
    """(first, full_lo, full_hi, last) over the ``nw`` walked tiles of
    size ``t`` that start at element ``w0``: tiles [first, last) hold a
    kept score of the fixed tile [a0, a0 + na); of those [full_lo,
    full_hi) hold kept scores only, the rest are crossed by the
    diagonal or the window's edge. Python ints in, ints out (the
    plan's counts, a non-causal kernel's static loops); traced
    offsets in, traced bounds out (the kernels). Every operand of //
    is clamped non-negative first: lax integer division truncates."""
    first = full_lo = 0
    last = full_hi = nw
    if lo is not None:
        first = _imin(_imax(a0 + lo - w0, 0) // t, nw)
        full_lo = _imin((_imax(a0 + na - 1 + lo - w0, 0) + t - 1) // t, nw)
    if hi is not None:
        last = _imin((_imax(a0 + na - 1 + hi - w0, 0) + t - 1) // t, nw)
        full_hi = _imin(_imax(a0 + hi - w0, 0) // t, nw)
    full_lo = _imin(_imax(full_lo, first), last)
    full_hi = _imin(_imax(full_hi, full_lo), last)
    return first, full_lo, full_hi, last


def _walk(band, body, mask_all=False):
    """body(slabs) over a band, a slab being (t0, n, masked): the walked
    tiles [t0, t0 + n) taken as ONE operand. Static bounds (a grid of
    one step a head: the offsets are Python ints) give one call with at
    most three slabs, each as wide as its run: the edge runs are
    masked, the run between them sees no iota, compare or select.
    Bounds that depend on program_id come with a walked block of one
    tile (the plan sees to it), so the walk is the grid-level skip: the
    block whole, masked only if an edge crosses it, or not at all.
    ``mask_all``: the mask is an operand (a selection inside the band),
    so every tile of the band is a masked one."""
    first, full_lo, full_hi, last = band
    if mask_all:
        full_lo = full_hi = first
    if all(isinstance(x, int) for x in band):
        if full_lo == full_hi:
            runs = ((first, last, True),)
        else:
            runs = ((first, full_lo, True), (full_lo, full_hi, False),
                    (full_hi, last, True))
        slabs = [(t0, t1 - t0, masked) for t0, t1, masked in runs if t1 > t0]
        if slabs:   # none: rows no key is visible to stay unwritten
            body(slabs)
        return
    if mask_all:
        pl.when(last > first)(lambda: body([(0, 1, True)]))
        return
    full = full_hi > full_lo
    pl.when(full)(lambda: body([(0, 1, False)]))
    pl.when(jnp.logical_and(last > first, jnp.logical_not(full)))(
        lambda: body([(0, 1, True)]))


def _pid(axis):
    """program_id, or a static 0 on a grid axis of one step (the grid's
    extents are static), so that a whole-sequence block's offsets fold."""
    return 0 if pl.num_programs(axis) == 1 else pl.program_id(axis)


def _band_steps(fixed, n_fixed, walk, n_walk, lo, hi):
    """Steps the walked grid axis takes: all ``n_walk`` blocks, or, under
    a band bounded on BOTH sides (a window), the most blocks any fixed
    block's band touches. The axis then counts from each band's first
    block, and its last steps may lie past a band's end. (A rectangular
    grid over a 128-wide band of 12,288 positions in 1,024-blocks
    executes 12 steps a fixed block to compute two.)"""
    if lo is None or hi is None:
        return n_walk
    return max(1, max(last - first for first, _, _, last in (
        _band(i * fixed, fixed, walk, lo, hi, 0, n_walk)
        for i in range(n_fixed))))


def _walk_index(fixed, walk, n_walk, lo, hi, steps=None):
    """(i, j) -> the walked block a (b, i, j) grid step holds, where the
    j axis walks ``steps`` (None: all ``n_walk``) blocks of ``walk``
    elements past fixed block i: a grid step outside the band is
    re-pointed at the nearest block inside it, so the pipeline issues no
    DMA for it (Pallas copies only when the block index changes)."""
    steps = n_walk if steps is None else steps
    if n_walk == 1 or (lo is None and hi is None):
        return lambda i, j: j

    def held(i, j):
        first, _, _, last = _band(i * fixed, fixed, walk, lo, hi, 0, n_walk)
        return jnp.clip(j if steps == n_walk else first + j, first,
                        last - 1)

    return held


def _walk_map(fixed, walk, n_walk, lo, hi, steps=None):
    """index_map of a walked [BH, n, D] operand (:func:`_walk_index`)."""
    held = _walk_index(fixed, walk, n_walk, lo, hi, steps)
    return lambda b, i, j: (b, held(i, j), 0)


# ------------------------------------------------------------------ plan

_BLOCK = 1024            # largest grid-level block of either axis
_TILES = (256, 128)      # in-kernel tile, first that divides the block
_VMEM_BUDGET = 12 << 20  # of Mosaic's 16 MiB scoped default


class FlashPlan(NamedTuple):
    """What the kernels are built from: grid-level blocks (one DMA, one
    grid step each), the tiles the in-kernel loops walk, and how much
    of the score square those loops touch."""
    block_q: int
    block_k: int
    tile_q: int
    tile_k: int
    tiles_total: int
    tiles_computed: int     # tiles holding at least one kept score
    tiles_masked: int       # of those, tiles an edge crosses

    def describe(self) -> dict:
        """The run's ``start`` record carries this."""
        return {**self._asdict(),
                "computed_share": self.tiles_computed / self.tiles_total,
                "masked_share": self.tiles_masked / self.tiles_computed}


def _tile(block):
    return next((t for t in _TILES if block % t == 0), block)


def _vmem_bytes(bq, bk, D, itemsize):
    """VMEM the blocks of one grid step of the hungriest kernel (dkv)
    take: double-buffered operands and results, f32 accumulators. Minor
    dims pad to 128 lanes. (The score slabs come on top: KBs to 1 MB
    under tiles, 4 MB each where a tile is a whole 1024 block.)"""
    dp = -(-D // 128) * 128
    q_side = 3 * bq * dp * itemsize + bq * 128 * 4      # q, do, o; lse
    k_side = 2 * bk * dp * itemsize                     # k, v
    return (2 * (q_side + k_side) + 2 * k_side          # in; dk, dv out
            + 2 * bk * dp * 4)                          # dk, dv scratch


def flash_plan(L: int, Lk: int, D: int, dtype=jnp.bfloat16, *,
               causal: bool = False, window: int = 0,
               block_q: Optional[int] = None,
               block_k: Optional[int] = None,
               vmem_bytes: int = _VMEM_BUDGET,
               Dv: Optional[int] = None) -> Optional[FlashPlan]:
    """THE choice of blocks and tiles, asked by supported(),
    flash_attention() and flash_attention_partial() alike; None where
    the kernels do not take the shape. ``Dv``: the values' width where
    it is not the queries' and keys' (the forward kernel alone takes
    that).

    Where one head's q (up to 1024 rows), K and V fit the byte budget
    together, a head is ONE grid step and the kernels tile inside it:
    every offset is static, so each q tile (dkv: k tile) takes its band
    as slabs whose extents are Python ints. Past the budget (long Lk),
    or past 1024 rows of q, the grid gets a k-major axis of the largest
    block that fits and a tile is a whole block: the band is then
    walked by the grid, one `pl.when` a step. ``block_q`` / ``block_k``
    pin the grid-level blocks (tests).
    """
    if max(D, Dv or D) > 256 or D % 8 or (Dv or D) % 8:
        return None
    itemsize = jnp.dtype(dtype).itemsize
    bq = min(block_q or _BLOCK, L)
    one_step = (bq == L and (block_k or Lk) >= Lk
                and _vmem_bytes(L, Lk, D, itemsize) <= vmem_bytes)
    if one_step:
        blocks = [Lk]
    elif block_k:
        blocks = [min(block_k, Lk)]
    else:
        blocks = [b for b in (1024, 512, 256, 128) if b <= Lk] or [Lk]
    blocks = [b for b in blocks if Lk % b == 0 and b % 8 == 0]
    if L % bq or bq % 8 or not blocks:
        return None
    bk = next((b for b in blocks
               if _vmem_bytes(bq, b, D, itemsize) <= vmem_bytes),
              min(blocks))
    tq, tk = (_tile(bq), _tile(bk)) if one_step else (bq, bk)
    lo, hi = _offsets(causal, window, walk_keys=True)
    computed = masked = 0
    for r0 in range(0, L, tq):
        first, full_lo, full_hi, last = _band(r0, tq, tk, lo, hi, 0,
                                              Lk // tk)
        computed += last - first
        masked += (full_lo - first) + (last - full_hi)
    return FlashPlan(bq, bk, tq, tk, (L // tq) * (Lk // tk), computed,
                     masked)


def _require_plan(name, q, k, causal, window, block_q, block_k):
    L, Lk, D = q.shape[1], k.shape[1], q.shape[3]
    plan = flash_plan(L, Lk, D, q.dtype, causal=causal, window=window,
                      block_q=block_q, block_k=block_k)
    if plan is None:
        # A grid over ragged blocks would silently skip the tail rows
        # (whose output buffer is uninitialized memory) — refuse.
        raise ValueError(
            f"{name}: seq lens ({L}, {Lk}) must divide the clamped "
            f"blocks ({block_q or _BLOCK}, {block_k or 'auto'}) and the "
            f"head dim {D} must be a multiple of 8 up to 256; see "
            f"supported()")
    return plan


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, window, plan,
                partial, keep=False, n_k=None):
    """One (head, q block, k block) grid step. Each q tile of the block
    takes the k slabs of ITS band in one pass: scores of every slab,
    one row max over them, one exp, one row sum. Where a head is one
    grid step that IS the softmax and the tile's rows are written at
    once; where the grid walks k blocks the pass folds into the (m, l,
    acc) streaming-softmax accumulators in VMEM scratch. ``partial``
    (the ring's variant) writes the accumulators out raw: o
    unnormalized in f32, m and l instead of the folded lse. ``keep``: a
    fourth operand, the [bq, bk] tile of a selection inside the band
    (nonzero = kept), takes the place of the positions' compare in
    EVERY tile: a dropped score is NEG_INF and its probability 0 (a row
    may keep nothing of a tile, and exp(NEG_INF - NEG_INF) is 1).
    ``n_k``: the key blocks there are, where the grid's k axis is
    shorter and counts from each q block's band (:func:`_band_steps`)."""
    keep_ref = None
    if keep:
        keep_ref, *refs = refs
    *out_refs, m_scr, l_scr, acc_scr = refs
    bq, bk, tq, tk = plan[:4]
    i, j = _pid(1), _pid(2)
    one_step = isinstance(i, int) and isinstance(j, int)
    lo, hi = _offsets(causal, window, walk_keys=True)
    # the key block this step holds, which the accumulators' first and
    # last step (j) need not be
    jk = j if n_k is None else j + _band(i * bq, bq, bk, lo, hi, 0, n_k)[0]

    def write(rows, m, l, acc):
        # Row statistics ride in [BH, L, 8] buffers: Mosaic requires the
        # last two block dims to divide (8, 128) or equal the array
        # dims, so a flat [BH, L] row output is unmappable; 8 lanes of
        # replication is the cheapest legal layout (the stock jax
        # kernel uses 128).
        wide = (acc.shape[0], 8)
        if partial:
            o_ref, m_ref, l_ref = out_refs
            o_ref[0, rows, :] = acc                    # UNnormalized, f32
            m_ref[0, rows, :] = jnp.broadcast_to(m, wide)
            l_ref[0, rows, :] = jnp.broadcast_to(l, wide)
        else:
            o_ref, *lse_ref = out_refs
            o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
            for ref in lse_ref:                        # none: stats=False
                ref[0, rows, :] = jnp.broadcast_to(m + jnp.log(l), wide)

    if not one_step:
        @pl.when(j == 0)
        def _():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    for qt in range(bq // tq):
        rows = pl.ds(qt * tq, tq)
        r0 = i * bq + qt * tq

        def attend(slabs, rows=rows, r0=r0):
            q = q_ref[0, rows, :]                      # [tq, D]
            scores, kept = [], []
            for t0, n, masked in slabs:
                s = jax.lax.dot_general(
                    q, k_ref[0, pl.ds(t0 * tk, n * tk), :],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                kp = None
                if keep_ref is not None:
                    kp = keep_ref[rows, pl.ds(t0 * tk, n * tk)] != 0
                    s = jnp.where(kp, s, NEG_INF)
                elif masked:
                    s = _causal_mask(s, r0, jk * bk + t0 * tk, window)
                scores.append(s)                       # [tq, n * tk] f32
                kept.append(kp)
            maxes = [jnp.max(s, axis=-1, keepdims=True) for s in scores]
            if not one_step:
                m_prev = m_scr[rows, :1]               # [tq, 1] f32
                maxes.append(m_prev)
            m = functools.reduce(jnp.maximum, maxes)
            l, acc = 0.0, 0.0
            for (t0, n, _), s, kp in zip(slabs, scores, kept):
                p = jnp.exp(s - m)
                if kp is not None:
                    p = jnp.where(kp, p, 0.0)
                v = v_ref[0, pl.ds(t0 * tk, n * tk), :]
                l = l + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            if one_step:
                write(rows, m, l, acc)
                return
            alpha = jnp.exp(m_prev - m)
            acc_scr[rows, :] = acc_scr[rows, :] * alpha + acc
            l_scr[rows, :] = jnp.broadcast_to(
                l_scr[rows, :1] * alpha + l, (tq, 128))
            m_scr[rows, :] = jnp.broadcast_to(m, (tq, 128))

        _walk(_band(r0, tq, tk, lo, hi, jk * bk, bk // tk), attend,
              mask_all=keep)

    if not one_step:
        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            write(pl.ds(0, bq), m_scr[:, :1], l_scr[:, :1], acc_scr[:])


# A step has one call site a layer (72 kernels in GPT-2 medium's), all
# of one shape: under jit the unrolled kernel bodies are traced, and
# lowered to Mosaic, once a shape instead of once a call site.
_STATIC = ("causal", "plan", "interpret", "window", "partial")


def _fwd_vmem_limit(plan, D, Dv, itemsize, keep):
    """None where a forward grid step of ``plan`` fits the budget the
    plan is made under (the training shapes: Mosaic's default stands),
    else the limit to compile it under: half again of what the step
    holds by this count. That is its blocks double-buffered (q, k, v, o,
    a selection's int8 tile), the accumulators, and the widest score
    slab three times over (f32 scores, f32 probabilities, probabilities
    in the values' dtype): 18 MiB for a [1024, 1024] step at 256-wide
    heads under a selection, which is what Mosaic asked for there (18.8
    MB), and 14.5 MiB at 192 / 128 without one."""
    bq, bk, tq, _ = plan[:4]
    pad = lambda d: -(-d // 128) * 128                 # noqa: E731
    blocks = 2 * itemsize * (bq * (pad(D) + pad(Dv))
                             + bk * (pad(D) + pad(Dv)))
    scratch = 4 * bq * (2 * 128 + pad(Dv))
    slab = tq * bk * (4 + 4 + itemsize)
    need = blocks + scratch + slab + (2 * bq * bk if keep else 0)
    return None if need <= _VMEM_BUDGET else need * 3 // 2


@functools.partial(jax.jit,
                   static_argnames=_STATIC + ("scale", "stats", "name"))
def _fwd(q, k, v, causal, plan, interpret, window=0, partial=False,
         keep=None, scale=None, stats=True, name=None):
    """q, k [BH, L / Lk, D], v [BH, Lk, Dv] -> (o [BH, L, Dv], row
    statistics). ``keep`` [L, Lk] int8, shared by the heads, streams as
    a tile beside each K / V block; ``scale`` None is 1 / sqrt(D);
    ``stats`` False (a forward nobody differentiates; not with
    ``partial``) returns o alone: a [BH, L, 8] float32 statistic is
    stored 128 lanes wide, 268 MB at 64 heads of 8,192 rows; ``name`` is
    the call's instruction name in a compiled program."""
    assert stats or not partial, "the ring's variant IS its statistics"
    BH, L, D = q.shape
    Lk, Dv = k.shape[1], v.shape[2]
    bq, bk = plan.block_q, plan.block_k
    lo, hi = _offsets(causal, window, walk_keys=True)
    q_map = lambda b, i, j: (b, i, 0)                  # noqa: E731
    n_k = Lk // bk
    steps = _band_steps(bq, L // bq, bk, n_k, lo, hi)
    kv_map = _walk_map(bq, bk, n_k, lo, hi, steps)
    stat = jax.ShapeDtypeStruct((BH, L, 8), jnp.float32)
    stat_spec = pl.BlockSpec((1, bq, 8), q_map)
    n_stats = 0 if not stats else 2 if partial else 1
    operands, keep_spec = (q, k, v), []
    if keep is not None:
        held = _walk_index(bq, bk, n_k, lo, hi, steps)
        keep_spec = [pl.BlockSpec((bq, bk), lambda b, i, j: (i, held(i, j)))]
        operands += (keep,)
    limit = _fwd_vmem_limit(plan, D, Dv, q.dtype.itemsize, keep is not None)
    return pl.pallas_call(
        functools.partial(_fwd_kernel,
                          scale=1.0 / (D ** 0.5) if scale is None else scale,
                          causal=causal, window=window, plan=plan,
                          partial=partial, keep=keep is not None,
                          n_k=n_k if steps < n_k else None),
        grid=(BH, L // bq, steps),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, Dv), kv_map),
        ] + keep_spec,
        out_specs=[pl.BlockSpec((1, bq, Dv), q_map)] + [stat_spec] * n_stats,
        out_shape=[jax.ShapeDtypeStruct(
            (BH, L, Dv), jnp.float32 if partial else q.dtype)]
        + [stat] * n_stats,
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
        name=name or ("flash_fwd_partial" if partial else "flash_fwd"),
        **({"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)}
           if limit else {}),
    )(*operands)


# --------------------------------------------------------------- backward

def _delta(do, out):
    """rowsum(dO * O) recomputed per grid step — cheaper than
    materializing a lane-replicated [BH, L, 8] delta buffer in HBM."""
    return jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1, keepdims=True)        # [bq, 1]


def _row_terms(do_ref, a_ref, b_ref, partial):
    """(row_sub, row_add) of a whole q block for _p_and_ds, [bq, 1]
    each, once a grid step. The normalized kernels are handed (o, lse)
    and pass (lse, -delta); the partial kernels are handed (dl, m) and
    pass (m, +dl)."""
    stat = b_ref[0][:, :1]
    if partial:
        return stat, a_ref[0][:, :1]
    return stat, -_delta(do_ref[0], a_ref[0])


def _p_and_ds(q, k, v, do, row_sub, row_add, scale, mask):
    """Backward-pass slab math shared by the dq and dkv kernels:
    p = exp(s - row_sub) and ds = p * (do.v^T + row_add) * scale;
    ``mask`` is None or the slab's (r0, c0, window)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = _causal_mask(s, *mask)
    p = jnp.exp(s - row_sub)                       # [rows, cols]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp + row_add) * scale


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, a_ref, b_ref, dq_ref, dq_scr,
               *, scale, causal, window, plan, partial):
    bq, bk, tq, tk = plan[:4]
    i, j = _pid(1), _pid(2)
    one_step = isinstance(i, int) and isinstance(j, int)
    lo, hi = _offsets(causal, window, walk_keys=True)
    # Once a grid step where a step is a head; on a walked grid inside
    # the branch that needs them (a skipped step computes nothing).
    terms = _row_terms(do_ref, a_ref, b_ref, partial) if one_step else None

    if not one_step:
        @pl.when(j == 0)
        def _():
            dq_scr[:] = jnp.zeros_like(dq_scr)

    for qt in range(bq // tq):
        rows = pl.ds(qt * tq, tq)
        r0 = i * bq + qt * tq

        def k_slabs(slabs, qt=qt, rows=rows, r0=r0):
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]
            row_sub, row_add = terms or _row_terms(do_ref, a_ref, b_ref,
                                                   partial)
            dq = 0.0
            for t0, n, masked in slabs:
                cols = pl.ds(t0 * tk, n * tk)
                k = k_ref[0, cols, :]
                _, ds = _p_and_ds(
                    q, k, v_ref[0, cols, :], do,
                    row_sub[qt * tq:(qt + 1) * tq],
                    row_add[qt * tq:(qt + 1) * tq], scale,
                    (r0, j * bk + t0 * tk, window) if masked else None)
                dq = dq + jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            if one_step:
                dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
            else:
                dq_scr[rows, :] += dq

        _walk(_band(r0, tq, tk, lo, hi, j * bk, bk // tk), k_slabs)

    if not one_step:
        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, a_ref, b_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, causal, window, plan, partial):
    bq, bk, tq, tk = plan[:4]
    i, j = _pid(1), _pid(2)                        # k block, q block
    one_step = isinstance(i, int) and isinstance(j, int)
    lo, hi = _offsets(causal, window, walk_keys=False)
    terms = _row_terms(do_ref, a_ref, b_ref, partial) if one_step else None

    if not one_step:
        @pl.when(j == 0)
        def _():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

    for kt in range(bk // tk):
        cols = pl.ds(kt * tk, tk)
        c0 = i * bk + kt * tk

        def q_slabs(slabs, cols=cols, c0=c0):
            # From this k tile's diagonal down (to the window's
            # horizon): the q tiles above it are never visited.
            k, v = k_ref[0, cols, :], v_ref[0, cols, :]
            row_sub, row_add = terms or _row_terms(do_ref, a_ref, b_ref,
                                                   partial)
            dk, dv = 0.0, 0.0
            for t0, n, masked in slabs:
                rows = pl.ds(t0 * tq, n * tq)
                q, do = q_ref[0, rows, :], do_ref[0, rows, :]
                p, ds = _p_and_ds(
                    q, k, v, do, row_sub[t0 * tq:(t0 + n) * tq],
                    row_add[t0 * tq:(t0 + n) * tq], scale,
                    (j * bq + t0 * tq, c0, window) if masked else None)
                dv = dv + jax.lax.dot_general(
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dk = dk + jax.lax.dot_general(
                    ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            if one_step:
                dk_ref[0, cols, :] = dk.astype(dk_ref.dtype)
                dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)
            else:
                dk_scr[cols, :] += dk
                dv_scr[cols, :] += dv

        _walk(_band(c0, tk, tq, lo, hi, j * bq, bq // tq), q_slabs)

    if not one_step:
        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd(q, k, v, a, b, do, causal, plan, interpret, window=0,
         partial=False):
    """dq, then dk and dv: two calls, so that each accumulates over its
    own walked axis. (a, b) are (out, lse), or the partial variant's
    (dl, m)."""
    BH, L, D = q.shape
    Lk = k.shape[1]
    bq, bk = plan.block_q, plan.block_k
    nq, nk = L // bq, Lk // bk
    static = dict(scale=1.0 / (D ** 0.5), causal=causal, window=window,
                  plan=plan, partial=partial)
    tag = "_partial" if partial else ""

    def specs(q_map, k_map):
        """The six operands' specs: q, k, v, do, a, b."""
        row = pl.BlockSpec((1, bq, D), q_map)
        col = pl.BlockSpec((1, bk, D), k_map)
        stat = pl.BlockSpec((1, bq, 8), q_map)
        return [row, col, col, row, stat if partial else row, stat]

    fixed = lambda b, i, j: (b, i, 0)              # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid=(BH, nq, nk),
        in_specs=specs(fixed, _walk_map(
            bq, bk, nk, *_offsets(causal, window, walk_keys=True))),
        out_specs=pl.BlockSpec((1, bq, D), fixed),
        out_shape=jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name="flash_dq" + tag,
    )(q, k, v, do, a, b)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid=(BH, nk, nq),
        in_specs=specs(_walk_map(
            bk, bq, nq, *_offsets(causal, window, walk_keys=False)), fixed),
        out_specs=[pl.BlockSpec((1, bk, D), fixed)] * 2,
        out_shape=[
            jax.ShapeDtypeStruct((BH, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Lk, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32)] * 2,
        interpret=interpret,
        name="flash_dkv" + tag,
    )(q, k, v, do, a, b)
    return dq, dk, dv


# ----------------------------------------------- partial-softmax variant
# Ring attention's building block (parallel.ring_attention): one Q-block
# vs one K,V-block PARTIAL attention returning the streaming-softmax
# triple (m = row max, l = exp-sum, o = unnormalized weighted V) that
# the ring merges across steps. The SAME three kernels with
# ``partial=True``; the only differences are (a) o is written
# UNnormalized in f32 and (b) m and l are emitted instead of the folded
# lse.
#
# VJP convention: m is the numerical stabilizer of the streaming
# softmax — the merged result is invariant to it — so it is treated as
# stop-gradient (exactly like jax.nn.softmax's max-shift). With
# p = exp(s - m):   dl/ds_ij = p_ij,   do_i/ds_ij = p_ij * v_j
# =>  ds_ij = p_ij * (do_i . v_j + dl_i),  dq = scale * ds @ k,
#     dk = scale * ds^T @ q,  dv = p^T @ do.
# That is the normalized backward with rowsum(do*o) replaced by the
# incoming -dl cotangent (delta there IS the normalized-case dl):
# _row_terms.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_partial(q, k, v, causal, plan, interpret):
    return _fwd(q, k, v, causal, plan, interpret, partial=True)


def _flash_partial_fwd(q, k, v, causal, plan, interpret):
    o, m, l = _fwd(q, k, v, causal, plan, interpret, partial=True)
    return (o, m, l), (q, k, v, m)


def _flash_partial_bwd(causal, plan, interpret, res, cots):
    q, k, v, m = res
    do, _dm, dl = cots  # m is the stop-grad stabilizer (see above)
    return _bwd(q, k, v, dl, m, do.astype(jnp.float32), causal, plan,
                interpret, partial=True)


_flash_partial.defvjp(_flash_partial_fwd, _flash_partial_bwd)


def _pack(x):
    """[B, L, H, D] -> [B*H, L, D]: one head a grid row."""
    B, n, H, D = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, n, D)


def flash_attention_partial(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            causal: bool = False,
                            block_q: Optional[int] = None,
                            block_k: Optional[int] = None,
                            interpret: Optional[bool] = None):
    """Partial (unnormalized) blockwise attention for the ring path.

    q: [B, Lq, H, D]; k, v: [B, Lk, H, D]. Returns the streaming-
    softmax partials in ``parallel.ring_attention._block_attend``'s
    layout: (m [B,H,Lq] f32, l [B,H,Lq] f32, o [B,Lq,H,D] f32 —
    UNnormalized weighted V). Differentiable (custom VJP, Pallas both
    ways). ``causal=True`` applies the triangular mask (the ring's
    diagonal blocks, where q and k share global offsets).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, L, H, D = q.shape
    plan = _require_plan("flash_attention_partial", q, k, causal, 0,
                         block_q, block_k)
    o, m, l = _flash_partial(_pack(q), _pack(k), _pack(v), causal, plan,
                             interpret)
    o = jnp.transpose(o.reshape(B, H, L, D), (0, 2, 1, 3))
    return m[..., 0].reshape(B, H, L), l[..., 0].reshape(B, H, L), o


# ------------------------------------------------------------ public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, plan, interpret, window):
    out, _ = _fwd(q, k, v, causal, plan, interpret, window)
    return out


def _flash_fwd(q, k, v, causal, plan, interpret, window):
    out, lse = _fwd(q, k, v, causal, plan, interpret, window)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, plan, interpret, window, res, do):
    q, k, v, out, lse = res
    return _bwd(q, k, v, out, lse, do, causal, plan, interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, window: int = 0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused tiled attention. q,k,v: [B, L, H, D] -> [B, L, H, D].

    Differentiable (custom VJP, Pallas both ways). ``flash_plan``
    chooses the grid-level blocks and the in-kernel tiles from the
    shapes (`supported()` gates the dispatcher on the same function);
    ``block_q`` / ``block_k`` pin the grid-level blocks, which clamp to
    the sequence lengths and must divide them.

    What the plan picks at L = 1024, D = 64 and why (one v5e, bf16,
    128 heads a call, device time from a profiler trace; my chip runs,
    PR 31). One grid step a head with 256 x 256 tiles: forward 323 us,
    dq 366, dkv 521, against 525, 586 and 816 for the kernels that
    computed and masked the whole 1024 x 1024 square in one tile
    (which had "won" a round-4 sweep only because the alternative was
    smaller GRID blocks). 128-tiles visit less (36 of 64 tiles against
    10 of 16) and lose: 365 / 364 / 590; 512-tiles 334 / 420 / 559.
    The forms that lost on the way: `fori_loop`s over tiles with
    bounds from the band, 3.7x slower in the forward than the tile it
    replaced (Mosaic schedules a static body far better); static
    slabs folded one by one into the running accumulators, 905 us
    forward (the row-wise rescale bookkeeping cost more than the
    skipped half saved). The same one-pass form made the unmasked
    whole-square kernel faster too (non-causal L = 512: 295 -> 121 us
    forward, 169 -> 154 dq, 241 -> 226 dkv). The price of an unrolled
    body is TRACING, not compiling (Mosaic takes the three kernels in
    2.3-2.5 s against 1.8-2.3): Pallas traces a kernel at every call
    site, and 72 of them added 12 s to a four-chip run's first step
    call, which is why `_fwd` and `_bwd` are jitted (once a shape).
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
    plan = _require_plan("flash_attention", q, k, causal, window,
                         block_q, block_k)
    out = _flash(_pack(q), _pack(k), _pack(v), causal, plan, interpret,
                 window)
    return jnp.transpose(out.reshape(B, H, L, D), (0, 2, 1, 3))


def supported(L: int, Lk: int, D: int, block_q: Optional[int] = None,
              block_k: Optional[int] = None, dtype=jnp.bfloat16) -> bool:
    """Whether the Pallas kernel handles these shapes (else use the
    XLA path, parallel.ring_attention.full_attention)."""
    return flash_plan(L, Lk, D, dtype, block_q=block_q,
                      block_k=block_k) is not None


def use_flash(L: int, Lk: int, D: int, dtype=jnp.bfloat16) -> bool:
    """The ONE flash-dispatch gate, shared by the single-shard
    dispatcher (attention) and the ring path (_partial_attend): TPU
    backend (or TFD_FLASH_INTERPRET=1 forcing interpreter mode
    off-TPU, for CPU-mesh tests of the exact TPU code path) and
    kernel-supported shapes."""
    import os

    on_tpu = jax.default_backend() == "tpu"
    force = os.environ.get("TFD_FLASH_INTERPRET", "") == "1"
    return (on_tpu or force) and supported(L, Lk, D, dtype=dtype)


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
    if allow_flash and mask is None and use_flash(L, k.shape[1], D,
                                                  q.dtype):
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
