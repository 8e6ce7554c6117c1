"""The device side of the ``glm_moe_dsa`` family
(models/glm_moe_dsa.py): cache row writes, the learned sparse selection,
the latent attend over the selected rows, the masked prefill attend and
the grouped matmul over the experts a chip holds.

Everything here has an XLA form that runs anywhere (the CPU tests
compare it with the plain reference). On the TPU the parts a decode
step is made of are NAMED Pallas kernels, so that a profiler capture
can tell them apart (an anonymous fusion cannot be attributed):

    latent_row_write   one token's row into a [B, T, C] cache leaf, in place
    dsa_index_scores   the indexer's scores of one query a row against
                       that row's cached index keys
    mla_latent_attend  softmax over the gathered selected latent rows
                       (the gather itself is XLA's, a loop over the LIVE
                       slots: ``gather_live_rows``)
    mla_latent_attend_dense  a layer without a selection: online softmax
                       over the row's own cache row up to its depth, in
                       place, in blocks of positions
    (megablox ``gmm``) the held experts' three matmuls
    moe_combine_held   a block of the held experts' result rows added to
                       their tokens' rows, weighted, the held rows only
                       (the prefill buckets past the one-hot branch)
    mla_prefill_attend the expanded attend of a fresh context, one call a
                       layer: ``ops/flash_attention.py``'s forward kernel
                       with the values' own width, the family's softmax
                       scale and the selection as an operand, or under a
                       sliding window in blocks taken from the band
                       (:func:`prefill_attend`; a blocked XLA loop on the
                       other backends)

Nothing ``[L, L]`` exists per head: the prefill's index scores and its
attend run in blocks of queries, and the only whole ``[L, L]`` array is
the boolean selection itself (one byte a pair, shared by the layers
that share it); a layer without a selection has none at all (causal by
block index).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
#: Queries a block of the prefill's index scores / attend (the widest
#: temporary is [heads, block, L] f32).
SCORE_BLOCK = 256
ATTEND_BLOCK_Q = 512
ATTEND_BLOCK_K = 512


def _block(n: int, target: int) -> int:
    """The largest divisor of ``n`` that is at most ``target``."""
    if n <= target:
        return n
    for b in range(target, 0, -1):
        if n % b == 0:
            return b
    return 1


def _prec(dtype):
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# -- cache -------------------------------------------------------------------

def write_rows(buf: jax.Array, new: jax.Array, start: jax.Array
               ) -> jax.Array:
    """``buf`` [B, T, C] with ``new`` [B, L, C] written at each row's
    own ``start``. One token a row on the TPU goes through the Pallas
    row write (XLA's vmapped update is a loop over the rows there, 5.6 us
    a row: 1.2 ms of a decode step's seven leaves; my chip run, PR 28)."""
    start = start.astype(jnp.int32)
    if new.shape[1] == 1 and on_tpu() and row_write_supported(buf):
        return row_write_kernel(buf, new, start)
    return jax.vmap(lambda b, n, s: jax.lax.dynamic_update_slice(
        b, n.astype(b.dtype), (s, 0)))(buf, new, start)


# -- selection ---------------------------------------------------------------

def _sort_key(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    neg = (bits >> 31).astype(jnp.bool_)
    return jnp.where(neg, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest_key(keys: jax.Array, k: int) -> jax.Array:
    """The k-th largest of each row of ``keys`` [R, N] uint32, exactly,
    by building it bit by bit (32 counting passes; no sort). A row
    with fewer than ``k`` entries above its minimum gives that
    minimum."""
    def body(i, r):
        cand = r | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        n = jnp.sum(keys >= cand[:, None], axis=1)
        return jnp.where(n >= k, cand, r)

    return jax.lax.fori_loop(0, 32, body,
                             jnp.zeros((keys.shape[0],), jnp.uint32))


def _index_scores(q, k, w):
    """q [Q, nh, dh], k [S, dh], w [Q, nh] f32 -> I [Q, S] f32."""
    s = jnp.einsum("qhd,sd->hqs", q, k, preferred_element_type=jnp.float32,
                   precision=_prec(q.dtype))
    return jnp.einsum("hqs,qh->qs", jax.nn.relu(s), w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def prefill_selection(q: jax.Array, k: jax.Array, w: jax.Array,
                      topk: int) -> jax.Array:
    """The selection of every query of a fresh context, as a mask:
    ``keep[t, s]`` iff ``s <= t`` and ``I[t, s]`` is among the ``topk``
    largest of row t's causal scores (all causal ``s`` while ``t <
    topk``). q [L, nh, dh], k [L, dh], w [L, nh] -> bool [L, L]."""
    L = q.shape[0]
    bq = _block(L, SCORE_BLOCK)
    cols = jnp.arange(L)[None, :]

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq)
        wb = jax.lax.dynamic_slice_in_dim(w, i * bq, bq)
        causal = cols <= (i * bq + jnp.arange(bq))[:, None]
        scores = jnp.where(causal, _index_scores(qb, k, wb), -jnp.inf)
        if L <= topk:
            return causal
        keys = _sort_key(scores)
        thr = kth_largest_key(keys, topk)[:, None]
        above, tied = keys > thr, keys == thr
        # Ties at the k-th value go to the lowest positions, as
        # lax.top_k breaks them.
        room = topk - jnp.sum(above, axis=1, keepdims=True)
        return (above | (tied & (jnp.cumsum(tied, axis=1) <= room))) & causal

    with jax.named_scope("dsa_prefill_selection"):
        return jax.lax.map(block, jnp.arange(L // bq)).reshape(L, L)


def decode_selection(q: jax.Array, w: jax.Array, keys: jax.Array,
                     pos: jax.Array, topk: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """One query a row against that row's cached index keys: the
    ``topk`` positions ``<= pos`` with the largest scores. q [B, nh,
    dh], w [B, nh], keys [B, T, dh], pos [B] -> (idx [B, K] int32,
    valid [B, K]) with K = min(topk, T); fewer than K causal positions
    leave the rest invalid."""
    B, T, _ = keys.shape
    K = min(topk, T)
    with jax.named_scope("dsa_decode_selection"):
        if on_tpu() and index_scores_supported(q, keys):
            scores = index_scores_kernel(q, w, keys, pos)
        else:
            s = jnp.einsum("bhd,btd->bht", q, keys,
                           preferred_element_type=jnp.float32,
                           precision=_prec(q.dtype))
            scores = jnp.einsum("bht,bh->bt", jax.nn.relu(s),
                                w.astype(jnp.float32),
                                precision=jax.lax.Precision.HIGHEST)
            scores = jnp.where(jnp.arange(T)[None, :] <= pos[:, None],
                               scores, -jnp.inf)
        vals, idx = jax.lax.top_k(scores, K)
    return idx.astype(jnp.int32), vals > -jnp.inf


# -- attends -----------------------------------------------------------------

def live_slots(pos: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The ids of the live slots (depth above 0: an admitted row is at
    least one token deep) in slot order, then zeros, and how many there
    are. pos [B] -> (int32 [B], int32 scalar). By counting, not by a sort:
    a capture attributes the ``%sort`` ops before a layer's attend to its
    selection."""
    live = pos > 0
    at = jnp.arange(pos.shape[0], dtype=jnp.int32)
    place = jnp.cumsum(live, dtype=jnp.int32) - 1
    here = live[None, :] & (place[None, :] == at[:, None])    # [i, b]
    return (jnp.sum(jnp.where(here, at[None, :], 0), axis=1,
                    dtype=jnp.int32),
            jnp.sum(live, dtype=jnp.int32))


def gather_live_rows(cache: jax.Array, idx: jax.Array, pos: jax.Array
                     ) -> jax.Array:
    """``cache[b, idx[b]]`` for every LIVE slot b, zeros for a free one
    (finite: the attend softmaxes them and the step's NaN sensor reads
    every slot's logits). cache [B, T, C], idx [B, K], pos [B] -> [B, K,
    C]. A loop over the live slots, one slot's K rows a turn by XLA's own
    gather from the whole leaf: the gather is priced per ROW (17 ns
    whatever the row holds), so a step pays for the rows of the slots
    that are live and not for all B (7 of 32 at the benchmark's rate: 4.56
    of a 10.06 ms step went on free slots' rows; PERF.md section 6, PR
    34)."""
    B, K = idx.shape
    order, n_live = live_slots(pos)

    def slot(i, rows):
        b = order[i]
        # top_k's indices are positions of the cache: no bounds handling
        # (the default fills out-of-bounds rows through a select over the
        # whole gathered block, 165 us a layer; my chip run, PR 28)
        mine = cache.at[b, idx[b]].get(mode="promise_in_bounds")
        return jax.lax.dynamic_update_slice(rows, mine[None], (b, 0, 0))

    return jax.lax.fori_loop(
        0, n_live, slot, jnp.zeros((B, K, cache.shape[2]), cache.dtype))


def live_rows_gathered(pos: jax.Array, K: int) -> jax.Array:
    """Cache rows one call of :func:`gather_live_rows` moves: ``K`` a
    turn of its loop. pos [B] -> int32 scalar."""
    return live_slots(pos)[1] * K


def decode_attend(q_abs: jax.Array, q_rope: jax.Array, cache: jax.Array,
                  idx: jax.Array, valid: jax.Array, pos: jax.Array,
                  scale: float, rank: int, rope: int) -> jax.Array:
    """The absorbed attend of one query a row over the SELECTED rows of
    the latent cache. q_abs [B, H, rank], q_rope [B, H, rope], cache [B,
    T, C] with C >= rank + rope (the rest padding), idx/valid [B, K], pos
    [B] -> [B, H, rank] f32 (the weighted sum of the selected ``c_kv``
    rows). A row at depth 0 is a free slot: none of its rows is gathered
    and its result is finite."""
    with jax.named_scope("mla_decode_attend"):
        rows = gather_live_rows(cache, idx, pos)
        if on_tpu() and latent_attend_supported(q_abs, rows):
            return latent_attend_kernel(q_abs, q_rope, rows, valid, scale,
                                        rank)
        return _latent_attend_xla(q_abs, q_rope, rows, valid, scale, rank)


def dense_attend_block(T: int) -> int:
    """Cached positions a block of the dense attend: the kernel's, and
    the unit its visits are counted in on every backend."""
    return _block(T, DENSE_BLOCK_T)


def dense_attend_visits(pos: jax.Array, T: int, bt: int = 0) -> jax.Array:
    """Cached positions the dense attend's blocks cover in one call over
    ALL rows, as the kernel's grid visits them: a live row at depth p
    the blocks 0 .. p // block, a row at depth 0 (a free slot) none.
    ``bt``: the block of another kernel that walks the same schedule
    (``ops.hybrid_attention.gqa_attend_kernel``). pos [B] -> int32
    scalar."""
    bt = bt or dense_attend_block(T)
    pos = pos.astype(jnp.int32)
    return jnp.sum(jnp.where(pos > 0, (pos // bt + 1) * bt, 0))


def decode_attend_dense(q_abs: jax.Array, q_rope: jax.Array,
                        cache: jax.Array, pos: jax.Array, scale: float,
                        rank: int, rope: int) -> jax.Array:
    """The absorbed attend of one query a row over that row's WHOLE
    latent cache row up to its own depth, in place (no selection, no
    gather). q_abs [B, H, rank], q_rope [B, H, rope], cache [B, T, C]
    with C >= rank + rope (the rest padding), pos [B] -> [B, H, rank]
    f32: the softmax over positions ``<= pos[b]`` of ``scale (q_abs .
    c_kv + q_rope . k_r)`` times ``c_kv``. A row at depth 0 is a free
    slot (an admitted row is at least one token deep): it gives zeros,
    and on the TPU none of its cache row is read."""
    with jax.named_scope("mla_decode_attend"):
        if on_tpu() and dense_attend_supported(q_abs, cache):
            return dense_attend_kernel(q_abs, q_rope, cache, pos, scale,
                                       rank)
        return _dense_attend_xla(q_abs, q_rope, cache, pos, scale, rank)


def _dense_attend_xla(q_abs, q_rope, cache, pos, scale, rank):
    """Slot-blind: every row's scores over all ``T`` positions, masked.
    For the backends without the kernel (the CPU tests)."""
    valid = jnp.arange(cache.shape[1])[None, :] <= pos[:, None]
    out = _latent_attend_xla(q_abs, q_rope, cache, valid, scale, rank)
    return jnp.where((pos > 0)[:, None, None], out, 0.0)


def _latent_attend_xla(q_abs, q_rope, rows, valid, scale, rank):
    prec = _prec(q_abs.dtype)
    c, kr = rows[..., :rank], rows[..., rank:rank + q_rope.shape[-1]]
    s = (jnp.einsum("bhr,bkr->bhk", q_abs, c,
                    preferred_element_type=jnp.float32, precision=prec)
         + jnp.einsum("bhe,bke->bhk", q_rope, kr,
                      preferred_element_type=jnp.float32,
                      precision=prec)) * scale
    s = jnp.where(valid[:, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkr->bhr", p.astype(c.dtype), c,
                      preferred_element_type=jnp.float32, precision=prec)


def prefill_attend(qh: jax.Array, kh: jax.Array, vh: jax.Array,
                   keep: Optional[jax.Array], scale: float,
                   window: int = 0) -> jax.Array:
    """Expanded-form attention of a fresh context under the selection
    mask, online softmax by blocks with the key blocks past the diagonal
    skipped. Head-major: qh, kh [H, L, dq], vh [H, L, dv], keep [L, L]
    bool -> [H, L, dv] in qh's dtype. ``keep`` None is plain causal
    attention by block index alone: the key blocks wholly below the
    diagonal take no mask, the ones that touch it compare positions.
    ``window`` w (with ``keep`` None) is the BAND: query t sees keys ``(t
    - w, t]`` (``ops.flash_attention.window_keep``), and only the key
    blocks the band touches are fetched or computed, in blocks taken from
    the band (:func:`prefill_attend_plan`), not the causal plan's.
    On the TPU one fused kernel a call (``%mla_prefill_attend``),
    everywhere else the XLA loop. What the kernel buys (PERF.md section
    6, PR 38) is NOT a score block kept out of HBM: XLA keeps it out too,
    and alone the loop is as fast without a selection. It is 14% of the
    attend under a selection, and a time that does not hang on where the
    compiler puts a loop's carried accumulators: inside A.X-K1's prefill
    programs the first layer's loop had them in HBM at every bucket and
    took four times its siblings' time."""
    if keep is not None and window:
        raise ValueError("a selection and a window are two masks: one call "
                         "takes one")
    if _kernel_plan(qh.shape[1], qh.shape[2], vh.shape[2], qh.dtype, window):
        return prefill_attend_kernel(qh, kh, vh, keep, scale, window=window)
    return prefill_attend_xla(qh, kh, vh, keep, scale, window)


def _kernel_plan(L: int, dq: int, dv: int, dtype, window: int = 0):
    """The kernel's plan where :func:`prefill_attend` runs the kernel
    (the TPU, a shape and dtype it takes), else None."""
    return prefill_attend_plan(L, dq, dv, dtype, window) if on_tpu() \
        else None


def _xla_first_block(i, bq: int, bk: int, window: int):
    """The first key block query block ``i`` of the XLA loop sees: 0, or
    under a window the block that holds the first query's oldest key."""
    return jnp.maximum(i * bq - window + 1, 0) // bk if window else 0


def prefill_attend_describe(L: int, dq: int, dv: int, dtype,
                            window: int = 0) -> dict:
    """What a run's ``start`` record carries for one prefill bucket:
    which form :func:`prefill_attend` traces there, its blocks, and how
    many of the score square's tiles it computes (the rest lie past the
    diagonal, or under a ``window`` before the band, and are neither
    fetched nor computed). Under a window the record says so and gives
    ``keys_per_query``: the keys the computed tiles hold a query (the
    window itself would be the least)."""
    plan = _kernel_plan(L, dq, dv, dtype, window)
    if plan is not None:
        form, computed = "kernel", plan.tiles_computed
        # under a window the unit counted, a tile; else, as the record
        # has read since PR 38, the grid's blocks
        bq, bk = (plan.tile_q, plan.tile_k) if window \
            else (plan.block_q, plan.block_k)
    else:
        form = "xla"
        bq, bk = _block(L, ATTEND_BLOCK_Q), _block(L, ATTEND_BLOCK_K)
        computed = sum(
            ((i + 1) * bq + bk - 1) // bk
            - int(_xla_first_block(i, bq, bk, window))
            for i in range(L // bq))
    total = (L // bq) * (L // bk)
    out = {"form": form, "block_q": bq, "block_k": bk,
           "tiles_total": total, "tiles_computed": computed,
           "computed_share": computed / total}
    if window:
        out.update(window=window, keys_per_query=computed * bq * bk / L)
    return out


def prefill_attend_plan(L: int, dq: int, dv: int, dtype=jnp.bfloat16,
                        window: int = 0):
    """The blocks :func:`prefill_attend_kernel` walks a context of ``L``
    positions in, with the counts of the score tiles its grid computes
    (``ops.flash_attention.flash_plan``: 1,024 queries by 1,024 keys a
    grid step wherever 1,024 divides ``L``); None where the kernel does
    not take the shape or the dtype (then the XLA loop runs). Under a
    ``window`` the blocks are the same and the grid's key axis is as long
    as a band, not as the context (``ops.flash_attention._band_steps``):
    two key blocks a query block at a window of 128, where the square
    grid would execute every block of the context to compute two. Swept
    on the chip, PR 47 (PERF.md section 6; 64 heads of 128, window 128,
    device ms a call at 3,072 / 12,288 positions): blocks of 128 2.28 /
    9.19, 256 2.05 / 8.40, 512 1.71 / 7.19, 1,024 (these) 1.42 / 6.39,
    beside the causal call's 1.76 / 21.64: a grid step costs a
    microsecond whatever it computes, so fewer, larger steps win although
    they compute more of what the mask then drops."""
    from tensorflow_distributed_tpu.ops.flash_attention import flash_plan
    if jnp.dtype(dtype) != jnp.bfloat16:
        return None
    return flash_plan(L, L, dq, dtype, causal=True, window=window, Dv=dv)


def prefill_attend_kernel(qh: jax.Array, kh: jax.Array, vh: jax.Array,
                          keep: Optional[jax.Array], scale: float,
                          interpret: bool = False, window: int = 0
                          ) -> jax.Array:
    """:func:`prefill_attend` as ONE call of the flash forward kernel
    (``ops/flash_attention.py::_fwd``: heads on the grid's leading axis,
    K / V blocks streamed under running statistics in VMEM, blocks past
    the diagonal neither fetched nor computed), the selection an int8
    tile beside each K / V block. The arithmetic is the XLA loop's:
    bfloat16 operands, float32 products and statistics, probabilities
    cast to the values' dtype, the division by the row sum last."""
    from tensorflow_distributed_tpu.ops.flash_attention import _fwd
    plan = prefill_attend_plan(qh.shape[1], qh.shape[2], vh.shape[2],
                               qh.dtype, window)
    return _fwd(qh, kh, vh, causal=True, plan=plan, interpret=interpret,
                window=window,
                keep=None if keep is None else keep.astype(jnp.int8),
                scale=float(scale), stats=False,
                name="mla_prefill_attend")[0]


def prefill_attend_xla(qh: jax.Array, kh: jax.Array, vh: jax.Array,
                       keep: Optional[jax.Array], scale: float,
                       window: int = 0) -> jax.Array:
    """:func:`prefill_attend` as a ``lax.map`` over query blocks around
    a ``fori_loop`` over key blocks of 512, for the backends without the
    kernel; under a ``window`` the loop starts at the band's first block.
    (On the chip, alone, this loop runs at 42-53% of the MXU's peak: no
    score block of its turns goes through HBM.)"""
    H, L, dq = qh.shape
    dv = vh.shape[-1]
    bq, bk = _block(L, ATTEND_BLOCK_Q), _block(L, ATTEND_BLOCK_K)
    prec = _prec(qh.dtype)

    def q_block(i):
        qi = jax.lax.dynamic_slice_in_dim(qh, i * bq, bq, axis=1)

        def k_step(j, carry, masked=True):
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(kh, j * bk, bk, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(vh, j * bk, bk, axis=1)
            s = jnp.einsum("hqd,hkd->hqk", qi, kj,
                           preferred_element_type=jnp.float32,
                           precision=prec) * scale
            if not masked:
                kp = None
            elif keep is None:
                cols = (j * bk + jnp.arange(bk))[None, :]
                rows = (i * bq + jnp.arange(bq))[:, None]
                kp = cols <= rows
                if window:
                    kp = kp & (cols > rows - window)
            else:
                kp = jax.lax.dynamic_slice(keep, (i * bq, j * bk),
                                           (bq, bk))
            if kp is not None:
                s = jnp.where(kp[None], s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            if kp is not None:
                p = jnp.where(kp[None], p, 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "hqk,hkd->hqd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32, precision=prec)
            return m_new, l, acc

        init = (jnp.full((H, bq), NEG, jnp.float32),
                jnp.zeros((H, bq), jnp.float32),
                jnp.zeros((H, bq, dv), jnp.float32))
        n_k = ((i + 1) * bq + bk - 1) // bk      # up to the diagonal
        n_free = _xla_first_block(i, bq, bk, window)
        if keep is None and not window:
            n_free = (i * bq + 1) // bk          # wholly at or below row 0
            init = jax.lax.fori_loop(
                0, n_free, functools.partial(k_step, masked=False), init)
        _, l, acc = jax.lax.fori_loop(n_free, n_k, k_step, init)
        return (acc / l[..., None]).astype(qh.dtype)

    with jax.named_scope("mla_prefill_attend"):
        out = jax.lax.map(q_block, jnp.arange(L // bq))       # [nq,H,bq,dv]
        return out.transpose(1, 0, 2, 3).reshape(H, L, dv)


# -- the held experts --------------------------------------------------------

#: Below this many tokens the (token, expert) pairs are moved by one-hot
#: matmuls (exact, and a gather of a few hundred rows is a loop on the
#: TPU); above, by row gathers.
ONE_HOT_TOKENS = 256
#: A block has room for routing whose ODDS of landing here are this many
#: times the configuration's (share s: the part h s / (1 - s + h s) of
#: all pairs): such a prefill is still one trip.
BLOCK_HEADROOM = 2.0
#: The most rows of a block, whatever the bucket: its [rows, D] float32
#: result and its gathered rows are temporaries of the prefill program
#: (tests/test_tpu_compile.py holds the largest buckets' plans).
MAX_BLOCK_ROWS = 8192
#: Row tiles of the grouped matmul, and the expected rows an expert gets
#: from which each is taken. A tile that two experts' rows share is
#: computed once for each, and an expert's weights are read once a tile
#: its rows touch: 256 rows where an expert fills a quarter of them, 128
#: below (a decode step, the shortest bucket). Swept on the chip, PR 42:
#: 512 is never the fastest, not at a thousand rows an expert either.
ROW_TILES = ((256, 64), (128, 0))
#: Numbers of one weight block [tk, tn] of the grouped matmul (3 MiB of
#: bfloat16: two of them, the row tile's [tm, tk] and the float32 [tm,
#: tn] accumulator and result stay under the kernel's 16 MiB of VMEM),
#: and the widest N tile.
WEIGHT_BLOCK = 3 * 512 * 1024
WIDEST_TILE_N = 2048
#: Numbers of the ``[N, td]`` float32 tile of the result that
#: ``moe_combine_held`` keeps in VMEM while it walks a block's rows (32
#: MiB; the pipeline holds two of them, half of a v5e's 128 MiB): the D
#: tile is as wide as that lets it be, since a row's add costs 13-17
#: cycles however few lanes it covers. Swept on the chip, PR 44: 512
#: lanes for 256 take GLM's 14,336 bucket from 2.64 to 1.84 ms a layer.
#: Past 2,048 lanes the add is bound by its 16 loads and stores a
#: thousand lanes (27 ns a row of 4,096 against 2 x 14.5), and a single
#: D tile has no next tile to hide its write-back under.
COMBINE_TILE = 8 * 1024 * 1024
COMBINE_LANES = 2048


class MoePlan(NamedTuple):
    """How :func:`held_experts` takes the (token, expert) pairs of ``N``
    tokens: all static, from the shapes of the call."""
    one_hot: bool            # pairs moved by one-hot matmuls, not gathers
    block_rows: int          # rows of a block of sorted pairs
    max_trips: int           # trips if EVERY pair landed here
    expected_trips: int      # trips the configuration's share takes
    tiles_in: Tuple[int, int, int]    # (tm, tk, tn) of gate and up
    tiles_out: Tuple[int, int, int]   # (tm, tk, tn) of down
    combine_tile: int        # lanes of the result the combine holds


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap`` (no masked part tile); where there is none, ``cap`` or the
    one tile that covers ``dim`` (megablox masks the rest)."""
    for t in range(min(cap, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return min(cap, -(-dim // 128) * 128)


def gmm_tiles(tm: int, K: int, N: int) -> Tuple[int, int, int]:
    """Tiles of one grouped matmul ``[*, K] x [E, K, N]`` under the row
    tile ``tm``, a function of (K, N): tiles that divide the expert, the
    N tile up to 1,024 first, then as much of K as :data:`WEIGHT_BLOCK`
    holds, and where that is all of K the N tile widened to fill it."""
    tn = _tile(N, 1024)
    tk = _tile(K, WEIGHT_BLOCK // tn)
    if tk == K:
        tn = _tile(N, min(WEIGHT_BLOCK // tk, WIDEST_TILE_N))
    return tm, tk, tn


def moe_plan(N: int, k: int, E: int, D: int, F: int, share: float
             ) -> MoePlan:
    """The plan of :func:`held_experts` for ``N`` tokens of ``k`` picked
    experts each, ``E`` experts ``[D, F]`` held here, and ``share`` of a
    token's pairs expected to land on them (held over routed experts).

    Row tile from the rows an expert gets (``N k share / E``,
    :data:`ROW_TILES`); block rows from the pairs that land, with
    :data:`BLOCK_HEADROOM`, so the usual prefill is one trip; K and N
    tiles from ``D`` and ``F`` (:func:`gmm_tiles`); the combine's D tile
    from ``N`` and ``D`` (:data:`COMBINE_TILE`, :data:`COMBINE_LANES`)."""
    P = N * k
    tm = next(t for t, rows in ROW_TILES if P * share / E >= rows)
    one_hot = N <= ONE_HOT_TOKENS
    if one_hot:
        # every pair in ONE block of whole row tiles (megablox takes no
        # other: 96 slots x 22 picked are 16.5 tiles of 128); the rows past
        # the pairs belong to no group
        tm = tm if P % tm == 0 else 128
        M = -(-P // tm) * tm
    else:
        room = BLOCK_HEADROOM * share / (1 - share + BLOCK_HEADROOM * share)
        M = min(math.ceil(math.floor(P * room) / tm) * tm, MAX_BLOCK_ROWS)
    td = D if D % 128 else _tile(
        D, min(COMBINE_LANES, max(128, COMBINE_TILE // N)))
    return MoePlan(one_hot, M, -(-P // M),
                   max(1, math.ceil(math.floor(P * share) / M)),
                   gmm_tiles(tm, D, F), gmm_tiles(tm, F, D), td)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   tiles: Tuple[int, int, int], kernel: bool) -> jax.Array:
    """``lhs[rows of group e] @ rhs[e]`` for consecutive row groups
    (rows past the last group are unspecified). lhs [M, K], rhs [E, K,
    N], group_sizes [E] int32 -> [M, N] f32. ``kernel``: megablox under
    its (tm, tk, tn) ``tiles`` (:func:`gmm_tiles`; the TPU), else XLA's
    ragged dot. megablox takes whole row tiles of bfloat16 only:
    :func:`moe_plan` gives every block whole tiles."""
    if kernel and lhs.dtype == jnp.bfloat16:
        if lhs.shape[0] % tiles[0]:
            raise ValueError(
                f"{lhs.shape[0]} rows are not whole row tiles of "
                f"{tiles[0]}: the grouped matmul would leave megablox")
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        return gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                   preferred_element_type=jnp.float32, tiling=tiles)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              precision=_prec(lhs.dtype),
                              preferred_element_type=jnp.float32)


def held_experts(xs: jax.Array, local: jax.Array, weights: jax.Array,
                 gate: Optional[jax.Array], up: jax.Array, down: jax.Array,
                 dtype, share: float, kernel: Optional[bool] = None,
                 act: Callable[[jax.Array], jax.Array] = jax.nn.silu
                 ) -> jax.Array:
    """The held experts' part of a routed layer, DROPLESS: every (token,
    expert) pair whose expert is held here is computed, whatever the
    routing. xs [N, D]; local [N, k] the pair's index among the held
    experts or -1; weights [N, k] f32; gate/up [E, D, F], down [E, F,
    D]; ``share`` the part of a token's pairs the configuration expects
    here (held over routed experts); ``kernel`` whether the grouped
    matmuls are megablox's and the gathered branch's combine the kernel
    ``moe_combine_held`` (None: on the TPU) -> [N, D] f32.

    An expert is ``down(act(gate x) * (up x))``, three grouped matmuls a
    block, or with ``gate`` None the UNGATED ``down(act(up x))``, two
    (nemotron_h: ``act`` the squared ReLU); ``act`` works on float32.

    The pairs are sorted by expert (absent ones last) and taken in
    blocks of ``M`` rows (:func:`moe_plan`): one block when ``M`` covers
    every pair (a decode step, a one-hot prefill), else a loop whose
    trip count is the number of blocks the held pairs fill: one trip
    while the odds of a pair's landing here are no more than
    :data:`BLOCK_HEADROOM` times the configuration's, every pair only
    under a routing that sends everything here."""
    N, D = xs.shape
    k = local.shape[1]
    E, _, F = up.shape
    P = N * k
    plan = moe_plan(N, k, E, D, F, share)
    kernel = on_tpu() if kernel is None else kernel
    small, M, n_blocks = plan.one_hot, plan.block_rows, plan.max_trips
    flat_e = jnp.where(local >= 0, local, E).reshape(P)
    w_held = jnp.where(local >= 0, weights, 0.0)
    if small:
        order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
        rank = jnp.argsort(order).astype(jnp.int32).reshape(N, k)
    else:
        # a pair's weight rides the sort to its row
        _, order, w_row = jax.lax.sort(
            (flat_e, jnp.arange(P, dtype=jnp.int32), w_held.reshape(P)),
            num_keys=1, is_stable=True)
        w_row = jnp.pad(w_row, (0, n_blocks * M - P))
    counts = jnp.sum(flat_e[:, None] == jnp.arange(E)[None, :], axis=0)
    ends = jnp.cumsum(counts)
    starts, n_held = ends - counts, ends[-1]
    tok = jnp.pad(order // k, (0, n_blocks * M - P))
    xd = xs.astype(dtype)
    if small:
        # The one-hot moves are matmuls over ALL rows: a non-finite row
        # (a poisoned slot) must not reach its neighbours through 0 * NaN.
        # Its own result stays non-finite through its weights.
        xd = jnp.where(jnp.isfinite(xd), xd, 0)

    def block(b, y):
        lo = b * M
        tok_b = jax.lax.dynamic_slice_in_dim(tok, lo, M)
        sizes = jnp.clip(ends - lo, 0, M) - jnp.clip(starts - lo, 0, M)
        if small:
            live = lo + jnp.arange(M) < n_held
            put = ((tok_b[:, None] == jnp.arange(N)[None, :])
                   & live[:, None]).astype(dtype)
            rows = jnp.einsum("mn,nd->md", put, xd,
                              precision=_prec(dtype)).astype(dtype)
        else:
            rows = xd[tok_b]
        def into(w):                     # rows @ w[e], by expert
            return grouped_matmul(rows, w.astype(dtype), sizes,
                                  plan.tiles_in, kernel)

        with jax.named_scope("moe_held_experts"):
            h = act(into(up)) if gate is None \
                else act(into(gate)) * into(up)
            out = grouped_matmul(h.astype(dtype), down.astype(dtype), sizes,
                                 plan.tiles_out, kernel)
        if not small:
            # Rows past the held pairs are whatever the grouped matmul
            # left there (the held pairs sort first): the combine stops
            # before them.
            return combine_held(
                y, out, tok_b, jax.lax.dynamic_slice_in_dim(w_row, lo, M),
                jnp.clip(n_held - lo, 0, M), plan.tiles_in[0],
                plan.combine_tile, kernel)
        at = rank - lo                                        # [N, k]
        here = (at >= 0) & (at < jnp.minimum(M, n_held - lo))
        # the move back is a matmul over every row: those are zeroed
        out = jnp.where(live[:, None], out, 0.0)
        back = jnp.sum(
            jnp.where(here[..., None] & (at[..., None] == jnp.arange(M)),
                      w_held[..., None], 0.0), axis=1)        # [N, M]
        return y + jnp.einsum("nm,md->nd", back, out,
                              precision=jax.lax.Precision.HIGHEST)

    y0 = jnp.zeros((N, D), jnp.float32)
    if n_blocks == 1:
        return block(0, y0)
    return jax.lax.fori_loop(0, -(-n_held // M), block, y0)


_held_experts_jit = jax.jit(
    held_experts, static_argnames=("dtype", "share", "kernel", "act"))


def held_experts_once(xs, local, weights, gate, up, down, dtype, share,
                      act=jax.nn.silu):
    """:func:`held_experts` traced ONCE a shape (and backend): the expert
    layers of a model all call it with the same shapes, and tracing its
    sorts and gathers again for each layer was a second of Python a
    program at set-up."""
    return _held_experts_jit(xs, local, weights, gate, up, down, dtype,
                             share, on_tpu(), act)


def combine_held(y: jax.Array, out: jax.Array, tok: jax.Array,
                 w: jax.Array, n_rows: jax.Array, tr: int, td: int,
                 kernel: bool) -> jax.Array:
    """``y[tok[r]] += w[r] * out[r]`` for the first ``n_rows`` rows of one
    block of expert-sorted pairs, in ROW ORDER (a token's at most ``k``
    float32 additions come in the order of its rows, run after run). y
    [N, D] f32; out [M, D] f32, its rows from ``n_rows`` on unspecified
    (maybe non-finite): they are never read into a sum; tok [M] int32; w
    [M] f32. ``kernel``: :func:`combine_held_kernel` under its tiles
    (the TPU), else a scatter-add whose dead rows are SELECTED to zero."""
    if kernel:
        return combine_held_kernel(y, out, tok, w, n_rows, tr, td)
    live = jnp.arange(out.shape[0]) < n_rows
    return y.at[tok].add(jnp.where(live[:, None], w[:, None] * out, 0.0))


# -- Pallas kernels (TPU) ----------------------------------------------------

def _sublanes(dtype) -> int:
    """Rows of one (sublane, lane) tile: 16 of bfloat16, 8 of float32."""
    return 16 if jnp.dtype(dtype).itemsize == 2 else 8


def row_write_supported(buf) -> bool:
    """A lane-aligned row-major leaf of whole sublane tiles."""
    return (buf.ndim == 3 and buf.shape[2] % 128 == 0
            and buf.shape[1] % _sublanes(buf.dtype) == 0
            and buf.dtype in (jnp.bfloat16, jnp.float32))


def _row_write_body(pos_ref, new_ref, buf_ref, out_ref, *, rows):
    at = pos_ref[pl.program_id(0)] % rows
    row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jnp.where(row == at,
                             jnp.broadcast_to(new_ref[...], out_ref.shape),
                             buf_ref[...])


def row_write_kernel(buf: jax.Array, new: jax.Array, start: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """``buf`` [B, T, C] with ``new`` [B, 1, C] written at ``(b,
    start[b])``, in place: for each row only the one sublane tile that
    holds the position is read, patched and written back through
    ``input_output_aliases``."""
    B, T, C = buf.shape
    rows = _sublanes(buf.dtype)
    start = jnp.clip(start.astype(jnp.int32), 0, T - 1)
    block = pl.BlockSpec((1, rows, C), lambda b, pos: (b, pos[b] // rows, 0))
    return pl.pallas_call(
        functools.partial(_row_write_body, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[pl.BlockSpec((1, 1, C), lambda b, pos: (b, 0, 0)),
                      block],
            out_specs=block),
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        input_output_aliases={2: 0},      # operand 0 is the prefetched start
        interpret=interpret, name="latent_row_write",
    )(start, new.astype(buf.dtype), buf)


def _combine_body(n_ref, tok_ref, w_ref, out_ref, y_hbm, y_ref, sem, *,
                  tr, td):
    d, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        lanes = pl.ds(pl.multiple_of(d * td, td), td)
        copy = pltpu.make_async_copy(y_hbm.at[:, lanes], y_ref, sem)
        copy.start()
        copy.wait()

    base = i * tr

    def row(r, _):
        t = tok_ref[base + r]
        y_ref[pl.ds(t, 1), :] += w_ref[base + r] * out_ref[pl.ds(r, 1), :]
        return 0

    jax.lax.fori_loop(0, jnp.clip(n_ref[0] - base, 0, tr), row, 0)


def combine_held_kernel(y: jax.Array, out: jax.Array, tok: jax.Array,
                        w: jax.Array, n_rows: jax.Array, tr: int, td: int,
                        interpret: Optional[bool] = None) -> jax.Array:
    """:func:`combine_held` on the TPU, ``moe_combine_held``: grid (D
    tiles of ``td`` lanes, row tiles of ``tr``). A ``[N, td]`` tile of
    ``y`` stays in VMEM while the block's rows pass under it in whole
    ``[tr, td]`` tiles (contiguous reads of ``out``); each live row is
    added to its token's row at a dynamic sublane index, its token and
    weight scalars prefetched to SMEM. Row tiles past ``n_rows`` are
    neither fetched (the index map stays on the last live tile) nor
    walked, so the work follows the HELD pairs (``serve_summary``'s
    ``moe_held_pairs`` over ``moe_pairs_routed``, the benchmark's
    ``serve.moe_held_pair_share``), not the ``N k`` slots. ``y`` is
    updated in place (``input_output_aliases``) and so carried across
    the blocks' trips. ``interpret`` None: off the TPU (the tests)."""
    N, D = y.shape
    M = out.shape[0]
    assert M % tr == 0 and D % td == 0, (M, tr, D, td)
    interpret = not on_tpu() if interpret is None else interpret

    def rows_at(d, i, n, tok, w):
        return jnp.minimum(i, jnp.maximum(pl.cdiv(n[0], tr) - 1, 0)), d

    need = 2 * (N + tr) * td * 4 + (4 << 20)
    return pl.pallas_call(
        functools.partial(_combine_body, tr=tr, td=td),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(D // td, M // tr),
            in_specs=[pl.BlockSpec((tr, td), rows_at),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((N, td), lambda d, i, *_: (0, d)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(y.shape, jnp.float32),
        input_output_aliases={4: 0},      # operands 0-2 are prefetched
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(need, 16 << 20)),
        interpret=interpret, name="moe_combine_held",
    )(jnp.reshape(n_rows, (1,)).astype(jnp.int32), tok.astype(jnp.int32),
      w.astype(jnp.float32), out, y)


INDEX_BLOCK_T = 2048


def index_scores_supported(q, keys) -> bool:
    """Shapes the decode index-score kernel takes: bfloat16, whole
    blocks of cached positions, lane-wide index heads."""
    T, dh = keys.shape[1], keys.shape[2]
    return (q.dtype == jnp.bfloat16 and keys.dtype == jnp.bfloat16
            and dh % 128 == 0 and T % 128 == 0
            and q.shape[1] % 8 == 0)


def _index_scores_body(pos_ref, q_ref, w_ref, k_ref, out_ref, *, bt):
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(j * bt <= pos)
    def _():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [nh, bt]
        score = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0,
                        keepdims=True)                        # [1, bt]
        col = j * bt + jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
        out_ref[0] = jnp.where(col <= pos, score, -jnp.inf)

    @pl.when(j * bt > pos)
    def _():
        out_ref[0] = jnp.full(out_ref.shape[1:], -jnp.inf, jnp.float32)


def index_scores_kernel(q: jax.Array, w: jax.Array, keys: jax.Array,
                        pos: jax.Array, interpret: bool = False
                        ) -> jax.Array:
    """``sum_j w[b, j] relu(q[b, j] . keys[b, t])`` for ``t <= pos[b]``,
    ``-inf`` past it. q [B, nh, dh], w [B, nh] f32, keys [B, T, dh],
    pos [B] -> [B, T] f32. Blocks of keys wholly past a row's depth are
    neither read (the index map stays on the last block that is not)
    nor computed."""
    B, T, dh = keys.shape
    nh = q.shape[1]
    bt = _block(T, INDEX_BLOCK_T)
    out = pl.pallas_call(
        functools.partial(_index_scores_body, bt=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, T // bt),
            in_specs=[
                pl.BlockSpec((1, nh, dh), lambda b, j, pos: (b, 0, 0)),
                pl.BlockSpec((1, nh, 1), lambda b, j, pos: (b, 0, 0)),
                pl.BlockSpec((1, bt, dh), lambda b, j, pos: (
                    b, jnp.minimum(j, pos[b] // bt), 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bt), lambda b, j, pos: (b, 0, j))),
        out_shape=jax.ShapeDtypeStruct((B, 1, T), jnp.float32),
        interpret=interpret, name="dsa_index_scores",
    )(pos.astype(jnp.int32), q, w.astype(jnp.float32)[..., None], keys)
    return out[:, 0]


def latent_attend_supported(q_abs, rows) -> bool:
    """bfloat16, a lane-wide latent rank, whole sublane tiles of heads
    and keys; the gathered rows of one slot (double-buffered) must sit
    well inside the 16 MiB of scoped VMEM."""
    B, K, C = rows.shape
    H, r = q_abs.shape[1], q_abs.shape[2]
    return (q_abs.dtype == jnp.bfloat16 and rows.dtype == jnp.bfloat16
            and r % 128 == 0 and K % 128 == 0 and H % 8 == 0
            and K * (-(-C // 128) * 128) * 2 <= 3 * 1024 * 1024)


def _latent_attend_body(qa_ref, qr_ref, rows_ref, bias_ref, out_ref, *,
                        scale, rank):
    rows = rows_ref[0]                                        # [K, C]
    c, kr = rows[:, :rank], rows[:, rank:rank + qr_ref.shape[-1]]
    nt = (((1,), (1,)), ((), ()))
    s = (jax.lax.dot_general(qa_ref[0], c, nt,
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qr_ref[0], kr, nt,
                               preferred_element_type=jnp.float32))
    s = s * scale + bias_ref[0]                               # [H, K]
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.dot(p.astype(c.dtype), c, preferred_element_type=jnp.float32)
    out_ref[0] = o / l


def latent_attend_kernel(q_abs: jax.Array, q_rope: jax.Array,
                         rows: jax.Array, valid: jax.Array, scale: float,
                         rank: int, interpret: bool = False) -> jax.Array:
    """Softmax attention of one query a slot (every head) over that
    slot's gathered latent rows. q_abs [B, H, rank], q_rope [B, H, dr],
    rows [B, K, C >= rank + dr], valid [B, K] -> [B, H, rank] f32."""
    B, K, C = rows.shape
    H = q_abs.shape[1]
    bias = jnp.where(valid, 0.0, NEG).astype(jnp.float32)[:, None, :]
    return pl.pallas_call(
        functools.partial(_latent_attend_body, scale=scale, rank=rank),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, rank), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, H, q_rope.shape[-1]), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, K, C), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, K), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, rank), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), jnp.float32),
        interpret=interpret, name="mla_latent_attend",
    )(q_abs, q_rope, rows, bias)


#: Cached positions a block of the dense attend (1,024 x 640 bfloat16 is
#: 1.3 MB in VMEM, twice for the double buffer).
DENSE_BLOCK_T = 1024


def dense_attend_supported(q_abs, cache) -> bool:
    """bfloat16, a lane-wide latent rank and cache row, whole sublane
    tiles of heads, whole blocks of positions."""
    T, C = cache.shape[1], cache.shape[2]
    H, r = q_abs.shape[1], q_abs.shape[2]
    return (q_abs.dtype == jnp.bfloat16 and cache.dtype == jnp.bfloat16
            and r % 128 == 0 and C % 128 == 0 and H % 8 == 0
            and dense_attend_block(T) % 128 == 0)


def dense_attend_schedule(pos: jax.Array, bt: int):
    """Which cache block each grid step (b, j) of the dense attend holds:
    block ``clip(j, lo[b], hi[b])`` of row ``row[b]``. A live row walks
    its own blocks 0 .. pos // bt and then stays on the last; a free row
    stays on the block the row before it ended on (the next live row's
    first block where none came before), so it moves nothing."""
    pos = pos.astype(jnp.int32)
    live = pos > 0
    at = jnp.arange(pos.shape[0], dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, at, -1))          # last live <= b
    first = jnp.argmax(live).astype(jnp.int32)                # 0 if none
    row = jnp.where(before >= 0, before, first)
    last = pos[row] // bt
    hi = jnp.where(before >= 0, last, 0)
    lo = jnp.where(live, 0, hi)
    return row, lo, hi


def _dense_attend_body(pos_ref, row_ref, lo_ref, hi_ref, qa_ref, qr_ref,
                       c_ref, out_ref, m_ref, l_ref, acc_ref, *, scale,
                       rank, bt):
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when((pos > 0) & (j * bt <= pos))
    def _():
        rows = c_ref[0]                                       # [bt, C]
        c, kr = rows[:, :rank], rows[:, rank:rank + qr_ref.shape[-1]]
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qa_ref[0], c, nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], kr, nt,
                                   preferred_element_type=jnp.float32))
        col = j * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col <= pos, s * scale, NEG)             # [H, bt]
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_ref[...] - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def dense_attend_kernel(q_abs: jax.Array, q_rope: jax.Array,
                        cache: jax.Array, pos: jax.Array, scale: float,
                        rank: int, interpret: bool = False) -> jax.Array:
    """Online-softmax attention of one query a row (every head) over
    that row's cache row, read in place in blocks of positions. q_abs
    [B, H, rank], q_rope [B, H, dr], cache [B, T, C >= rank + dr], pos
    [B] -> [B, H, rank] f32. Blocks past a row's depth and every block of
    a row at depth 0 are neither read (:func:`dense_attend_schedule`
    keeps the grid on the block it already holds) nor computed."""
    B, T, C = cache.shape
    H, dr = q_abs.shape[1], q_rope.shape[-1]
    bt = dense_attend_block(T)
    pos = pos.astype(jnp.int32)
    row, lo, hi = dense_attend_schedule(pos, bt)
    return pl.pallas_call(
        functools.partial(_dense_attend_body, scale=scale, rank=rank,
                          bt=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B, T // bt),
            in_specs=[
                pl.BlockSpec((1, H, rank), lambda b, j, *_: (b, 0, 0)),
                pl.BlockSpec((1, H, dr), lambda b, j, *_: (b, 0, 0)),
                pl.BlockSpec((1, bt, C), lambda b, j, pos, row, lo, hi: (
                    row[b], jnp.clip(j, lo[b], hi[b]), 0)),
            ],
            out_specs=pl.BlockSpec((1, H, rank), lambda b, j, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="mla_latent_attend_dense",
    )(pos, row, lo, hi, q_abs, q_rope, cache)
