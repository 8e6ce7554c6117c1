"""The device side of the ``minicpm_sala`` family (models/minicpm_sala.py):
the two mixers a hybrid of linear and block-sparse attention is made of.

- **Lightning (linear) attention.** A head keeps a FIXED-size float32 state
  ``S`` (``[d, d]``) and no per-position cache: ``S_t = lambda S_{t-1} +
  k_t^T v_t``, ``o_t = q_t S_t``. The prefill computes it in chunks
  (:func:`lightning_chunk_scan`: inside a chunk ``((Q K^T) * D) V`` with
  ``D[i, j] = lambda^(i-j)``, across chunks through ``S``) and returns the
  state AT ``true_len``, not at the end of the padded bucket; a decode
  step moves the state of the LIVE rows only, in place
  (:func:`lightning_state_step`).
- **Block-sparse grouped-query attention (InfLLM-v2).** Past ``dense_len``
  a query keeps ``topk`` blocks of ``block_size`` keys a key-value group,
  chosen from mean-pooled keys (:func:`sparse_block_scores`,
  :func:`block_scores`, :func:`decode_selection` /
  :func:`prefill_selection`); a decode step attends the kept blocks read
  IN PLACE from the cache (:func:`sparse_block_attend`), the prefill
  attends by tiles under the selection's block mask
  (:func:`sparse_prefill_attend`, a blocked XLA loop on every backend:
  the masked triangle on full MXU tiles).

Everything has an XLA form that runs anywhere (the CPU tests compare it
with the plain reference). On the TPU four parts are NAMED Pallas kernels,
so that a profiler capture can tell them apart: ``lightning_chunk_scan``,
``lightning_state_step``, ``sparse_block_scores``, ``sparse_block_attend``.
The decode kernels walk the live slots only
(:func:`live_schedule`): a free slot costs a grid step that moves and
computes nothing.

Beside them, for any grouped-query attention layer that keeps ``[B, T, 2 G
d]`` rows of K and V (models/granitemoehybrid.py's mixer, whichever family
runs it): :func:`dense_decode_attend`, slot-blind XLA over every slot's
first ``limit`` positions, and :func:`gqa_decode_attend`, on the TPU the
named kernel ``gqa_dense_attend`` over the LIVE rows' blocks up to each
row's depth (every family's ``kv`` leaf: granitemoehybrid's 4,096 deep,
nemotron_h's 6,144, exaone_moe's full layers' 16,384), and
:func:`ring_rows`, what a prefill leaves in a ring of the last ``W`` rows.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflow_distributed_tpu.ops.latent_attention import (
    NEG, _block, _prec, _sort_key, dense_attend_schedule,
    dense_attend_visits, kth_largest_key, live_slots, on_tpu)

HI = jax.lax.Precision.HIGHEST
#: Tokens a chunk of the lightning prefill (the intra-chunk product is
#: ``[C, C]``, the carried state ``[d, d]``).
SCAN_CHUNK = 256
#: Queries a block of the prefill's selection, positions a tile of its
#: attend.
SELECT_BLOCK = 256
ATTEND_TILE = 512


@dataclasses.dataclass(frozen=True)
class SparseConfig:
    """The source's ``sparse_config``: pooled windows of ``kernel_size``
    keys every ``kernel_stride``, blocks of ``block_size`` positions of
    which ``topk`` are kept (``init_blocks`` leading ones and the
    ``window_size / block_size`` ending at the query's own forced), past
    a context of ``dense_len``."""
    kernel_size: int
    kernel_stride: int
    init_blocks: int
    block_size: int
    window_size: int
    topk: int
    dense_len: int

    def __post_init__(self):
        if self.block_size % self.kernel_stride or \
                self.kernel_size % self.kernel_stride:
            raise ValueError("block_size and kernel_size are multiples of "
                             "kernel_stride")
        if self.init_blocks + self.window_size // self.block_size \
                > self.topk:
            raise ValueError("the forced blocks exceed topk")

    @property
    def local_blocks(self) -> int:
        return self.window_size // self.block_size

    def windows_seen(self, pos: jax.Array) -> jax.Array:
        """Complete pooled windows that end at or before ``pos``."""
        return jnp.maximum(
            (pos - (self.kernel_size - 1)) // self.kernel_stride + 1, 0)

    def pooled_len(self, max_len: int) -> int:
        """Rows of a slot's pooled-key leaf: one a ``kernel_stride``
        positions, in whole lane tiles (the scores' minor dimension)."""
        return -(-(-(-max_len // self.kernel_stride)) // 128) * 128

    def kept_positions(self, pos: jax.Array) -> jax.Array:
        """Positions a query at ``pos`` attends, a key-value group: its
        whole context up to ``dense_len``, else ``topk`` blocks less what
        of its own block lies ahead of it."""
        bs = self.block_size
        return jnp.where(pos + 1 <= self.dense_len, pos + 1,
                         self.topk * bs - (bs - 1 - pos % bs))


def decay_slopes(n_heads: int) -> jax.Array:
    """``s_h = 2^(-8 h / H)``, ``h = 1..H``: a head's fixed decay is
    ``lambda_h = exp(-s_h)`` a token."""
    return jnp.asarray([2.0 ** (-8.0 * h / n_heads)
                        for h in range(1, n_heads + 1)], jnp.float32)


def live_schedule(pos: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Which slot each grid step of a decode kernel works on: step i the
    i-th live slot (depth above 0) while there is one, then the last of
    them again (``active`` 0: nothing moves, nothing is computed). With no
    live slot step 0 works on slot 0, so that every block a kernel leaves
    in its output was written. pos [B] -> (row [B], active [B] int32,
    n_live scalar)."""
    order, n_live = live_slots(pos)
    at = jnp.arange(pos.shape[0], dtype=jnp.int32)
    n_sched = jnp.maximum(n_live, 1)
    return (order[jnp.minimum(at, n_sched - 1)],
            (at < n_sched).astype(jnp.int32), n_live)


# -- lightning attention ------------------------------------------------------

def lightning_chunk_scan(q: jax.Array, k: jax.Array, v: jax.Array,
                         slopes: jax.Array,
                         true_len: Optional[jax.Array] = None,
                         interpret: Optional[bool] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """Causal linear attention of fresh contexts, in chunks. q, k, v [B,
    L, H, d]; slopes [H]; true_len [B] or a scalar (None: ``L``) ->
    (``o`` [B, L, H, d] f32 with ``o_t = q_t S_t``, ``S`` [B, H, d, d] f32
    the state after token ``true_len - 1``). Tokens at or past
    ``true_len`` (a bucket's padding) leave no trace in ``S`` and do not
    decay it; their own ``o`` is finite and means nothing."""
    B, L, H, d = q.shape
    if true_len is None:
        true_len = L
    true_len = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32), (B,))
    C = _block(L, SCAN_CHUNK)
    kernel = (interpret is not None or on_tpu()) and \
        chunk_scan_supported(q, C)
    with jax.named_scope("lightning_prefill_scan"):
        if kernel:
            return chunk_scan_kernel(q, k, v, slopes, true_len, C,
                                     interpret=bool(interpret))
        return _chunk_scan_xla(q, k, v, slopes, true_len, C)


def _chunk_scan_xla(q, k, v, slopes, true_len, C):
    B, L, H, d = q.shape
    prec = _prec(q.dtype)
    dt = q.dtype
    s = slopes.astype(jnp.float32)[None, :, None, None]       # [1,H,1,1]
    i = jnp.arange(C, dtype=jnp.float32)
    diff = i[:, None] - i[None, :]
    D = jnp.where(diff >= 0, jnp.exp(-s * jnp.maximum(diff, 0.0)), 0.0)
    qdec = jnp.exp(-s[..., 0] * (i + 1.0))                    # [1,H,C]

    def chunks(x):                       # [B,L,H,d] -> [n,B,H,C,d]
        return x.reshape(B, L // C, C, H, d).transpose(1, 0, 3, 2, 4)

    def chunk(S, xs):
        c, qc, kc, vc = xs
        n = jnp.clip(true_len - c * C, 0, C).astype(jnp.float32)
        a = jnp.einsum("bhid,bhjd->bhij", qc, kc, precision=prec,
                       preferred_element_type=jnp.float32) * D
        o = jnp.einsum("bhij,bhjd->bhid", a.astype(dt), vc, precision=prec,
                       preferred_element_type=jnp.float32)
        o = o + qdec[..., None] * jnp.einsum(
            "bhid,bhde->bhie", qc, S.astype(dt), precision=prec,
            preferred_element_type=jnp.float32)
        left = n[:, None, None] - 1.0 - i[None, None, :]      # [B,1,C]
        w = jnp.where(left >= 0, jnp.exp(-s[..., 0]
                                         * jnp.maximum(left, 0.0)), 0.0)
        kw = (kc.astype(jnp.float32) * w[..., None]).astype(dt)
        S = jnp.exp(-s * n[:, None, None, None]) * S + jnp.einsum(
            "bhjd,bhje->bhde", kw, vc, precision=prec,
            preferred_element_type=jnp.float32)
        return S, o

    S, o = jax.lax.scan(
        chunk, jnp.zeros((B, H, d, d), jnp.float32),
        (jnp.arange(L // C), chunks(q), chunks(k), chunks(v)))
    return o.transpose(1, 0, 3, 2, 4).reshape(B, L, H, d), S


def lightning_state_step(S: jax.Array, q: jax.Array, k: jax.Array,
                         v: jax.Array, slopes: jax.Array, fold: jax.Array,
                         pos: jax.Array, interpret: Optional[bool] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """One token a row against the row's state, LIVE rows only (depth
    above 0). S [B, H, d, d] f32; q, k, v [B, H, d]; fold [B] bool; pos
    [B] -> (S, o [B, H, d] f32). Where ``fold``: ``S = lambda S + k^T
    v``; then ``o = q S``. A live row that does not fold (its state
    already holds this token: the step is being computed again) only
    reads. A free row's state is neither read nor written and its ``o``
    is zeros."""
    with jax.named_scope("lightning_decode_step"):
        if (interpret is not None or on_tpu()) and state_step_supported(S):
            return state_step_kernel(S, q, k, v, slopes, fold, pos,
                                     interpret=bool(interpret))
        return _state_step_xla(S, q, k, v, slopes, fold, pos)


def _state_step_xla(S, q, k, v, slopes, fold, pos):
    """A loop over the live rows, one row's whole state a turn."""
    B, H, d, _ = S.shape
    order, n_live = live_slots(pos)
    lam = jnp.exp(-slopes.astype(jnp.float32))[:, None, None]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))

    def row(i, carry):
        S, o = carry
        b = order[i]
        mine = jax.lax.dynamic_index_in_dim(S, b, keepdims=False)
        new = lam * mine + k[b][:, :, None] * v[b][:, None, :]
        mine = jnp.where(fold[b], new, mine)
        ob = jnp.einsum("hd,hde->he", q[b], mine, precision=HI)
        return (jax.lax.dynamic_update_index_in_dim(S, mine, b, 0),
                jax.lax.dynamic_update_index_in_dim(o, ob, b, 0))

    return jax.lax.fori_loop(0, n_live, row,
                             (S, jnp.zeros((B, H, d), jnp.float32)))


# -- block selection ----------------------------------------------------------

def pool_keys(k: jax.Array, sp: SparseConfig) -> jax.Array:
    """The means of the complete windows of a fresh context: k [L, C] ->
    [n_w, C] in k's dtype (f32 sums)."""
    L = k.shape[0]
    n_w = max((L - sp.kernel_size) // sp.kernel_stride + 1, 0)
    if not n_w:
        return jnp.zeros((0, k.shape[1]), k.dtype)
    parts = sp.kernel_size // sp.kernel_stride
    st = sp.kernel_stride
    # a window is `parts` consecutive strides: sum the strides, then the
    # shifted copies
    strides = jnp.sum(k[:(L // st) * st].astype(jnp.float32).reshape(
        L // st, st, -1), axis=1)
    total = sum(strides[j:j + n_w] for j in range(parts))
    return (total / sp.kernel_size).astype(k.dtype)


def window_of_step(kv: jax.Array, pos: jax.Array, sp: SparseConfig,
                   width: int) -> Tuple[jax.Array, jax.Array]:
    """The newest COMPLETE pooled window of each row after the token at
    ``pos`` was written, recomputed from the K rows in the cache (so that
    writing it again changes nothing): kv [B, T, C] whose first ``width``
    lanes are K, pos [B] -> (window index [B], its mean [B, 1, width]).
    Before a row's first window is complete, window 0's slot takes a
    mean of rows not all written yet; no query sees it
    (:meth:`SparseConfig.windows_seen`) before the step at ``kernel_size
    - 1`` writes it whole."""
    j = jnp.maximum((pos - (sp.kernel_size - 1)) // sp.kernel_stride, 0)
    rows = jax.vmap(lambda c, lo: jax.lax.dynamic_slice(
        c, (lo, 0), (sp.kernel_size, width)))(kv, j * sp.kernel_stride)
    mean = jnp.mean(rows.astype(jnp.float32), axis=1, keepdims=True)
    return j.astype(jnp.int32), mean.astype(kv.dtype)


def sparse_block_scores(q: jax.Array, pooled: jax.Array, seen: jax.Array,
                        pos: jax.Array, scale: float,
                        interpret: Optional[bool] = None) -> jax.Array:
    """One query a row against that row's pooled keys: the softmax over
    the ``seen`` windows of every head, summed over each key-value
    group's heads. q [B, G, h, d]; pooled [B, NW, G * d]; seen, pos [B]
    -> [B, G, NW] f32, ``-1`` at a window not seen (a sum of
    probabilities is never negative). Live rows only on the TPU; a free
    row reads ``-1`` everywhere."""
    with jax.named_scope("sparse_decode_scores"):
        if (interpret is not None or on_tpu()) and \
                block_scores_supported(q, pooled):
            return block_scores_kernel(q, pooled, seen, pos, scale,
                                       interpret=bool(interpret))
        B, G, h, d = q.shape
        kc = pooled.reshape(B, pooled.shape[1], G, d)
        s = jnp.einsum("bghd,bjgd->bghj", q, kc, precision=_prec(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        return _group_probabilities(
            s, jnp.arange(kc.shape[1])[None, :] < seen[:, None])


def _group_probabilities(s: jax.Array, seen: jax.Array) -> jax.Array:
    """s [N, G, h, NW] scores, seen [N, NW] -> [N, G, NW]."""
    seen = seen[:, None, None, :]
    s = jnp.where(seen, s, NEG)
    e = jnp.where(seen, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
    return jnp.where(seen[:, :, 0], jnp.sum(p, axis=2), -1.0)


def block_scores(grp: jax.Array, at: jax.Array, sp: SparseConfig,
                 n_blocks: int) -> jax.Array:
    """A block's score for each query: the largest of the pooled windows
    that overlap it (those the query sees), ``+inf`` if forced, ``-inf``
    if no window reached it or it lies ahead of the query. grp [N, G, NW]
    (``-1`` where not seen), at [N] the queries' positions -> [N, G,
    n_blocks] f32."""
    N, G, NW = grp.shape
    per = sp.block_size // sp.kernel_stride
    first = -((sp.kernel_size - 1) // sp.kernel_stride)
    count = (sp.block_size - 1) // sp.kernel_stride - first + 1
    need = per * (n_blocks - 1) + count
    padded = jnp.pad(grp, ((0, 0), (0, 0), (-first, max(
        0, need + first - NW))), constant_values=-1.0)
    score = functools.reduce(jnp.maximum, [
        jax.lax.slice_in_dim(padded, i, i + per * (n_blocks - 1) + 1,
                             stride=per, axis=2) for i in range(count)])
    score = jnp.where(score >= 0, score, -jnp.inf)
    m = jnp.arange(n_blocks)[None, :]
    mine = (at // sp.block_size)[:, None]
    forced = (m < sp.init_blocks) | (m > mine - sp.local_blocks)
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    return jnp.where((m <= mine)[:, None, :], score, -jnp.inf)


def decode_selection(grp: jax.Array, pos: jax.Array, sp: SparseConfig,
                     n_blocks: int) -> Tuple[jax.Array, jax.Array]:
    """The blocks one query a row keeps, a group: grp [B, G, NW], pos [B]
    -> (idx [B, G, K] int32, valid [B, G, K]) with K = min(topk,
    n_blocks)."""
    with jax.named_scope("sparse_decode_selection"):
        vals, idx = jax.lax.top_k(block_scores(grp, pos, sp, n_blocks),
                                  min(sp.topk, n_blocks))
    return idx.astype(jnp.int32), vals > -jnp.inf


def prefill_selection(q: jax.Array, kc: jax.Array, sp: SparseConfig,
                      scale: float) -> jax.Array:
    """The blocks every query of a fresh context keeps, as a mask: q [L,
    G, h, d], kc [n_w, G, d] -> bool [G, L, n_blocks] (``n_blocks`` whole
    blocks cover ``L``). A query whose context is at most ``dense_len``
    keeps every causal block."""
    L, G, h, d = q.shape
    n_w = kc.shape[0]
    n_blocks = -(-L // sp.block_size)
    K = min(sp.topk, n_blocks)
    bq = _block(L, SELECT_BLOCK)
    m = jnp.arange(n_blocks)[None, None, :]

    def block(i):
        at = i * bq + jnp.arange(bq)
        causal = m <= (at // sp.block_size)[:, None, None]    # [bq,1,nb]
        if n_w == 0 or L <= sp.dense_len:
            return jnp.broadcast_to(causal, (bq, G, n_blocks))
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq)
        s = jnp.einsum("qghd,jgd->qghj", qb, kc, precision=_prec(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        grp = _group_probabilities(
            s, jnp.arange(n_w)[None, :] < sp.windows_seen(at)[:, None])
        score = block_scores(grp, at, sp, n_blocks)
        keys = _sort_key(score).reshape(bq * G, n_blocks)
        thr = kth_largest_key(keys, K)[:, None]
        above, tied = keys > thr, keys == thr
        # ties at the K-th value go to the lowest blocks, as top_k
        room = K - jnp.sum(above, axis=1, keepdims=True)
        keep = (above | (tied & (jnp.cumsum(tied, axis=1) <= room))
                ).reshape(bq, G, n_blocks) & (score > -jnp.inf)
        return jnp.where((at + 1 <= sp.dense_len)[:, None, None],
                         causal, keep)

    with jax.named_scope("sparse_prefill_selection"):
        keep = jax.lax.map(block, jnp.arange(L // bq))
    return keep.reshape(L, G, n_blocks).transpose(1, 0, 2)


# -- block-sparse attends -----------------------------------------------------

def sparse_block_attend(q: jax.Array, kv: jax.Array, idx: jax.Array,
                        valid: jax.Array, pos: jax.Array, sp: SparseConfig,
                        scale: float, interpret: Optional[bool] = None
                        ) -> jax.Array:
    """One query a row over its kept blocks, read in place. q [B, G, h,
    d]; kv [B, T, 2 G d] (a position's K of every group, then its V);
    idx, valid [B, G, K]; pos [B] -> [B, G, h, d] f32: the causal softmax
    over the positions ``<= pos`` of the valid kept blocks. A free row
    (depth 0) gives zeros on the TPU and reads nothing."""
    B, G, h, d = q.shape
    bs = sp.block_size
    at = idx[..., None] * bs + jnp.arange(bs)                 # [B,G,K,bs]
    ok = valid[..., None] & (at <= pos[:, None, None, None])
    bias = jnp.where(ok, 0.0, NEG).reshape(B, G, 1, -1)
    with jax.named_scope("sparse_decode_attend"):
        if (interpret is not None or on_tpu()) and \
                block_attend_supported(q, kv, sp):
            return block_attend_kernel(q, kv, idx, bias, pos, bs, scale,
                                       interpret=bool(interpret))
        prec = _prec(q.dtype)
        flat = jnp.clip(at, 0, kv.shape[1] - 1).reshape(B, G, -1)
        rows = jax.vmap(lambda c, a: c[a])(kv, flat)          # [B,G,N,2Gd]
        rows = rows.reshape(B, G, flat.shape[-1], 2, G, d)
        pick = jnp.arange(G)
        kk, vv = rows[:, pick, :, 0, pick], rows[:, pick, :, 1, pick]
        kk, vv = (x.transpose(1, 0, 2, 3) for x in (kk, vv))  # [B,G,N,d]
        s = jnp.einsum("bghd,bgnd->bghn", q, kk, precision=prec,
                       preferred_element_type=jnp.float32) * scale + bias
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bghn,bgnd->bghd", p.astype(q.dtype), vv,
                          precision=prec,
                          preferred_element_type=jnp.float32)


def dense_decode_attend(q: jax.Array, kv: jax.Array, pos: jax.Array,
                        limit: int, scale: float) -> jax.Array:
    """One query a row over its row's first ``limit`` cached positions up
    to its depth: q [B, G, h, d], kv [B, T, 2 G d] -> [B, G, h, d] f32.
    SLOT-BLIND: every slot's ``limit`` rows are read and scored, live or
    free, whatever the depth, and the float32 scores are ``[B, G h,
    limit]``. Who calls it, and at what ``limit``: models/minicpm_sala.py
    for the rows whose context is at most ``dense_len`` (8,192; only when
    such a row is live), models/exaone_moe.py's
    window layers over their ring of ``sliding_window`` rows (128: with
    ``pos`` capped at the ring's last row once it has wrapped), and
    :func:`gqa_decode_attend` off the TPU."""
    B, G, h, d = q.shape
    prec = _prec(q.dtype)
    seen = (jnp.arange(limit)[None, :] <= pos[:, None])[:, None, :]
    out = []
    for g in range(G):          # a group's K and V are lane slices of a row
        kg = jax.lax.slice(kv, (0, 0, g * d), (B, limit, (g + 1) * d))
        vg = jax.lax.slice(kv, (0, 0, (G + g) * d),
                           (B, limit, (G + g + 1) * d))
        s = jnp.einsum("bhd,bnd->bhn", q[:, g], kg, precision=prec,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(seen, s, NEG), axis=-1)
        out.append(jnp.einsum("bhn,bnd->bhd", p.astype(q.dtype), vg,
                              precision=prec,
                              preferred_element_type=jnp.float32))
    return jnp.stack(out, axis=1)


def gqa_attend_block(T: int) -> int:
    """Cached positions a block of ``gqa_dense_attend``: the kernel's, and
    the unit its visits are counted in on every backend."""
    return _block(T, GQA_BLOCK_T)


def gqa_attend_visits(pos: jax.Array, T: int) -> jax.Array:
    """Cached positions the blocks of one :func:`gqa_decode_attend` call
    cover over ALL rows, as the kernel's grid visits them
    (``ops.latent_attention.dense_attend_visits`` in this kernel's
    blocks). pos [B] -> int32 scalar."""
    return dense_attend_visits(pos, T, gqa_attend_block(T))


def gqa_decode_attend(q: jax.Array, kv: jax.Array, pos: jax.Array,
                      scale: float, interpret: Optional[bool] = None
                      ) -> jax.Array:
    """One query a row (every head) over that row's cached K and V up to
    its own depth, read IN PLACE from the ``[B, T, 2 G d]`` leaf: q [B, G,
    h, d], kv (a position's K of every group, then its V), pos [B] -> [B,
    G, h, d] f32, the causal softmax over positions ``<= pos[b]``. A row
    at depth 0 is a free slot (an admitted row is at least one token
    deep): it gives zeros, and on the TPU none of its cache row is read,
    nor any block past a live row's depth (``gqa_dense_attend``). Off
    the TPU :func:`dense_decode_attend` over the whole leaf."""
    with jax.named_scope("gqa_decode_attend"):
        if (interpret is not None or on_tpu()) and \
                gqa_attend_supported(q, kv):
            return gqa_attend_kernel(q, kv, pos, scale,
                                     interpret=bool(interpret))
        out = dense_decode_attend(q, kv, pos, kv.shape[1], scale)
        return jnp.where((pos > 0)[:, None, None, None], out, 0.0)


def ring_rows(rows: jax.Array, true_len: Optional[jax.Array], W: int
              ) -> jax.Array:
    """What a prefill leaves in a ring of the last ``W`` rows: rows [B, L,
    C] of a fresh context, true_len [B] or a scalar (None: ``L``) -> [B,
    W, C], position p of ``(true_len - W, true_len - 1]`` in row ``p mod
    W``, zeros for positions before the sequence, and nothing of a
    bucket's padding."""
    B, L, _ = rows.shape
    true_len = jnp.broadcast_to(jnp.asarray(
        L if true_len is None else true_len, jnp.int32), (B,))
    padded = jnp.pad(rows, ((0, 0), (W, 0), (0, 0)))
    # rows true_len - W .. true_len - 1, rolled so that position p lies in
    # row p mod W
    return jax.vmap(lambda p, n: jnp.roll(
        jax.lax.dynamic_slice_in_dim(p, n, W, axis=0), n % W, axis=0))(
            padded, true_len)


def sparse_prefill_attend(q: jax.Array, k: jax.Array, v: jax.Array,
                          keep: Optional[jax.Array], sp: SparseConfig,
                          scale: float) -> jax.Array:
    """Grouped-query attention of a fresh context under the selection's
    block mask, by tiles (online softmax; tiles past the diagonal are
    skipped; a group's heads are the rows of one product). q [L, G, h,
    d]; k, v [L, G, d]; keep [G, L, n_blocks] bool (None: plain causal)
    -> [L, G, h, d] in q's dtype. What is computed is the selection's
    result: a position outside a query's kept blocks has weight 0."""
    L, G, h, d = q.shape
    bs = sp.block_size
    n_blocks = -(-L // bs)
    Lp = n_blocks * bs
    if Lp != L:                      # keys past L lie ahead of every query
        pad = ((0, Lp - L), (0, 0), (0, 0))
        q, k, v = (jnp.pad(q, pad + ((0, 0),)), jnp.pad(k, pad),
                   jnp.pad(v, pad))
        if keep is not None:
            keep = jnp.pad(keep, ((0, 0), (0, Lp - L), (0, 0)))
    tb = _block(n_blocks, max(ATTEND_TILE // bs, 1))
    t = tb * bs                                   # a tile: whole blocks
    prec = _prec(q.dtype)
    qg = q.transpose(1, 2, 0, 3)                              # [G,h,Lp,d]
    kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)       # [G,Lp,d]

    def q_tile(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * t, t, axis=2)
        rows = i * t + jnp.arange(t)

        def k_tile(j, carry):
            m_, l_, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(kg, j * t, t, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(vg, j * t, t, axis=1)
            s = jnp.einsum("ghqd,gkd->ghqk", qi, kj, precision=prec,
                           preferred_element_type=jnp.float32) * scale
            ok = ((j * t + jnp.arange(t))[None, :] <= rows[:, None])[None]
            if keep is not None:
                kept = jax.lax.dynamic_slice(
                    keep, (0, i * t, j * tb), (G, t, tb))
                ok = ok & jnp.repeat(kept, bs, axis=2)
            ok = ok[:, None]                                  # [G,1,t,t]
            s = jnp.where(ok, s, NEG)
            m_new = jnp.maximum(m_, jnp.max(s, axis=-1))
            p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            alpha = jnp.exp(m_ - m_new)
            l_ = l_ * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "ghqk,gkd->ghqd", p.astype(vj.dtype), vj, precision=prec,
                preferred_element_type=jnp.float32)
            return m_new, l_, acc

        init = (jnp.full((G, h, t), NEG, jnp.float32),
                jnp.zeros((G, h, t), jnp.float32),
                jnp.zeros((G, h, t, d), jnp.float32))
        _, l_, acc = jax.lax.fori_loop(0, i + 1, k_tile, init)
        return (acc / l_[..., None]).astype(q.dtype)

    with jax.named_scope("sparse_prefill_attend"):
        out = jax.lax.map(q_tile, jnp.arange(Lp // t))        # [n,G,h,t,d]
    return out.transpose(0, 3, 1, 2, 4).reshape(Lp, G, h, d)[:L]


# -- Pallas kernels (TPU) -----------------------------------------------------

def chunk_scan_supported(q, C: int) -> bool:
    """bfloat16, lane-wide heads, chunks of whole lane tiles."""
    return (q.dtype == jnp.bfloat16 and q.shape[-1] % 128 == 0
            and C % 128 == 0)


def _chunk_scan_body(len_ref, q_ref, k_ref, v_ref, slope_ref, o_ref,
                     s_out_ref, S, *, C):
    b, c = pl.program_id(0), pl.program_id(2)
    s_col = slope_ref[0][:, :1]                               # [1, 1]
    n = jnp.clip(len_ref[b] - c * C, 0, C).astype(jnp.float32)

    @pl.when(c == 0)
    def _():
        S[...] = jnp.zeros(S.shape, jnp.float32)

    q, k, v = q_ref[0], k_ref[0], v_ref[0]                    # [C, d]
    dt = q.dtype
    i = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0).astype(jnp.float32)
    j = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1).astype(jnp.float32)
    diff = i - j                                              # [C, C]
    decay = jnp.where(diff >= 0.0,
                      jnp.exp(-s_col * jnp.maximum(diff, 0.0)), 0.0)
    a = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * decay
    o = jnp.dot(a.astype(dt), v, preferred_element_type=jnp.float32)
    o = o + jnp.exp(-s_col * (i + 1.0)) * jnp.dot(
        q, S[...].astype(dt), preferred_element_type=jnp.float32)
    o_ref[0] = o
    left = n - 1.0 - i                                        # [C, 1]
    w = jnp.where(left >= 0.0, jnp.exp(-s_col * jnp.maximum(left, 0.0)),
                  0.0)
    kw = (k.astype(jnp.float32) * w).astype(dt)
    S[...] = jnp.exp(-s_col * n) * S[...] + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[0, 0] = S[...]


def chunk_scan_kernel(q, k, v, slopes, true_len, C: int,
                      interpret: bool = False):
    """:func:`lightning_chunk_scan` on the TPU: grid (row, head, chunk),
    the chunks of one head in order with its state in VMEM. The operands
    are read as ``[C, d]`` tiles of the ``[L, H d]`` projections as they
    stand (no transpose to head-major)."""
    B, L, H, d = q.shape
    flat = [x.reshape(B, L, H * d) for x in (q, k, v)]
    tile = pl.BlockSpec((1, C, d), lambda b, h, c, n: (b, c, h))
    lanes = jnp.broadcast_to(slopes.astype(jnp.float32)[:, None, None],
                             (H, 1, 128))
    o, S = pl.pallas_call(
        functools.partial(_chunk_scan_body, C=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H, L // C),
            in_specs=[tile, tile, tile,
                      pl.BlockSpec((1, 1, 128), lambda b, h, c, n: (h, 0, 0))],
            out_specs=[tile, pl.BlockSpec((1, 1, d, d),
                                          lambda b, h, c, n: (b, h, 0, 0))],
            scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, L, H * d), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="lightning_chunk_scan",
    )(true_len, *flat, lanes)
    return o.reshape(B, L, H, d), S


#: Heads a grid step of the state step (8 x [128, 128] f32 is 512 KB in,
#: as much out, each double-buffered).
STATE_HEADS = 8


def state_step_supported(S) -> bool:
    """Lane-wide square states, whole groups of heads."""
    return (S.dtype == jnp.float32 and S.shape[-1] % 128 == 0
            and S.shape[-1] == S.shape[-2]
            and S.shape[1] % STATE_HEADS == 0)


def _state_step_body(row_ref, act_ref, fold_ref, S_ref, q_ref, k_ref, v_ref,
                     lam_ref, o0_ref, S_out, o_out, *, hb):
    del o0_ref
    i = pl.program_id(0)

    @pl.when(act_ref[i] == 1)
    def _():
        fold = fold_ref[i] == 1
        q8, k8, v8 = q_ref[0], k_ref[0], v_ref[0]             # [hb, d] f32
        head = jax.lax.broadcasted_iota(jnp.int32, q8.shape, 0)
        o = jnp.zeros(q8.shape, jnp.float32)
        for h in range(hb):
            mine = S_ref[0, h]                                # [d, d]
            # k_h^T v_h as a product over the head axis with every other
            # head's v zeroed: the operands stay whole (8, 128) tiles
            outer = jax.lax.dot_general(
                k8, jnp.where(head == h, v8, 0.0), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=HI)
            mine = jnp.where(fold, lam_ref[0, h:h + 1, :] * mine + outer,
                             mine)
            S_out[0, h] = mine
            o = jnp.where(head == h, jnp.dot(
                q8, mine, preferred_element_type=jnp.float32, precision=HI),
                o)
        o_out[0] = o


def state_step_kernel(S, q, k, v, slopes, fold, pos,
                      interpret: bool = False):
    """:func:`lightning_state_step` on the TPU, in place
    (``input_output_aliases``): grid (live-slot schedule, head groups); a
    step past the live slots stays on the block it holds."""
    B, H, d, _ = S.shape
    hb = STATE_HEADS
    nj = H // hb
    row, active, _ = live_schedule(pos)
    fold_of = fold.astype(jnp.int32)[row]
    lam = jnp.broadcast_to(jnp.exp(-slopes.astype(jnp.float32))[:, None],
                           (H, d)).reshape(nj, hb, d)

    def at(i, j, row, act, fold):
        return row[i], jnp.where(act[i] == 1, j, nj - 1)

    state = pl.BlockSpec((1, hb, d, d), lambda i, j, *s: at(i, j, *s)
                         + (0, 0))
    vec = pl.BlockSpec((1, hb, d), lambda i, j, *s: at(i, j, *s) + (0,))
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    return pl.pallas_call(
        functools.partial(_state_step_body, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, nj),
            in_specs=[state, vec, vec, vec,
                      pl.BlockSpec((1, hb, d), lambda i, j, row, act, fold: (
                          jnp.where(act[i] == 1, j, nj - 1), 0, 0)),
                      vec],
            out_specs=[state, vec]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, jnp.float32),
                   jax.ShapeDtypeStruct((B, H, d), jnp.float32)],
        # operands 0-2 are the prefetched schedule; the state is updated in
        # place and a free row's output stays the zeros it is handed
        input_output_aliases={3: 0, 8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="lightning_state_step",
    )(row, active, fold_of, S, q, k, v, lam,
      jnp.zeros((B, H, d), jnp.float32))


def block_scores_supported(q, pooled) -> bool:
    """bfloat16, lane-wide heads, whole lane tiles of windows, a group's
    heads a whole sublane tile; a row's pooled keys of one group
    (double-buffered) well inside scoped VMEM."""
    B, G, h, d = q.shape
    NW = pooled.shape[1]
    return (q.dtype == jnp.bfloat16 and pooled.dtype == jnp.bfloat16
            and d % 128 == 0 and NW % 128 == 0 and h % 16 == 0
            and NW * d * 2 <= 3 * 1024 * 1024)


def _block_scores_body(row_ref, act_ref, seen_ref, q_ref, kc_ref, init_ref,
                       out_ref, *, scale):
    del init_ref
    i = pl.program_id(0)

    @pl.when(act_ref[i] == 1)
    def _():
        s = jax.lax.dot_general(
            q_ref[0, 0], kc_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [h, NW]
        seen = jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1) \
            < seen_ref[i]
        s = jnp.where(seen, s, NEG)
        e = jnp.where(seen, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
        out_ref[0, 0] = jnp.where(seen, jnp.sum(p, axis=0, keepdims=True),
                                  -1.0)


def block_scores_kernel(q, pooled, seen, pos, scale: float,
                        interpret: bool = False):
    """:func:`sparse_block_scores` on the TPU: grid (live-slot schedule,
    group); a row's pooled keys of one group are one block read in place
    from the ``[B, NW, G d]`` leaf."""
    B, G, h, d = q.shape
    NW = pooled.shape[1]
    row, active, _ = live_schedule(pos)

    def at(i, g, row, act, seen):
        return row[i], jnp.where(act[i] == 1, g, G - 1)

    out = pl.BlockSpec((1, 1, 1, NW), lambda i, g, *s: at(i, g, *s) + (0, 0))
    return pl.pallas_call(
        functools.partial(_block_scores_body, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, G),
            in_specs=[
                pl.BlockSpec((1, 1, h, d),
                             lambda i, g, *s: at(i, g, *s) + (0, 0)),
                pl.BlockSpec((1, NW, d), lambda i, g, *s: (
                    at(i, g, *s)[0], 0, at(i, g, *s)[1])),
                out],
            out_specs=out),
        out_shape=jax.ShapeDtypeStruct((B, G, 1, NW), jnp.float32),
        input_output_aliases={5: 0},     # a free row's scores stay -1
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="sparse_block_scores",
    )(row, active, seen.astype(jnp.int32)[row], q, pooled,
      jnp.full((B, G, 1, NW), -1.0, jnp.float32))[:, :, 0]


def block_attend_supported(q, kv, sp: SparseConfig) -> bool:
    """bfloat16, lane-wide heads, blocks of whole sublane tiles, a group's
    heads a whole sublane tile; the kept blocks' K and V of one group fit
    VMEM."""
    B, G, h, d = q.shape
    K = min(sp.topk, kv.shape[1] // sp.block_size)
    return (q.dtype == jnp.bfloat16 and kv.dtype == jnp.bfloat16
            and d % 128 == 0 and sp.block_size % 16 == 0 and h % 16 == 0
            and kv.shape[1] % sp.block_size == 0
            and K * sp.block_size * d * 2 * 2 <= 6 * 1024 * 1024)


def _block_attend_body(row_ref, act_ref, idx_ref, q_ref, bias_ref, kv_ref,
                       init_ref, out_ref, kbuf, vbuf, sem, *, K, bs, G, d,
                       scale):
    del init_ref
    i, g = pl.program_id(0), pl.program_id(1)

    @pl.when(act_ref[i] == 1)
    def _():
        b = row_ref[i]
        base = (b * G + g) * K
        k_lane = pl.multiple_of(g * d, 128)
        v_lane = pl.multiple_of((G + g) * d, 128)

        def copies(j, at):
            rows = pl.ds(pl.multiple_of(at * bs, bs), bs)
            mine = pl.ds(pl.multiple_of(j * bs, bs), bs)
            return (pltpu.make_async_copy(
                kv_ref.at[b, rows, pl.ds(k_lane, d)], kbuf.at[mine],
                sem.at[0]),
                    pltpu.make_async_copy(
                kv_ref.at[b, rows, pl.ds(v_lane, d)], vbuf.at[mine],
                sem.at[1]))

        def start(j, _):
            for c in copies(j, idx_ref[base + j]):
                c.start()
            return 0

        def wait(j, _):
            for c in copies(j, 0):
                c.wait()
            return 0

        jax.lax.fori_loop(0, K, start, 0)
        jax.lax.fori_loop(0, K, wait, 0)
        q = q_ref[0, 0]                                       # [h, d]
        s = jax.lax.dot_general(
            q, kbuf[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale + bias_ref[0, 0]
        p = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        out_ref[0, 0] = jnp.dot(
            p.astype(q.dtype), vbuf[...],
            preferred_element_type=jnp.float32) / jnp.sum(
                p, -1, keepdims=True)


def block_attend_kernel(q, kv, idx, bias, pos, bs: int, scale: float,
                        interpret: bool = False):
    """:func:`sparse_block_attend` on the TPU: grid (live-slot schedule,
    group); a step copies its row's kept blocks (K and V of its group,
    ``[bs, d]`` each) from the cache leaf where it lies into VMEM and
    attends them at once."""
    B, G, h, d = q.shape
    K = idx.shape[-1]
    row, active, _ = live_schedule(pos)

    def at(i, g, row, act, idx):
        return row[i], jnp.where(act[i] == 1, g, G - 1), 0, 0

    out = pl.BlockSpec((1, 1, h, d), at)
    return pl.pallas_call(
        functools.partial(_block_attend_body, K=K, bs=bs, G=G, d=d,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, G),
            in_specs=[pl.BlockSpec((1, 1, h, d), at),
                      pl.BlockSpec((1, 1, 1, K * bs), at),
                      pl.BlockSpec(memory_space=pl.ANY),
                      out],
            out_specs=out,
            scratch_shapes=[pltpu.VMEM((K * bs, d), kv.dtype),
                            pltpu.VMEM((K * bs, d), kv.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, G, h, d), jnp.float32),
        input_output_aliases={6: 0},     # a free row's result stays zeros
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="sparse_block_attend",
    )(row, active, idx.reshape(-1).astype(jnp.int32), q, bias, kv,
      jnp.zeros((B, G, h, d), jnp.float32))


#: Cached positions a block of ``gqa_dense_attend`` (512 x 2,048 bfloat16
#: is 2 MB in VMEM, twice for the double buffer). Swept on the chip, PR 47
#: (PERF.md section 6; 32 slots of 16,384, 25 live at 128-14,000 deep,
#: 187,171 live positions: 0.94 ms at 819 GB/s): 256 positions 1.80 ms,
#: 512 1.18, 1,024 1.20; the slot-blind ``dense_decode_attend`` 2.94.
GQA_BLOCK_T = 512


def gqa_attend_supported(q, kv) -> bool:
    """bfloat16, lane-wide heads, whole blocks of positions in whole
    sublane tiles (a group's queries are padded to whole float32 sublane
    tiles: granitemoehybrid's groups hold 4 and pad to 8; jamba's ONE
    group holds 20, the first count that is no whole tile, and pads to 24:
    the padding's rows are separate rows of the product and are dropped)."""
    B, G, h, d = q.shape
    T, C = kv.shape[1], kv.shape[2]
    return (q.dtype == jnp.bfloat16 and kv.dtype == jnp.bfloat16
            and d % 128 == 0 and C == 2 * G * d
            and gqa_attend_block(T) % 16 == 0)


def _gqa_attend_body(pos_ref, row_ref, lo_ref, hi_ref, q_ref, kv_ref,
                     out_ref, m_ref, l_ref, acc_ref, *, scale, bt, G, h, d):
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when((pos > 0) & (j * bt <= pos))
    def _():
        col = j * bt + jax.lax.broadcasted_iota(jnp.int32, (h, bt), 1)
        for g in range(G):      # a group's K and V are lane slices of a row
            mine = pl.ds(g * h, h)
            k = kv_ref[0, :, g * d:(g + 1) * d]               # [bt, d]
            v = kv_ref[0, :, (G + g) * d:(G + g + 1) * d]
            s = jax.lax.dot_general(
                q_ref[0, g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # [h, bt]
            s = jnp.where(col <= pos, s * scale, NEG)
            m_old = m_ref[mine, :]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_old - m_new)
            l_ref[mine, :] = l_ref[mine, :] * alpha + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_ref[mine, :] = acc_ref[mine, :] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[mine, :] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).reshape(G, h, d)


def gqa_attend_kernel(q: jax.Array, kv: jax.Array, pos: jax.Array,
                      scale: float, interpret: bool = False) -> jax.Array:
    """:func:`gqa_decode_attend` on the TPU, ``gqa_dense_attend``: grid
    (row, blocks of positions); a step holds one block of a row's K and V
    of EVERY group (``[bt, 2 G d]``, contiguous in the leaf) and folds it
    into each group's running softmax, the group's ``h`` queries the rows
    of one product. Blocks past a row's depth and every block of a free
    slot are neither read (``dense_attend_schedule`` keeps the grid on the
    block it already holds) nor computed. A group of fewer than 8
    queries (or not a multiple) is padded with zero queries to whole
    sublane tiles, and the padding's rows are dropped."""
    B, G, asked, d = q.shape
    h = -(-asked // 8) * 8      # a group's queries: the rows of one product
    if h != asked:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, h - asked), (0, 0)))
    T = kv.shape[1]
    bt = gqa_attend_block(T)
    pos = pos.astype(jnp.int32)
    row, lo, hi = dense_attend_schedule(pos, bt)
    out = pl.pallas_call(
        functools.partial(_gqa_attend_body, scale=scale, bt=bt, G=G, h=h,
                          d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B, T // bt),
            in_specs=[
                pl.BlockSpec((1, G, h, d), lambda b, j, *_: (b, 0, 0, 0)),
                pl.BlockSpec((1, bt, 2 * G * d),
                             lambda b, j, pos, row, lo, hi: (
                                 row[b], jnp.clip(j, lo[b], hi[b]), 0)),
            ],
            out_specs=pl.BlockSpec((1, G, h, d),
                                   lambda b, j, *_: (b, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((G * h, 1), jnp.float32),
                            pltpu.VMEM((G * h, 1), jnp.float32),
                            pltpu.VMEM((G * h, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, G, h, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="gqa_dense_attend",
    )(pos, row, lo, hi, q, kv)
    return out if h == asked else out[:, :, :asked]
