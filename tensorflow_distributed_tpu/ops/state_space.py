"""The device side of the state-space mixers: a depthwise convolution over
the last ``d_conv`` inputs and, behind it, one of TWO selective state
recurrences.

**Mamba-2** (models/granitemoehybrid.py and models/nemotron_h.py), ONE decay
a head a token. Per head ``h`` (``P`` channels, a state of ``N`` numbers a
channel; ``B_t`` and ``C_t`` come in ``G`` groups, ``G`` dividing the heads,
and head ``h`` reads group ``g = h // (H / G)``: granite has one group,
nemotron_h eight):

    S_t = exp(dt_t A_h) S_{t-1} + (dt_t x_t) B_{g,t}^T    y_t = S_t C_{g,t}

**Mamba-1** (models/jamba.py), a decay for every channel AND state number a
token (``dt`` a channel, ``A`` ``[N, channels]``):

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c] = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]

Because Mamba-2's decay is one number a head, a chunk of its recurrence is
matrix products (:func:`ssd_chunk_scan`); Mamba-1's has no such form and is
a scan over positions on the vector and transcendental units
(:func:`s6_chunk_scan`), its section at the end of this file. The
convolution, its ring and :func:`live_schedule` serve both.

- **Why a file of its own** and not a section of ``ops/hybrid_attention
  .py``: the lightning kernels there are built for a square ``[d, d]`` state
  a head, one constant decay a head and q, k, v a head; here the decay
  differs at every token, ``B`` and ``C`` are shared by a group's heads (the
  chunk's ``C B^T`` is computed once for the heads of a grid step, which
  lie inside one group), the heads are 64 wide (half a lane tile) and the
  state is ``[P, N]``. No core serves both
  without moving the lightning kernels' numbers, so they stay as they are
  (only :func:`live_schedule` is shared).
- **The state's layout.** A row keeps ``S^T``: ``[N, H P]`` float32, ``(head,
  channel)`` along the lanes (Mamba-1: ``[N, channels]``, for the same
  reason and one more: its ``N`` is 16, an eighth of a lane tile). Decay,
  ``dt x`` and the read-out are then
  rows as the projections produce them, a decode step is element-wise over
  whole lane tiles (no product of 64-wide operands), and the chunked scan's
  carried state is the ``[N, 128]`` right-hand side of a plain product.
- :func:`ssd_chunk_scan` (prefill): chunks of :data:`SCAN_CHUNK` tokens,
  per-token log-decays summed inside a chunk, the carried state in VMEM;
  a token whose ``dt`` is 0 (the caller zeroes ``dt`` past ``true_len``)
  neither decays the state nor enters it, so the state returned is the one
  AT ``true_len``.
- :func:`ssd_state_step` (decode): the LIVE rows only, in place; a row
  folds its token iff the caller says so (``pos == state_pos``) and is
  read either way; a free row is neither read nor written.
- :func:`s6_chunk_scan` / :func:`s6_state_step`: the same two for Mamba-1
  (the scan is handed ``true_len`` and zeroes ``dt`` itself; the step
  computes its decay inside the kernel, where Mamba-2's comes in as a row).
- :func:`ssd_conv` / :func:`ssd_conv_step`: the convolution. What a slot
  keeps of it is a RING of the last ``d_conv`` inputs, row ``position mod
  d_conv``: a step computed again writes the same row again, so the leaf
  needs no stamp, unlike the state. (``d_conv - 1`` rows, the usual
  convolution state, cannot serve a step computed again: the window of the
  repeated step needs the row that the first pass shifted out.)

Each of the four recurrence functions is a NAMED Pallas kernel on the TPU
(``ssd_chunk_scan``, ``ssd_state_step``, ``s6_chunk_scan``,
``s6_state_step``) with an XLA form that runs anywhere; the scopes are
``ssd_prefill_scan``, ``ssd_decode_step``, ``s6_scan``, ``s6_state_step``
and ``ssd_conv`` (models/jamba.py wraps the last in ``s6_conv``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflow_distributed_tpu.ops.hybrid_attention import live_schedule
from tensorflow_distributed_tpu.ops.latent_attention import (
    _block, _prec, on_tpu)

HI = jax.lax.Precision.HIGHEST
#: Tokens a chunk of the prefill scan (the source's ``mamba_chunk_size``).
SCAN_CHUNK = 256
#: Heads a grid step of the chunk scan (pairs of 64-wide heads fill a lane
#: tile; 8 heads are 512 lanes of x and of the carried state).
SCAN_HEADS = 8
#: Lanes of ``(head, channel)`` a grid step of the state step moves (a
#: ``[128, 2048]`` float32 block is 1 MB in, as much out, double-buffered).
STEP_LANES = 2048


# -- the convolution ----------------------------------------------------------

def ssd_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
             true_len: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """Causal depthwise convolution of fresh contexts, then SiLU. xbc [B,
    L, C]; w [K, C] (tap j multiplies the input ``K - 1 - j`` positions
    back); b [C]; true_len [B] or a scalar (None: ``L``) -> (``silu(b +
    sum_j w_j xbc_{t-K+1+j})`` [B, L, C] in xbc's dtype, zeros before the
    sequence; the ring [B, K, C] of the last ``K`` inputs before
    ``true_len``, row ``position mod K``, zeros for positions before the
    sequence)."""
    B, L, C = xbc.shape
    K = w.shape[0]
    if true_len is None:
        true_len = L
    true_len = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32), (B,))
    with jax.named_scope("ssd_conv"):
        padded = jnp.pad(xbc, ((0, 0), (K, 0), (0, 0)))
        acc = b.astype(jnp.float32)
        for j in range(K):
            acc = acc + w[j].astype(jnp.float32) * jax.lax.slice_in_dim(
                padded, j + 1, j + 1 + L, axis=1).astype(jnp.float32)
        # rows true_len - K .. true_len - 1, rolled so that position q lies
        # in row q mod K
        tail = jax.vmap(lambda p, n: jnp.roll(
            jax.lax.dynamic_slice_in_dim(p, n, K, axis=0), n % K, axis=0))(
                padded, true_len)
    return jax.nn.silu(acc).astype(xbc.dtype), tail


def ssd_conv_step(ring: jax.Array, new: jax.Array, w: jax.Array,
                  b: jax.Array, pos: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """One token a row: ring [B, K, C] (row ``q mod K`` holds the input of
    position q), new [B, C] the input at ``pos`` [B] -> (the ring with
    ``new`` at row ``pos mod K``, the convolution's output at ``pos`` [B,
    C] in new's dtype). Writing the same token again changes nothing."""
    K = ring.shape[1]
    with jax.named_scope("ssd_conv"):
        s = jnp.arange(K, dtype=jnp.int32)[None, :]
        pos = pos.astype(jnp.int32)[:, None]
        ring = jnp.where((s == pos % K)[..., None],
                         new[:, None, :].astype(ring.dtype), ring)
        # row s holds position pos - ((pos - s) mod K): tap K-1-that
        tap = K - 1 - (pos - s) % K                               # [B, K]
        # (a one-hot product, not ``w[tap]``: a gather of B K rows is a
        # loop on the TPU)
        taps = jnp.einsum(
            "bsj,jc->bsc", (tap[..., None] == jnp.arange(K)).astype(
                jnp.float32), w.astype(jnp.float32), precision=HI)
        out = b.astype(jnp.float32) + jnp.sum(
            taps * ring.astype(jnp.float32), axis=1)
    return ring, jax.nn.silu(out).astype(new.dtype)


# -- the prefill scan ---------------------------------------------------------

def ssd_chunk_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                   Cm: jax.Array, interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over fresh contexts, in chunks. x [B, L, H, P]; dt
    [B, L, H] float32 (after the softplus; 0 at a token that does not
    count); A [H] float32 (negative); Bm, Cm [B, L, G, N], ``G`` dividing
    ``H`` -> (``y`` [B, L, H, P] float32 with ``y_t = S_t C_t``, ``S^T``
    [B, N, H P] float32 after the last token). A token with ``dt = 0`` (a
    bucket's padding) leaves no trace in the state and does not decay it;
    its own ``y`` is finite and means nothing."""
    L = x.shape[1]
    C = _block(L, SCAN_CHUNK)
    kernel = (interpret is not None or on_tpu()) and \
        chunk_scan_supported(x, Bm, C)
    with jax.named_scope("ssd_prefill_scan"):
        if kernel:
            return chunk_scan_kernel(x, dt, A, Bm, Cm, C,
                                     interpret=bool(interpret))
        return _chunk_scan_xla(x, dt, A, Bm, Cm, C)


def _log_decay_in_chunks(dt, A, C):
    """dt [B, L, H] f32, A [H] -> the log-decays ``dt A`` summed from each
    chunk's first token up to and with each token: [B, L / C, C, H]."""
    B, L, H = dt.shape
    return jnp.cumsum((dt * A.astype(jnp.float32)).reshape(B, L // C, C, H),
                      axis=2)


def _chunk_scan_xla(x, dt, A, Bm, Cm, C):
    B, L, H, P = x.shape
    G, N = Bm.shape[2:]
    hg = H // G                          # heads a group of B and C
    cd, prec = x.dtype, _prec(x.dtype)
    nc = L // C
    dt = dt.astype(jnp.float32)
    cum = _log_decay_in_chunks(dt, A, C)                       # [B,nc,C,H]
    xdt = (x.astype(jnp.float32) * dt[..., None]).astype(cd)
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]

    def chunks(t):                       # [B, L, ...] -> [nc, B, C, ...]
        return jnp.swapaxes(t.reshape((B, nc, C) + t.shape[2:]), 0, 1)

    def chunk(S, xs):                    # S [B, N, H, P]
        cu, xc, bc, cc = xs              # bc, cc [B, C, G, N]
        g = jnp.moveaxis(jnp.einsum(
            "bign,bjgn->bgij", cc, bc, precision=prec,
            preferred_element_type=jnp.float32), 1, -1)        # [B,i,j,G]
        diff = cu[:, :, None, :] - cu[:, None, :, :]           # [B,i,j,H]
        decay = jnp.where(causal[None, :, :, None],
                          jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        y = jnp.einsum("bijh,bjhp->bihp",
                       (jnp.repeat(g, hg, axis=-1) * decay).astype(cd),
                       xc, precision=prec,
                       preferred_element_type=jnp.float32)
        y = y + jnp.exp(cu)[..., None] * jnp.einsum(
            "bign,bngkp->bigkp", cc, S.astype(cd).reshape(B, N, G, hg, P),
            precision=prec, preferred_element_type=jnp.float32
        ).reshape(B, C, H, P)
        last = cu[:, -1]                                       # [B, H]
        xw = (xc.astype(jnp.float32)
              * jnp.exp(last[:, None, :] - cu)[..., None]).astype(cd)
        # (as a product over a group's flat lanes: the CPU has no bfloat16
        # thunk for the form with heads and channels apart under a batch)
        S = jnp.exp(last)[:, None, :, None] * S + jnp.einsum(
            "bjgn,bjgq->bngq", bc, xw.reshape(B, C, G, hg * P),
            precision=prec,
            preferred_element_type=jnp.float32).reshape(B, N, H, P)
        return S, y

    S, y = jax.lax.scan(
        chunk, jnp.zeros((B, N, H, P), jnp.float32),
        (jnp.swapaxes(cum, 0, 1), chunks(xdt), chunks(Bm), chunks(Cm)))
    return (jnp.swapaxes(y, 0, 1).reshape(B, L, H, P),
            S.reshape(B, N, H * P))


def chunk_scan_supported(x, Bm, C: int) -> bool:
    """bfloat16, pairs of 64-wide heads (one lane tile), a lane-wide
    state, whole grid steps of heads inside each group of ``B`` and ``C``,
    chunks of whole lane tiles."""
    H, G = x.shape[2], Bm.shape[2]
    return (x.dtype == jnp.bfloat16 and Bm.dtype == jnp.bfloat16
            and x.shape[-1] == 64 and Bm.shape[-1] % 128 == 0
            and H % G == 0 and (H // G) % SCAN_HEADS == 0 and C % 128 == 0)


def _chunk_scan_body(x_ref, b_ref, c_ref, col_ref, dtc_ref, row_ref, y_ref,
                     s_out_ref, S, *, C, hb):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        S[...] = jnp.zeros(S.shape, jnp.float32)

    bm, cm = b_ref[0], c_ref[0]                               # [C, N]
    cd = bm.dtype
    g = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [C, C]
    i = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    causal = i >= j
    first = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1) < 64
    col, dtc, row = col_ref[0, 0], dtc_ref[0, 0], row_ref[0, 0]
    for k in range(hb // 2):             # two 64-wide heads a lane tile
        lanes = slice(128 * k, 128 * (k + 1))
        h0, h1 = 2 * k, 2 * k + 1

        def of(a, b):                    # head h0's on its half of the tile
            return jnp.where(first, a, b)

        xd = (x_ref[0, :, lanes].astype(jnp.float32) * of(
            dtc[:, h0:h0 + 1], dtc[:, h1:h1 + 1])).astype(cd)  # [C, 128]
        ys = []
        for h in (h0, h1):
            decay = jnp.where(causal, jnp.exp(jnp.minimum(
                col[:, h:h + 1] - row[h:h + 1, :], 0.0)), 0.0)
            ys.append(jnp.dot((g * decay).astype(cd), xd,
                              preferred_element_type=jnp.float32))
        mine = S[:, lanes]                                    # [N, 128]
        y = of(*ys) + of(jnp.exp(col[:, h0:h0 + 1]),
                         jnp.exp(col[:, h1:h1 + 1])) * jnp.dot(
            cm, mine.astype(cd), preferred_element_type=jnp.float32)
        y_ref[0, :, lanes] = y
        l0, l1 = row[h0:h0 + 1, C - 1:C], row[h1:h1 + 1, C - 1:C]  # [1, 1]
        xw = (xd.astype(jnp.float32)
              * of(jnp.exp(l0 - col[:, h0:h0 + 1]),
                   jnp.exp(l1 - col[:, h1:h1 + 1]))).astype(cd)
        S[:, lanes] = of(jnp.exp(l0), jnp.exp(l1)) * mine \
            + jax.lax.dot_general(bm, xw, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[0] = S[...]


def chunk_scan_kernel(x, dt, A, Bm, Cm, C: int, interpret: bool = False):
    """:func:`ssd_chunk_scan` on the TPU: grid (row, step of
    :data:`SCAN_HEADS` heads, chunk), the chunks of one step's heads in
    order with their state in VMEM. A step's heads lie inside one group of
    ``B`` and ``C``, whose ``C B^T`` of a chunk is computed once a step; a
    head's per-token decay comes in as the cumulative log-decay inside the
    chunk, as a column and as a row (both a few KB, made outside)."""
    B, L, H, P = x.shape
    G, N = Bm.shape[2:]
    hb, nc = SCAN_HEADS, L // C
    ng = H // hb
    per = ng // G                        # grid steps a group of B and C
    dt = dt.astype(jnp.float32)
    cum = _log_decay_in_chunks(dt, A, C).reshape(B, L, ng, hb)
    col = cum.transpose(0, 2, 1, 3)                           # [B,ng,L,hb]
    tile = pl.BlockSpec((1, C, hb * P), lambda b, g, c: (b, c, g))
    shared = pl.BlockSpec((1, C, N), lambda b, g, c: (b, c, g // per))
    column = pl.BlockSpec((1, 1, C, hb), lambda b, g, c: (b, g, c, 0))
    y, S = pl.pallas_call(
        functools.partial(_chunk_scan_body, C=C, hb=hb),
        grid=(B, ng, nc),
        in_specs=[tile, shared, shared, column, column,
                  pl.BlockSpec((1, 1, hb, C), lambda b, g, c: (b, g, 0, c))],
        out_specs=[tile, pl.BlockSpec((1, N, hb * P),
                                      lambda b, g, c: (b, 0, g))],
        scratch_shapes=[pltpu.VMEM((N, hb * P), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct((B, L, H * P), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, H * P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="ssd_chunk_scan",
    )(x.reshape(B, L, H * P), Bm.reshape(B, L, G * N),
      Cm.reshape(B, L, G * N), col,
      dt.reshape(B, L, ng, hb).transpose(0, 2, 1, 3),
      col.transpose(0, 1, 3, 2))
    return y.reshape(B, L, H, P), S


# -- the decode step ----------------------------------------------------------

def ssd_state_step(S: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
                   Bm: jax.Array, Cm: jax.Array, fold: jax.Array,
                   pos: jax.Array, interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """One token a row against the row's state, LIVE rows only (depth
    above 0). S [B, N, H P] f32 (``S^T``); x [B, H, P]; dt [B, H] f32; A
    [H]; Bm, Cm [B, G, N], ``G`` dividing ``H``; fold [B] bool; pos [B] ->
    (S, y [B, H, P] f32).
    Where ``fold``: ``S = exp(dt A) S + (dt x) B^T``; then ``y = S C``. A
    live row that does not fold (its state already holds this token: the
    step is being computed again) only reads. A free row's state is
    neither read nor written and its ``y`` is zeros."""
    B, H, P = x.shape
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.repeat(jnp.exp(dt * A.astype(f32)), P, axis=1)   # [B, H P]
    xdt = (x.astype(f32) * dt[..., None]).reshape(B, H * P)
    args = (S, decay, xdt, Bm.astype(f32), Cm.astype(f32), fold, pos)
    with jax.named_scope("ssd_decode_step"):
        if (interpret is not None or on_tpu()) \
                and state_step_supported(S, Bm.shape[1]):
            S, y = state_step_kernel(*args, interpret=bool(interpret))
        else:
            S, y = _state_step_xla(*args)
    return S, y.reshape(B, H, P)


def _state_step_xla(S, decay, xdt, Bm, Cm, fold, pos):
    """Slot-blind and masked: for the backends without the kernel."""
    lanes = S.shape[2] // Bm.shape[1]    # of one group of B and C

    def columns(m):                      # [B, G, N] -> [B, N, H P]
        return jnp.repeat(jnp.swapaxes(m, 1, 2), lanes, axis=2)

    new = decay[:, None, :] * S + columns(Bm) * xdt[:, None, :]
    S = jnp.where(fold[:, None, None], new, S)
    y = jnp.sum(S * columns(Cm), axis=1)
    return S, jnp.where((pos > 0)[:, None], y, 0.0)


def _step_lanes(HP: int, G: int) -> Tuple[int, int]:
    """(lanes a grid step of the state step moves, groups of ``B`` and
    ``C`` those lanes span): a block of :data:`STEP_LANES` is whole groups
    or lies inside one."""
    W = min(STEP_LANES, HP)
    return W, max(1, W * G // HP)


def state_step_supported(S, G: int = 1) -> bool:
    """A float32 state of one lane tile of numbers a channel (the
    kernel turns the ``B`` and ``C`` rows into columns through one
    ``[128, 128]`` transpose), whole blocks of lanes, and groups of ``B``
    and ``C`` that are whole lane tiles and divide a block or are divided
    by it."""
    HP = S.shape[2]
    W, _ = _step_lanes(HP, G)
    return (S.dtype == jnp.float32 and S.shape[1] == 128
            and HP % W == 0 and HP % G == 0 and (HP // G) % 128 == 0
            and (W % (HP // G) == 0 or (HP // G) % W == 0))


def _state_step_body(row_ref, act_ref, fold_ref, S_ref, d_ref, x_ref, b_ref,
                     c_ref, y0_ref, S_out, y_out, *, W, lanes):
    del y0_ref
    i = pl.program_id(0)

    @pl.when(act_ref[i] == 1)
    def _():
        fold = fold_ref[i] == 1
        n = S_ref.shape[1]

        def column(ref, g):              # a group's row, along a lane tile
            return jnp.broadcast_to(ref[0, 0, g:g + 1], (128, n)).T

        # B and C of the block's groups as columns [N, 128]
        cols = [(column(b_ref, g), column(c_ref, g))
                for g in range(b_ref.shape[2])]
        for k in range(W // 128):
            bcol, ccol = cols[128 * k // lanes]
            tile = slice(128 * k, 128 * (k + 1))
            mine = S_ref[0, :, tile]                             # [N, 128]
            new = d_ref[0, :, tile] * mine + bcol * x_ref[0, :, tile]
            mine = jnp.where(fold, new, mine)
            S_out[0, :, tile] = mine
            y_out[0, :, tile] = jnp.sum(mine * ccol, axis=0, keepdims=True)


def state_step_kernel(S, decay, xdt, Bm, Cm, fold, pos,
                      interpret: bool = False):
    """:func:`ssd_state_step` on the TPU, in place
    (``input_output_aliases``): grid (live-slot schedule, blocks of
    lanes), all of it element-wise over ``[N, 128]`` tiles; a block of
    lanes is handed the ``B`` and ``C`` rows of the groups it spans; a
    step past the live slots stays on the block it holds and moves
    nothing."""
    B, N, HP = S.shape
    G = Bm.shape[1]
    W, span = _step_lanes(HP, G)
    nj = HP // W
    row, active, _ = live_schedule(pos)
    fold_of = fold.astype(jnp.int32)[row]

    def at(i, j, row, act, fold):
        return row[i], 0, jnp.where(act[i] == 1, j, nj - 1)

    def groups_at(i, j, row, act, fold):
        return row[i], at(i, j, row, act, fold)[2] * G // (nj * span), 0, 0

    state = pl.BlockSpec((1, N, W), at)
    lane_row = pl.BlockSpec((1, 1, W), at)
    shared = pl.BlockSpec((1, 1, span, N), groups_at)
    S, y = pl.pallas_call(
        functools.partial(_state_step_body, W=W, lanes=HP // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, nj),
            in_specs=[state, lane_row, lane_row, shared, shared, lane_row],
            out_specs=[state, lane_row]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, HP), jnp.float32)],
        # operands 0-2 are the prefetched schedule; the state is updated in
        # place and a free row's output stays the zeros it is handed
        input_output_aliases={3: 0, 8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="ssd_state_step",
    )(row, active, fold_of, S, decay[:, None, :], xdt[:, None, :],
      Bm.reshape(B, G // span, span, N), Cm.reshape(B, G // span, span, N),
      jnp.zeros((B, 1, HP), jnp.float32))
    return S, y[:, 0]


# -- the per-channel recurrence (Mamba-1) -------------------------------------
#
# ``h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]``,
# ``y_t[c] = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]``: a decay for every one of
# ``N x C`` numbers a token, so no chunk of it is a matrix product. Both
# kernels hold a row's state ``[N, channels]``, channels along the lanes:
# ``dt_t`` and ``x_t`` are rows as the projections produce them (broadcast
# over the ``N`` sublanes for nothing), ``B_t`` and ``C_t`` are columns.

#: Positions a chunk of the prefill scan (its grid's sequential axis) and
#: channels a block of it. Swept on the chip, PERF.md section 4 (PR 50).
S6_CHUNK = 256
S6_CHANNELS = 1280
#: Positions a turn of the scan kernel's loop writes out one after another
#: (one bfloat16 tile of ``x``; the ``B`` and ``C`` columns of its positions
#: are static lane slices of one ``[N, 16]`` block).
S6_GROUP = 16
#: Channels a grid step of the state step moves (a ``[16, 5120]`` float32
#: block is 320 KB in, as much out, double-buffered).
S6_STEP_LANES = 5120


def s6_chunk_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                  Cm: jax.Array, D: jax.Array,
                  true_len: Optional[jax.Array] = None,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """The per-channel recurrence over fresh contexts. x [B, L, C]; dt [B,
    L, C] float32 (after the softplus); A [N, C] float32 (negative: the
    source's ``A`` transposed); Bm, Cm [B, L, N]; D [C] float32; true_len
    [B] or a scalar (None: ``L``) -> (``y`` [B, L, C] float32, ``h`` [B, N,
    C] float32 AT ``true_len``). ``dt`` is taken as 0 from ``true_len`` on:
    ``exp(0) = 1`` and nothing is added, so a bucket's padding neither
    decays the state nor enters it; its own ``y`` means nothing (the
    kernel leaves zeros for whole chunks of padding and does not compute
    them)."""
    B, L, C = x.shape
    f32 = jnp.float32
    true_len = jnp.broadcast_to(jnp.asarray(
        L if true_len is None else true_len, jnp.int32), (B,))
    dt = jnp.where((jnp.arange(L) < true_len[:, None])[..., None],
                   dt.astype(f32), 0.0)
    args = (x, dt, A.astype(f32), Bm.astype(f32), Cm.astype(f32),
            D.astype(f32))
    with jax.named_scope("s6_scan"):
        if (interpret is not None or on_tpu()) \
                and s6_scan_supported(x, A.shape[0]):
            return s6_scan_kernel(*args, true_len, interpret=bool(interpret))
        return _s6_scan_xla(*args)


def _s6_scan_xla(x, dt, A, Bm, Cm, D):
    """A ``lax.scan`` over positions of the kernel's arithmetic."""
    B, L, C = x.shape
    xf = x.astype(jnp.float32)

    def token(h, xs):                    # h [B, N, C]
        xt, dtt, bt, ct = xs             # [B, C], [B, C], [B, N], [B, N]
        h = jnp.exp(dtt[:, None, :] * A) * h \
            + bt[:, :, None] * (dtt * xt)[:, None, :]
        return h, jnp.sum(h * ct[:, :, None], axis=1) + D * xt

    h, y = jax.lax.scan(
        token, jnp.zeros((B, A.shape[0], C), jnp.float32),
        tuple(jnp.swapaxes(t, 0, 1) for t in (xf, dt, Bm, Cm)))
    return jnp.swapaxes(y, 0, 1), h


def _s6_blocks(L: int, C: int) -> Tuple[int, int]:
    """(positions a chunk, channels a block) of the scan kernel."""
    return _block(L, S6_CHUNK), _block(C, S6_CHANNELS)


def s6_scan_supported(x, N: int) -> bool:
    """Whole sublane tiles of state numbers, whole lane tiles of channels
    a block, chunks of whole groups of positions."""
    T, bc = _s6_blocks(x.shape[1], x.shape[2])
    return N % 8 == 0 and bc % 128 == 0 and T % S6_GROUP == 0


def _s6_scan_body(len_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref,
                  h_out_ref, h_ref, *, T):
    b, c = pl.program_id(0), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        h_ref[...] = jnp.zeros(h_ref.shape, jnp.float32)

    @pl.when(c * T >= len_ref[b])        # a whole chunk of padding
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)

    @pl.when(c * T < len_ref[b])
    def _():
        A, skip = a_ref[...], d_ref[...]                  # [N, bc], [1, bc]

        def group(g, h):                 # S6_GROUP positions, in order
            rows = pl.ds(pl.multiple_of(g * S6_GROUP, S6_GROUP), S6_GROUP)
            dts = dt_ref[0, rows, :]                      # [16, bc]
            xs = x_ref[0, rows, :].astype(jnp.float32)
            cols_b, cols_c = b_ref[0, g], c_ref[0, g]     # [N, 16]
            ys = []
            for t in range(S6_GROUP):
                dt, xt = dts[t:t + 1], xs[t:t + 1]        # [1, bc]
                h = jnp.exp(dt * A) * h + cols_b[:, t:t + 1] * (dt * xt)
                ys.append(jnp.sum(h * cols_c[:, t:t + 1], axis=0,
                                  keepdims=True))
            y_ref[0, rows, :] = jnp.concatenate(ys, axis=0) + skip * xs
            return h

        h_ref[...] = jax.lax.fori_loop(0, T // S6_GROUP, group, h_ref[...])

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        h_out_ref[0] = h_ref[...]


def s6_scan_kernel(x, dt, A, Bm, Cm, D, true_len, interpret: bool = False):
    """:func:`s6_chunk_scan` on the TPU, ``s6_chunk_scan``: grid (row,
    block of channels, chunk of positions), the chunks of one block in
    order with its state ``[N, channels]`` in VMEM, written to HBM once,
    after the last. ``B`` and ``C`` come in as ``[L / 16, N, 16]``: the
    column of a position is a static lane slice of its group's block (a
    few MB in HBM as the TPU pads it; ``x``, ``dt`` and ``y`` are hundreds).
    A chunk that starts at or past ``true_len`` is not computed."""
    B, L, C = x.shape
    N = A.shape[0]
    T, bc = _s6_blocks(L, C)
    tile = pl.BlockSpec((1, T, bc), lambda b, j, c, n: (b, c, j))
    cols = pl.BlockSpec((1, T // S6_GROUP, N, S6_GROUP),
                        lambda b, j, c, n: (b, c, 0, 0))

    def columns(m):                      # [B, L, N] -> [B, L / 16, N, 16]
        return jnp.swapaxes(m.reshape(B, L // S6_GROUP, S6_GROUP, N), 2, 3)

    y, h = pl.pallas_call(
        functools.partial(_s6_scan_body, T=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, C // bc, L // T),
            in_specs=[tile, tile,
                      pl.BlockSpec((N, bc), lambda b, j, c, n: (0, j)),
                      cols, cols,
                      pl.BlockSpec((1, bc), lambda b, j, c, n: (0, j))],
            out_specs=[tile, pl.BlockSpec((1, N, bc),
                                          lambda b, j, c, n: (b, 0, j))],
            scratch_shapes=[pltpu.VMEM((N, bc), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, L, C), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="s6_chunk_scan",
    )(true_len, x, dt, A, columns(Bm), columns(Cm), D[None, :])
    return y, h


def s6_state_step(S: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
                  Bm: jax.Array, Cm: jax.Array, D: jax.Array,
                  fold: jax.Array, pos: jax.Array,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """One token a row against the row's state, LIVE rows only (depth
    above 0). S [B, N, C] f32; x [B, C]; dt [B, C] f32 (after the
    softplus); A [N, C] f32; Bm, Cm [B, N]; D [C] f32; fold [B] bool; pos
    [B] -> (S, y [B, C] f32). Where ``fold``: ``S = exp(dt A) S + B (dt
    x)``; then ``y = sum_n S C + D x``. A live row that does not fold (its
    state already holds this token: the step is being computed again) only
    reads. A free row's state is neither read nor written and its ``y``
    is zeros."""
    f32 = jnp.float32
    args = (S, x.astype(f32), dt.astype(f32), A.astype(f32), Bm.astype(f32),
            Cm.astype(f32), D.astype(f32), fold, pos)
    with jax.named_scope("s6_state_step"):
        if (interpret is not None or on_tpu()) and s6_step_supported(S):
            return s6_step_kernel(*args, interpret=bool(interpret))
        return _s6_step_xla(*args)


def _s6_step_xla(S, x, dt, A, Bm, Cm, D, fold, pos):
    """Slot-blind and masked: for the backends without the kernel."""
    new = jnp.exp(dt[:, None, :] * A) * S \
        + Bm[:, :, None] * (dt * x)[:, None, :]
    S = jnp.where(fold[:, None, None], new, S)
    y = jnp.sum(S * Cm[:, :, None], axis=1) + D * x
    return S, jnp.where((pos > 0)[:, None], y, 0.0)


def s6_step_supported(S) -> bool:
    """A float32 state of whole sublane tiles of numbers a channel and
    whole blocks of whole lane tiles of channels."""
    N, C = S.shape[1:]
    W = _block(C, S6_STEP_LANES)
    return S.dtype == jnp.float32 and N % 8 == 0 and W % 128 == 0


def _s6_step_body(row_ref, act_ref, fold_ref, S_ref, x_ref, dt_ref, a_ref,
                  b_ref, c_ref, d_ref, y0_ref, S_out, y_out):
    del y0_ref
    i = pl.program_id(0)

    @pl.when(act_ref[i] == 1)
    def _():
        mine, dt, xt = S_ref[0], dt_ref[0], x_ref[0]   # [N, W], [1, W] x 2
        N, W = mine.shape
        reps = W // 128

        def column(ref):                 # [N, 128], each lane the same
            return jnp.tile(ref[0], (1, reps))

        new = jnp.exp(dt * a_ref[...]) * mine + column(b_ref) * (dt * xt)
        mine = jnp.where(fold_ref[i] == 1, new, mine)
        S_out[0] = mine
        y_out[0] = jnp.sum(mine * column(c_ref), axis=0, keepdims=True) \
            + d_ref[...] * xt


def s6_step_kernel(S, x, dt, A, Bm, Cm, D, fold, pos,
                   interpret: bool = False):
    """:func:`s6_state_step` on the TPU, ``s6_state_step``, in place
    (``input_output_aliases``): grid (live-slot schedule, blocks of
    channels), element-wise over ``[N, channels]``; the decay is computed
    here (it is as large as the state: made outside it would be read from
    HBM beside it); ``B`` and ``C`` come in as columns, each number along
    a lane tile; a step past the live slots stays on the block it holds
    and moves nothing."""
    B, N, C = S.shape
    W = _block(C, S6_STEP_LANES)
    nj = C // W
    row, active, _ = live_schedule(pos)
    fold_of = fold.astype(jnp.int32)[row]

    def at(i, j, row, act, fold):
        return row[i], 0, jnp.where(act[i] == 1, j, nj - 1)

    def shared(i, j, row, act, fold):
        return 0, at(i, j, row, act, fold)[2]

    state = pl.BlockSpec((1, N, W), at)
    lane_row = pl.BlockSpec((1, 1, W), at)
    column = pl.BlockSpec((1, N, 128),
                          lambda i, j, row, act, fold: (row[i], 0, 0))

    def columns(m):                      # [B, N] -> [B, N, 128]
        return jnp.broadcast_to(m[:, :, None], (B, N, 128))

    S, y = pl.pallas_call(
        _s6_step_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, nj),
            in_specs=[state, lane_row, lane_row,
                      pl.BlockSpec((N, W), shared), column, column,
                      pl.BlockSpec((1, W), shared), lane_row],
            out_specs=[state, lane_row]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, C), jnp.float32)],
        # operands 0-2 are the prefetched schedule; the state is updated in
        # place and a free row's output stays the zeros it is handed
        input_output_aliases={3: 0, 10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="s6_state_step",
    )(row, active, fold_of, S, x[:, None, :], dt[:, None, :], A,
      columns(Bm), columns(Cm), D[None, :],
      jnp.zeros((B, 1, C), jnp.float32))
    return S, y[:, 0]
