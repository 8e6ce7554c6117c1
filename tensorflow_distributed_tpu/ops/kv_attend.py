"""Pallas TPU decode attend over a position-indexed K/V cache: one query
a row over that row's cached keys and values up to its own depth, read
in place.

The decode step of the dense slot engine attends ``[B, T, nk, dh]`` K and
V leaves in which a few of the ``B`` slots are live, each a fraction of
``T`` deep. The XLA form (models/transformer.py: a ``[B, 1, T]`` bias and
``full_attention`` over the whole leaf) reads every position of every
slot whatever is live: 3.0 of the 6.1 GB a GPT-2-large step moves, for
the 7-11% of them a query can see (PERF.md section 6, PR 48).

This kernel reads what is live. It takes the leaves as ``[B, nk, dh, T]``
(the layout the TPU keeps them in when ``dh`` is not a lane multiple:
ops/kv_write.py's docstring; the transpose is a bitcast) and walks blocks
of whole 128-lane tiles of positions under
``ops.latent_attention.dense_attend_schedule``, the schedule
``%mla_latent_attend_dense`` and ``%gqa_dense_attend`` share: a live row
its own blocks ``0 .. pos // block``, a free row (``pos == 0``) and
every step past a row's depth the block the grid already holds, which
moves nothing. The numerics are ``parallel.ring_attention._block_attend``'s:
operands in the cache's dtype, float32 scores and running softmax, the
probabilities rounded to the cache's dtype for the weighted sum, float32
accumulation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflow_distributed_tpu.ops import kv_write
from tensorflow_distributed_tpu.ops.latent_attention import (
    NEG, _prec, dense_attend_schedule, dense_attend_visits)

LANES = kv_write.LANES
#: Cached positions a block. Swept on the chip, PR 48 (PERF.md section 6;
#: GPT-2 large's leaves, 16 slots of 1,024, the call alone with its
#: schedule; XLA's attend over the whole leaves 124.9 us): 5 live rows
#: 128 positions 35.0 us, 256 30.2, 512 34.3, 1,024 52.1; 16 live rows
#: 66.1, 65.1, 86.8, 118.5. A step of the grid that moves nothing still
#: costs its turn, a block past a row's depth its bytes.
BLOCK_T = 256
#: A group's queries are the rows of one product: padded to a float32
#: sublane tile.
QUERY_ROWS = 8
#: One [nk, dh, block] block of K and one of V, each double-buffered.
_MAX_BLOCK_BYTES = 2 * 1024 * 1024


def block(shape, dtype) -> int:
    """Cached positions a block of the attend over a ``[B, T, nk, dh]``
    leaf: the kernel's, and the unit its visits are counted in on every
    backend. The most whole lane tiles that divide ``T``, up to
    ``BLOCK_T`` and the VMEM a block may take."""
    _, T, nk, dh = shape
    most = min(T, BLOCK_T, _MAX_BLOCK_BYTES
               // (nk * dh * jnp.dtype(dtype).itemsize))
    return max((b for b in range(LANES, most + 1, LANES) if T % b == 0),
               default=0)


def supported(shape, dtype) -> bool:
    """The leaves ``ops.kv_write`` writes (a 4-d float leaf the TPU
    keeps T-minor, ``T`` whole lane tiles) of which a block fits."""
    return kv_write.supported(shape, dtype) and block(shape, dtype) > 0


def use_kv_attend(shape, dtype, mesh=None) -> bool:
    """The dispatch gate, ``ops.kv_write.use_token_write``'s: the TPU,
    a supported leaf, no multi-device mesh (the Mosaic call has no
    partitioning rule)."""
    return (jax.default_backend() == "tpu"
            and (mesh is None or mesh.size == 1)
            and supported(shape, dtype))


def visits(pos, shape, dtype):
    """Cached positions the blocks of ONE call cover over all rows, as
    the kernel's grid visits them (``dense_attend_visits`` in this
    kernel's blocks). pos [B] -> int32 scalar."""
    return dense_attend_visits(pos, shape[1], block(shape, dtype))


def _body(pos_ref, row_ref, lo_ref, hi_ref, q_ref, k_ref, v_ref, out_ref,
          m_ref, l_ref, acc_ref, *, scale, bt):
    b, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when((pos > 0) & (j * bt <= pos))
    def _():
        k, v = k_ref[0], v_ref[0]                            # [nk, dh, bt]
        prec = _prec(k.dtype)
        s = jax.lax.dot_general(                             # [nk, g, bt]
            q_ref[0], k, (((2,), (1,)), ((0,), (0,))), precision=prec,
            preferred_element_type=jnp.float32)
        col = j * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(col <= pos, s * scale, NEG)
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_ref[...] - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (2,)), ((0,), (0,))),
            precision=prec, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def decode_attend(q: jax.Array, kc: jax.Array, vc: jax.Array,
                  pos: jax.Array, v_new: jax.Array,
                  interpret: Optional[bool] = None) -> jax.Array:
    """One query a row over its row's cache to its own depth: q [B, 1,
    h, dh], kc and vc [B, T, nk, dh] AFTER the step's write (``h`` a
    multiple of ``nk``: query head ``i`` reads key-value head ``i // (h
    // nk)``), pos [B] the position the step wrote, v_new [B, 1, nk, dh]
    what it wrote there -> [B, 1, h, dh] in q's dtype: the softmax over
    positions ``<= pos[b]`` of ``q . k / sqrt(dh)`` times ``v``,
    ``full_attention`` under the position mask. A row at position 0 sees
    one key, so its result is ``v_new``, and none of its cache row is
    read: the slot engine's free slots (``SlotDecodeEngine.free`` zeroes
    their position) cost no bytes. ``interpret=None`` picks by
    backend."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _decode_attend(q, kc, vc, pos, v_new, interpret)


# Jitted on its own, as ops.kv_write._token_write is: a 36-layer step
# traces and lowers the kernel once.
@functools.partial(jax.jit, static_argnums=(5,))
def _decode_attend(q, kc, vc, pos, v_new, interpret):
    B, T, nk, dh = kc.shape
    h = q.shape[2]
    g = h // nk
    rows = -(-g // QUERY_ROWS) * QUERY_ROWS
    bt = block(kc.shape, kc.dtype)
    pos = jnp.clip(pos.astype(jnp.int32), 0, T - 1)
    row, lo, hi = dense_attend_schedule(pos, bt)
    qg = jnp.pad(q.reshape(B, nk, g, dh).astype(kc.dtype),
                 ((0, 0), (0, 0), (0, rows - g), (0, 0)))
    cached = pl.BlockSpec(
        (1, nk, dh, bt), lambda b, j, pos, row, lo, hi: (
            row[b], 0, 0, jnp.clip(j, lo[b], hi[b])))
    mine = pl.BlockSpec((1, nk, rows, dh), lambda b, j, *_: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_body, scale=dh ** -0.5, bt=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B, T // bt),
            in_specs=[mine, cached, cached], out_specs=mine,
            scratch_shapes=[pltpu.VMEM((nk, rows, 1), jnp.float32),
                            pltpu.VMEM((nk, rows, 1), jnp.float32),
                            pltpu.VMEM((nk, rows, dh), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, nk, rows, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="kv_decode_attend",
    )(pos, row, lo, hi, qg, jnp.transpose(kc, (0, 2, 3, 1)),
      jnp.transpose(vc, (0, 2, 3, 1)))
    out = out[:, :, :g].reshape(B, 1, h, dh)
    first = jnp.repeat(v_new.astype(vc.dtype), g, axis=2)
    return jnp.where((pos == 0)[:, None, None, None], first.astype(q.dtype),
                     out.astype(q.dtype))
