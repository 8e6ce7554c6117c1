"""Pallas TPU one-token KV-cache write: each row's new K (or V) lands
at that row's own position, in place.

The decode step appends ONE token per row to a ``[B, T, nk, dh]``
cache leaf, every row at a different depth. The XLA form
(models/transformer.py: ``vmap(dynamic_update_slice)``) lowers on TPU
to a scatter loop over the rows, and each iteration's one-position
update costs about 8 us there (130 us a leaf at 16 rows, 72 leaves a
GPT-2-large step: 8.5 of its 19 ms; PERF.md section 6, PR 26) —
because of where the bytes live. For ``dh`` that is not a multiple of
the 128 lanes the TPU keeps such an array with T MINOR
(``{1,3,2,0:T(8,128)}``: the layout the attention's q.K^T wants), so
one position is one lane of ``nk * dh / 8`` tiles, not a contiguous
row.

This kernel works WITH that layout: it takes the leaf as
``[B, nk, dh, T]`` (for a T-minor buffer the transpose is a bitcast;
tests/test_tpu_compile.py pins that no whole-leaf copy appears), and
for each row reads the one 128-lane block that holds the row's
position, replaces that lane and writes the block back through
``input_output_aliases`` — the rest of the leaf is never touched.
About 1 us a row on a v5e (16 us a leaf at 16 rows, my chip run,
PR 26).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: One [nk, dh, LANES] block is double-buffered in and out: four of
#: them must sit well inside the 16 MiB of scoped VMEM.
_MAX_BLOCK_BYTES = 2 * 1024 * 1024


def supported(shape, dtype) -> bool:
    """Shapes the kernel takes: a 4-d float leaf whose T is whole
    lane blocks and whose ``dh`` is NOT (then the TPU keeps T minor
    and the transposed view is free; with ``dh`` a lane multiple the
    array is row-major already and a row write is contiguous — XLA's
    own update is the right form there)."""
    if len(shape) != 4:
        return False
    _, T, nk, dh = shape
    dtype = jnp.dtype(dtype)
    return (dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and T % LANES == 0 and dh % LANES != 0
            and nk * dh * LANES * dtype.itemsize <= _MAX_BLOCK_BYTES)


def use_token_write(shape, dtype, mesh=None) -> bool:
    """The dispatch gate (the shape of ops.flash_attention.use_flash):
    TPU backend, a supported leaf, and no multi-device mesh — the
    Mosaic call has no partitioning rule, so a GSPMD-partitioned
    (tensor-parallel) cache keeps the XLA form."""
    return (jax.default_backend() == "tpu"
            and (mesh is None or mesh.size == 1)
            and supported(shape, dtype))


def _kernel(pos_ref, new_ref, buf_ref, out_ref):
    lane = pos_ref[pl.program_id(0)] % LANES
    col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 3)
    old = buf_ref[...]
    out_ref[...] = jnp.where(col == lane,
                             jnp.broadcast_to(new_ref[...], old.shape),
                             old)


def token_write(buf: jax.Array, new: jax.Array, start: jax.Array,
                interpret: Optional[bool] = None) -> jax.Array:
    """``buf`` [B, T, nk, dh] with ``new`` [B, 1, nk, dh] written at
    ``(b, start[b])`` — ``vmap(dynamic_update_slice)``'s result
    (starts clamp into the buffer the same way), updated in place when
    the caller donates ``buf``. ``interpret=None`` picks by backend."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _token_write(buf, new, start, interpret)


# Jitted on its own so that the 72 calls of a 36-layer step trace and
# lower the kernel ONCE: lowering it 72 times is 2.5 s of such a
# step's 5 s, paid at every process start whatever the compile cache
# holds.
@functools.partial(jax.jit, static_argnums=(3,))
def _token_write(buf, new, start, interpret):
    B, T, nk, dh = buf.shape
    start = jnp.clip(start.astype(jnp.int32), 0, T - 1)
    block = pl.BlockSpec((1, nk, dh, LANES),
                         lambda b, pos: (b, 0, 0, pos[b] // LANES))
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[pl.BlockSpec((1, nk, dh, 1),
                                   lambda b, pos: (b, 0, 0, 0)),
                      block],
            out_specs=block),
        out_shape=jax.ShapeDtypeStruct((B, nk, dh, T), buf.dtype),
        # Operand 0 is the scalar-prefetched ``start``.
        input_output_aliases={2: 0},
        interpret=interpret, name="kv_token_write",
    )(start, jnp.transpose(new, (0, 2, 3, 1)).astype(buf.dtype),
      jnp.transpose(buf, (0, 2, 3, 1)))
    return jnp.transpose(out, (0, 3, 1, 2))
