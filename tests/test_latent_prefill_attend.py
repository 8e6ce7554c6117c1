"""The latent family's fused prefill attend (PR 38): the flash forward
kernel of ``ops/flash_attention.py`` given a value width of its own, an
explicit softmax scale and an optional selection operand, as
``ops/latent_attention.py::prefill_attend`` calls it on the TPU
(``%mla_prefill_attend``), here under Pallas ``interpret=True`` at toy
sizes: against the XLA loop it stands in for and against a plain float32
softmax attention; the grid's index map and predicate walked step by
step; and what the run's ``start`` record says of it.

Float32 operands go through ``_fwd`` itself (the wrapper takes bfloat16
alone: on the chip a float32 product in the kernel would not be the XLA
loop's ``HIGHEST`` one), so the tolerance there is summation order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_distributed_tpu.ops import flash_attention as F
from tensorflow_distributed_tpu.ops import latent_attention as L


def _operands(H, n, dq, dv, dtype, seed=0):
    k = jax.random.PRNGKey(seed)
    q, kk = (jax.random.normal(jax.random.fold_in(k, i), (H, n, dq), dtype)
             for i in (0, 1))
    return q, kk, jax.random.normal(jax.random.fold_in(k, 2), (H, n, dv),
                                    dtype)


def _causal(n):
    return jnp.tril(jnp.ones((n, n), bool))


def _selection(n, share, seed=7, nothing_in=0):
    """A random causal selection that always keeps the diagonal; rows
    from ``nothing_in`` on keep NOTHING of the first ``nothing_in``
    keys (so a whole first key block is empty for them)."""
    keep = (jax.random.uniform(jax.random.PRNGKey(seed), (n, n)) < share
            ) | jnp.eye(n, dtype=bool)
    keep = keep & _causal(n)
    if nothing_in:
        keep = keep.at[nothing_in:, :nothing_in].set(False)
    return keep


def _plain(q, k, v, mask, scale):
    """Float32 softmax attention, nothing blocked."""
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=hi) * scale
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
    return jnp.einsum("hqk,hkd->hqd", p, v.astype(jnp.float32),
                      precision=hi)


def _kernel_f32(q, k, v, keep, scale, bq, bk):
    n = q.shape[1]
    plan = F.flash_plan(n, n, q.shape[2], q.dtype, causal=True,
                        Dv=v.shape[2], block_q=bq, block_k=bk)
    assert plan is not None and plan[:2] == (min(bq, n), min(bk, n))
    return F._fwd(q, k, v, causal=True, plan=plan, interpret=True,
                  keep=None if keep is None else keep.astype(jnp.int8),
                  scale=scale, stats=False, name="mla_prefill_attend")[0]


# (n, dq, dv, block_q, block_k, selection): A.X-K1's widths (192 is no
# lane multiple, the values narrower) and GLM's; the diagonal inside a
# block both ways round (block_q under and over block_k) and on blocks'
# edges (block_q == block_k, as the chip runs it: a block on the diagonal
# is taken whole under the positions' compare); one grid step a head,
# whole and in tiles (a short context: every offset static).
CASES = {
    "axk1_widths_causal": (384, 192, 128, 64, 128, None),
    "axk1_widths_wide_q_blocks": (384, 192, 128, 128, 64, None),
    "axk1_widths_square_blocks": (512, 192, 128, 128, 128, None),
    "axk1_widths_two_blocks_of_512": (1024, 192, 128, 512, 512, None),
    "glm_widths_selection": (384, 256, 256, 64, 128, 0.08),
    "glm_widths_selection_wide_q": (256, 256, 256, 128, 64, 0.3),
    "glm_widths_selection_square_blocks": (384, 256, 256, 128, 128, 0.1),
    "glm_widths_selection_two_blocks_of_512": (1024, 256, 256, 512, 512,
                                               0.05),
    "one_step": (128, 24, 8, 128, 128, None),
    "one_step_selection": (128, 24, 8, 128, 128, 0.2),
    "one_step_in_tiles_selection": (512, 192, 128, 512, 512, 0.1),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_the_xla_loop_and_a_plain_softmax_in_float32(
        case, monkeypatch):
    """Several blocks with the diagonal inside one, ``Dv != D``, a width
    that is no lane multiple, a scale that is not ``1 / sqrt(D)``; under
    a selection, rows that keep fewer keys than a block holds and rows
    that keep NOTHING of their first key block (their probabilities
    there are 0, not ``exp(NEG - NEG)``)."""
    n, dq, dv, bq, bk, share = CASES[case]
    q, k, v = _operands(3, n, dq, dv, jnp.float32)
    keep = None if share is None else _selection(
        n, share, nothing_in=min(bk, 128))
    got = _kernel_f32(q, k, v, keep, 0.173, bq, bk)
    assert got.shape == (3, n, dv) and got.dtype == jnp.float32
    mask = _causal(n) if keep is None else keep
    np.testing.assert_allclose(got, _plain(q, k, v, mask, 0.173),
                               atol=6e-6, rtol=0)
    monkeypatch.setattr(L, "ATTEND_BLOCK_Q", 32)
    monkeypatch.setattr(L, "ATTEND_BLOCK_K", 64)
    np.testing.assert_allclose(
        got, L.prefill_attend_xla(q, k, v, keep, 0.173), atol=6e-6, rtol=0)


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "selection"])
def test_the_wrapper_in_bfloat16_is_the_xla_loop_to_a_rounding(masked):
    """``prefill_attend_kernel`` as ``prefill_attend`` calls it on the
    TPU: bfloat16 in and out, the selection handed over as it comes
    (bool), the blocks ``prefill_attend_plan`` picks: 1,024 by 1,024 at
    2,048 positions, a grid of 2 x 2 steps a head of which 3 compute.
    Both forms round a probability to bfloat16 against another running
    maximum, so they differ by roundings of the result (2^-7 of a value
    near 1), and each is that close to the float32 softmax of the same
    operands."""
    n = 2048
    q, k, v = _operands(2, n, 192, 128, jnp.bfloat16, seed=3)
    keep = _selection(n, 0.02, nothing_in=1024) if masked else None
    plan = L.prefill_attend_plan(n, 192, 128, jnp.bfloat16)
    assert plan == F.FlashPlan(1024, 1024, 1024, 1024, 4, 3, 2)
    got = L.prefill_attend_kernel(q, k, v, keep, 0.07, interpret=True)
    assert got.dtype == jnp.bfloat16 and got.shape == (2, n, 128)
    want = _plain(q, k, v, _causal(n) if keep is None else keep, 0.07)
    xla = L.prefill_attend_xla(q, k, v, keep, 0.07)
    for a, b in ((got, want), (got, xla), (xla, want)):
        assert float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))) < 0.02


def test_off_the_tpu_prefill_attend_is_the_xla_loop(monkeypatch):
    """No flag chooses: the backend and the shapes do. On the CPU the
    dispatcher gives the loop's result bit for bit (a Mosaic kernel does
    not lower there), and the plan refuses what the kernel does not
    take: float32 operands (its products would not be ``HIGHEST``), a
    length past 1,024 that 1,024 does not divide, widths past 256."""
    q, k, v = _operands(2, 256, 24, 8, jnp.bfloat16)
    assert not L.on_tpu()
    np.testing.assert_array_equal(
        np.asarray(L.prefill_attend(q, k, v, None, 0.3), np.float32),
        np.asarray(L.prefill_attend_xla(q, k, v, None, 0.3), np.float32))
    for n in (2048, 8192, 14336):
        assert L.prefill_attend_plan(n, 192, 128, jnp.bfloat16)[:2] == (
            1024, 1024)
    assert L.prefill_attend_plan(8192, 192, 128, jnp.float32) is None
    assert L.prefill_attend_plan(8192 + 512, 192, 128, jnp.bfloat16) is None
    assert L.prefill_attend_plan(8192, 320, 128, jnp.bfloat16) is None


@pytest.mark.parametrize("n, bq, bk", [
    (8192, 1024, 1024), (4096, 512, 512), (3072, 1024, 512),
    (14336, 1024, 1024), (2048, 256, 1024)])
def test_no_block_past_the_diagonal_is_fetched_or_computed(n, bq, bk):
    """Walk the kernel's (q block, k block) grid as the chip does, with
    the index map the K / V blocks and the selection's tile are fetched
    by and the predicate the body runs under: a step computes iff its
    key block holds a key at or below one of its queries; every step
    holds a block at or before the diagonal, so none past it is ever
    fetched, and a skipped step holds the block the step before it held
    (no copy is issued); the computed steps number what the plan counts
    and the ``start`` record reports."""
    plan = F.flash_plan(n, n, 192, jnp.bfloat16, causal=True, Dv=128,
                        block_q=bq, block_k=bk)
    assert plan[:4] == (bq, bk, bq, bk)         # a tile is a whole block
    lo, hi = F._offsets(True, 0, walk_keys=True)
    held = F._walk_index(bq, bk, n // bk, lo, hi)
    computed, fetched = 0, set()
    for i in range(n // bq):
        last_row, before = (i + 1) * bq - 1, None
        for j in range(n // bk):
            first, _, _, last = F._band(i * bq, bq, bk, lo, hi, j * bk, 1)
            runs = bool(last > first)
            assert runs == (j * bk <= last_row)
            at = int(held(i, j))
            assert at * bk <= last_row
            if runs:
                assert at == j
                computed += 1
            else:
                assert at == before
            fetched.add((i, at))
            before = at
    assert computed == len(fetched) == plan.tiles_computed
    assert plan.tiles_total == (n // bq) * (n // bk)
    said = L.prefill_attend_describe(n, 192, 128, jnp.bfloat16)
    assert said["form"] == "xla"               # here; "kernel" on the TPU
    if (bq, bk) == (1024, 1024):               # what the chip runs
        assert L.prefill_attend_plan(n, 192, 128) == plan


def test_the_start_record_says_which_form_a_bucket_traced(monkeypatch):
    """By bucket: the form, its blocks and ``tiles_computed`` of
    ``tiles_total``. Off the TPU the XLA loop's own blocks and the turns
    its loops make; on it (the backend steered here, nothing lowered)
    the kernel's blocks and the plan's count."""
    off = L.prefill_attend_describe(4096, 192, 128, jnp.bfloat16)
    assert off == {"form": "xla", "block_q": 512, "block_k": 512,
                   "tiles_total": 64, "tiles_computed": 36,
                   "computed_share": 36 / 64}
    monkeypatch.setattr(L, "on_tpu", lambda: True)
    on = L.prefill_attend_describe(4096, 192, 128, jnp.bfloat16)
    plan = L.prefill_attend_plan(4096, 192, 128, jnp.bfloat16)
    assert on == {"form": "kernel", "block_q": plan.block_q,
                  "block_k": plan.block_k,
                  "tiles_total": plan.tiles_total,
                  "tiles_computed": plan.tiles_computed,
                  "computed_share": plan.tiles_computed / plan.tiles_total}
    assert 0.5 < on["computed_share"] < 0.7
    # float32 serving (the CPU tests) stays on the loop even there
    assert L.prefill_attend_describe(
        4096, 192, 128, jnp.float32)["form"] == "xla"


def test_the_train_kernels_are_the_ones_they_were():
    """The forward the training cells run (``D == Dv``, one grid step a
    head, no selection, ``1 / sqrt(D)``) takes none of what the prefill
    added: its plan is the one it was, it still returns the row
    statistics its backward reads, and its jaxpr names no selection
    operand."""
    plan = F.flash_plan(1024, 1024, 64, jnp.bfloat16, causal=True)
    assert plan == F.FlashPlan(1024, 1024, 256, 256, 16, 10, 4)
    q = jnp.zeros((2, 1024, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q: F._fwd(
        q, q, q, causal=True, plan=plan, interpret=True))(q)
    (call,) = [e for e in jaxpr.eqns[0].params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    assert len(call.invars) == 3
    assert [v.aval.shape for v in call.outvars] == [
        (2, 1024, 64), (2, 1024, 8)]
    assert call.params["name"] == "flash_fwd"
    assert not call.params["compiler_params"]     # Mosaic's defaults


def test_a_name_only_names_and_the_vmem_limit_follows_the_blocks():
    """``name`` names the call and nothing else: a named forward still
    returns its row statistics unless ``stats=False`` says otherwise.
    The VMEM limit is worked out from the plan's blocks: none (Mosaic's
    default) for the training cells' step of 256-tiles, half again of
    the 18 MiB a [1024, 1024] step holds at GLM's widths under a
    selection (two more MiB for the int8 tile's two buffers than
    without), 14.5 MiB at A.X-K1's."""
    q, k, v = _operands(1, 256, 24, 8, jnp.float32)
    plan = F.flash_plan(256, 256, 24, q.dtype, causal=True, Dv=8,
                        block_q=128, block_k=128)
    o, lse = F._fwd(q, k, v, causal=True, plan=plan, interpret=True,
                    name="some_name")
    (alone,) = F._fwd(q, k, v, causal=True, plan=plan, interpret=True,
                      stats=False)
    np.testing.assert_array_equal(o, alone)
    assert lse.shape == (1, 256, 8)
    train = F.flash_plan(1024, 1024, 64, jnp.bfloat16, causal=True)
    assert F._fwd_vmem_limit(train, 64, 64, 2, False) is None
    mib = 1 << 20
    glm = L.prefill_attend_plan(14336, 256, 256)
    assert F._fwd_vmem_limit(glm, 256, 256, 2, True) == 27 * mib
    assert F._fwd_vmem_limit(glm, 256, 256, 2, False) == 24 * mib
    axk1 = L.prefill_attend_plan(8192, 192, 128)
    assert F._fwd_vmem_limit(axk1, 192, 128, 2, False) == 87 * mib // 4
