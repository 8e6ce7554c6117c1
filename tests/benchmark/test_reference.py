"""The plain reference against the package's model at tiny size on the
CPU, on the benchmark's own weights; and the control: the reference below
the stated precision moves the compared numbers far more than the
configuration's own precision does."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import common, loader
from harness.train_runner import worst_leaf_gap

gpt2 = loader.load_model("gpt2")

SIZES = dict(vocab_size=64, n_positions=64, n_embd=32, n_layer=2,
             n_head=4, n_inner=64)


@pytest.fixture(scope="module")
def setup():
    from tensorflow_distributed_tpu.models.transformer import gpt_lm
    key = common.root_key(2 ** 31 + 3)
    program = jax.jit(lambda k: gpt2.make_params(k, SIZES))(key)
    stacked = jax.jit(
        lambda k: gpt2.make_params(k, SIZES, stacked=True))(key)
    model = gpt_lm(None, size="tiny", tie_embeddings=True,
                   dropout_rate=0.0, compute_dtype=jnp.float32, max_len=64)
    rng = np.random.default_rng(0)
    seq = jnp.asarray(rng.integers(0, 64, (4, 65)), jnp.int32)
    batch = {"tokens": seq[:, :-1], "targets": seq[:, 1:],
             "mask": jnp.ones((4, 64), jnp.float32)}
    return model, program, stacked, batch


def test_layouts_hold_the_same_numbers(setup):
    _, program, stacked, _ = setup
    again = gpt2.stack_like_reference(program, SIZES["n_layer"])
    same = jax.tree_util.tree_map(
        lambda a, b: bool((a == b).all()), again, stacked)
    assert all(jax.tree_util.tree_leaves(same))


def test_every_leaf_is_random(setup):
    _, program, _, _ = setup
    for path, leaf in jax.tree_util.tree_leaves_with_path(program):
        assert float(jnp.std(leaf)) > 0.01, jax.tree_util.keystr(path)


def test_logits_agree_with_the_package(setup):
    model, program, stacked, batch = setup
    got = model.apply({"params": program}, batch["tokens"], train=False)
    want = gpt2.logits_fn(stacked, batch["tokens"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_loss_and_gradient_agree_with_the_package(setup):
    model, program, stacked, batch = setup
    from tensorflow_distributed_tpu.train.tasks import mlm_loss

    def package_loss(p):
        return mlm_loss(model.apply, p, {}, batch, None, False)[0]

    loss, grads = jax.value_and_grad(package_loss)(program)
    ref_loss, ref_grads = jax.value_and_grad(gpt2.loss_fn)(
        stacked, batch)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    got = gpt2.stack_like_reference(grads, SIZES["n_layer"])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=2e-3,
            err_msg=jax.tree_util.keystr(path))


def test_control_below_stated_precision_is_far_off(setup):
    """fp8 operands (the nearest precision below bf16) must move the
    gradient norms at least three times as far from the f32 reference as
    bf16 operands (the stated precision) do."""
    _, _, _, batch = setup
    key = common.root_key(5)
    make = lambda: jax.jit(  # noqa: E731
        lambda k: gpt2.make_params(k, SIZES, stacked=True))(key)
    runs = {p: gpt2.follow_training(make, [batch] * 3, 3e-4, precision=p)
            for p in gpt2.PRECISIONS}
    want = runs["f32"]["grad_norms"]
    assert "layer_1/attn/out/bias" in want and "ln_f/scale" in want
    gap = {p: worst_leaf_gap(runs[p]["grad_norms"], want)
           for p in ("bf16", "fp8")}
    assert gap["fp8"] > 3 * gap["bf16"], gap
    assert gap["fp8"] > 0.01, gap
    assert runs["f32"]["losses"][-1] < runs["f32"]["losses"][0]


def test_worst_leaf_gap_uses_the_median_floor():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "c": 1e-3}
    # c's own norm is all but zero: its gap is held against the median, 1.0
    assert worst_leaf_gap(prog, ref) == pytest.approx(0.1)
    with pytest.raises(ValueError):
        worst_leaf_gap({"a": 1.0}, ref)


def test_served_token_gaps_and_the_control(setup):
    _, _, stacked, batch = setup
    seqs = batch["tokens"]
    gap, top = gpt2.served_token_gaps(stacked, seqs[:2])
    assert gap.shape == (2, 63) and float(gap.min()) >= 0
    # the reference's own greedy token has no gap
    own = gpt2.gaps_of(stacked, seqs[:2], top)
    assert float(jnp.abs(own).max()) == 0.0
    _, low = gpt2.served_token_gaps(stacked, seqs[:2], "fp8")
    assert float(gpt2.gaps_of(stacked, seqs[:2], low).max()) > 0


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in sorted(
            (jax.tree_util.keystr(p), x) for p, x
            in jax.tree_util.tree_leaves_with_path(tree)):
        a = np.asarray(leaf)
        for part in (path, str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed,program,stacked,gap_sum,gap_max,top,fp8", [
    (1, "cd77ce8a9609bf0b24a4b1c475f57b3d66cdd694a4b5534a6684f9b671fba5b5",
     "508dfa671dfb8a7392a1548e896ecdcfe54e7e8acbfe9b79a1fd50a3b91afeb8",
     40.08171463012695, 0.6791044473648071,
     "e7cc3ad7305a7ac6213a6a7db6ed2acd52ab731817a12a9f5f482cebacb4a5c1",
     0.0248497873544693),
    (2, "34390be04724d8926bc30eaf1acaa6ab41e1eb6cd6f5f4bd8794f41ffd2e2cdc",
     "c7c755161889ca1b8b7d3ca422e43309285e9f84ccf893dbca7321914504eced",
     40.51987838745117, 0.6759393811225891,
     "5556a97f0cbee50b7d09273ca5cc03cbc40eec19730124c47f6d4a2fbd14e89d",
     0.020589277148246765),
])
def test_the_move_changed_no_weight_and_no_gap(seed, program, stacked,
                                               gap_sum, gap_max, top, fp8):
    """Recorded on the CPU from ``harness/weights.py`` and
    ``harness/reference.py`` at PR 26's commit, before PR 27 moved them
    into ``models/gpt2.py``: every weight bit for bit in both layouts, the
    f32 reference's gaps and argmax over a fixed ``seqs``, and the gaps of
    the tokens fp8 operands put first (the control's path)."""
    key = common.root_key(seed)
    assert _digest(jax.jit(
        lambda k: gpt2.make_params(k, SIZES))(key)) == program
    params = jax.jit(lambda k: gpt2.make_params(k, SIZES, stacked=True))(key)
    assert _digest(params) == stacked
    seqs = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 64)),
                       jnp.int32)
    gap, best = gpt2.served_token_gaps(params, seqs)
    assert float(gap.sum()) == pytest.approx(gap_sum, rel=1e-6)
    assert float(gap.max()) == pytest.approx(gap_max, rel=1e-6)
    assert hashlib.sha256(np.asarray(best).tobytes()).hexdigest() == top
    _, low = gpt2.served_token_gaps(params, seqs, "fp8")
    assert float(gpt2.gaps_of(params, seqs, low).sum()) == pytest.approx(
        fp8, rel=1e-5)
