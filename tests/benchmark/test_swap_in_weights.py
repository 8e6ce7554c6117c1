"""``probes.swap_in_weights`` (PR 32) never holds two whole copies of the
parameters: it takes shapes, dtypes and shardings from the program's tree,
frees the program's buffers, then makes the benchmark's. What comes out is
laid out and placed as what went in and holds, for a seed, the numbers
``model.make_params`` gives; a leaf the two sides do not share is refused
by name while the program's tree is still whole."""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harness import common, loader, probes

gpt2 = loader.load_model("gpt2")
SIZES = dict(vocab_size=64, n_positions=64, n_embd=32, n_layer=2,
             n_head=4, n_inner=64)
SEED = 2 ** 31 + 37


@dataclasses.dataclass(frozen=True)
class State:
    """What ``swap_in_weights`` asks of the program's TrainState."""
    step: int
    params: Any

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


def _shapes():
    return jax.eval_shape(lambda k: gpt2.make_params(k, SIZES),
                          common.root_key(SEED))


def _one_device(shape):
    return jax.devices()[0]


def _over_eight(shape):
    """Rows over the 8-device mesh where 8 divides them, else a copy on
    every device: a tree with sharded and replicated leaves."""
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    split = len(shape) == 2 and shape[0] % 8 == 0
    return NamedSharding(mesh, P("data") if split else P())


def _program_state(place, params_like=None):
    """A program's state: ones in the benchmark's shapes, placed."""
    tree = params_like if params_like is not None else _shapes()
    return State(step=7, params=jax.tree_util.tree_map(
        lambda s: jax.device_put(jnp.ones(s.shape, s.dtype), place(s.shape)),
        tree))


@pytest.mark.parametrize("place", [_one_device, _over_eight],
                         ids=["one_device", "sharded_over_eight"])
def test_one_copy_laid_out_and_placed_as_the_programs(place):
    state = _program_state(place)
    went_in = jax.tree_util.tree_leaves(state.params)
    like = [(x.shape, x.dtype, x.sharding) for x in went_in]
    if place is _over_eight:
        assert any(len(x.sharding.device_set) == 8
                   and not x.sharding.is_fully_replicated for x in went_in)
    out = probes.swap_in_weights(state, SEED, gpt2, SIZES)
    assert all(x.is_deleted() for x in went_in)
    assert out.step == 7
    came_out = jax.tree_util.tree_leaves(out.params)
    assert [(x.shape, x.dtype, x.sharding) for x in came_out] == like
    assert (jax.tree_util.tree_structure(out.params)
            == jax.tree_util.tree_structure(_shapes()))
    # the numbers the parent's swap made: make_params for the seed, laid
    # out by _program_layout, from one jitted call
    want = jax.jit(lambda k: probes._program_layout(
        _shapes(), gpt2.make_params(k, SIZES)))(common.root_key(SEED))
    for got, ref in zip(came_out, jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    other = probes.swap_in_weights(_program_state(place), SEED + 1, gpt2,
                                   SIZES)
    assert not np.array_equal(
        np.asarray(jax.tree_util.tree_leaves(other.params)[0]),
        np.asarray(came_out[0]))


def _without_a_leaf(tree):
    tree = dict(tree)
    tree.pop(sorted(tree)[0])
    return tree


def _with_a_leaf_more(tree):
    return dict(tree, extra=jax.ShapeDtypeStruct((3,), jnp.float32))


def _with_another_shape(tree):
    flat, treedef = jax.tree_util.tree_flatten(tree)
    flat[0] = jax.ShapeDtypeStruct(flat[0].shape + (2,), flat[0].dtype)
    return jax.tree_util.tree_unflatten(treedef, flat)


@pytest.mark.parametrize("change, says", [
    (_without_a_leaf, "the benchmark makes parameters the program lacks"),
    (_with_a_leaf_more, "is not one the benchmark makes"),
    (_with_another_shape, "the program holds"),
])
def test_a_leaf_that_differs_is_refused_with_the_programs_tree_whole(
        change, says):
    state = _program_state(_one_device, change(_shapes()))
    with pytest.raises(ValueError, match=says):
        probes.swap_in_weights(state, SEED, gpt2, SIZES)
    assert not any(x.is_deleted()
                   for x in jax.tree_util.tree_leaves(state.params))
