"""The serving tree: what an engine holds of a trained parameter tree.

A training tree keeps every leaf in the dtype the optimizer updates. A
server only reads it, and a model family may say that some leaves are
only ever read through a cast (``model.serving_params(params)``, a pure
function of the tree; ``models/transformer.py`` has the one
declaration). :func:`serving_tree` applies that statement ONCE, after
the tree is built or restored, so no step of any request pays for the
cast again. A family that declares nothing, or whose declaration
changes nothing (float32 compute, parameters already in the compute
dtype), gets its tree back: the same buffers.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def _keyed(tree) -> Dict[str, Any]:
    return {jax.tree_util.keystr(path): leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(tree)}


def made_leaves(model, params) -> Tuple[Any, Dict[str, Any]]:
    """``(abstract serving tree, {path: its abstract leaf})`` for the
    leaves that ``model``'s declaration MAKES: those held in another
    dtype than ``params`` holds them, or that ``params`` lacks. No
    device work; ``params`` may be arrays or shapes."""
    declare = getattr(model, "serving_params", None)
    if declare is None:
        return params, {}
    abstract = jax.eval_shape(declare, params)
    trained = _keyed(params)
    return abstract, {
        key: leaf for key, leaf in _keyed(abstract).items()
        if key not in trained or trained[key].dtype != leaf.dtype}


def serving_tree(model, params, donate: bool = False
                 ) -> Tuple[Any, Dict[str, int]]:
    """``(serving tree, counter)`` for ``params`` in the training
    layout. One jitted program makes the leaves the declaration changes,
    each with the sharding of the trained leaf at its path (a derived
    leaf takes what the compiler propagates from its source); every
    other leaf is ``params``' own buffer. With ``donate`` the trained
    buffer of each cast leaf is given back as soon as the program has
    run, so the device holds both only for the length of the call: pass
    it only for a tree nothing else reads. The counter is what a serve
    run's ``start`` record carries as ``serving_params``."""
    trained_bytes = _nbytes(params)
    abstract, made = made_leaves(model, params)
    if not made:
        return params, {"leaves_cast": 0, "bytes_trained": trained_bytes,
                        "bytes_held": trained_bytes}
    trained = _keyed(params)

    def make(p):
        return {key: leaf for key, leaf
                in _keyed(model.serving_params(p)).items() if key in made}

    placed = {key: (trained[key].sharding
                    if key in trained
                    and getattr(trained[key], "committed", False)
                    else None) for key in made}
    new = jax.jit(make, out_shardings=placed)(params)
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    keys = [jax.tree_util.keystr(path) for path, _ in paths]
    tree = jax.tree_util.tree_unflatten(
        treedef, [new[k] if k in new else trained[k] for k in keys])
    if donate:
        jax.block_until_ready(new)
        for key in made:
            if key in trained:
                trained[key].delete()
    return tree, {"leaves_cast": len(made), "bytes_trained": trained_bytes,
                  "bytes_held": _nbytes(tree)}
