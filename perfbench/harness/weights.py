"""GPT-2 weights from ``--seed``, made on the device in one jitted call.

The benchmark owns the weights: the program is handed this tree in the
layout its model expects, the plain reference reads the same numbers
(``stacked=True`` gives the per-layer leaves stacked on a leading layer
axis, which is how the reference scans over layers). Nothing the program
initialised is used.

Every leaf is random, biases and norms too (std 0.02 around 0, norm
scales around 1), so that a path that dropped a bias or a scale would
show in ``correct``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

STD = 0.02
# (path inside a layer, shape as a function of the sizes, centre)
_LAYER_LEAVES = (
    (("ln1", "scale"), lambda d, h, f: (d,), 1.0),
    (("ln1", "bias"), lambda d, h, f: (d,), 0.0),
    (("attn", "qkv", "kernel"), lambda d, h, f: (d, 3, h, d // h), 0.0),
    (("attn", "qkv", "bias"), lambda d, h, f: (3, h, d // h), 0.0),
    (("attn", "out", "kernel"), lambda d, h, f: (h, d // h, d), 0.0),
    (("attn", "out", "bias"), lambda d, h, f: (d,), 0.0),
    (("ln2", "scale"), lambda d, h, f: (d,), 1.0),
    (("ln2", "bias"), lambda d, h, f: (d,), 0.0),
    (("mlp", "up", "kernel"), lambda d, h, f: (d, f), 0.0),
    (("mlp", "up", "bias"), lambda d, h, f: (f,), 0.0),
    (("mlp", "down", "kernel"), lambda d, h, f: (f, d), 0.0),
    (("mlp", "down", "bias"), lambda d, h, f: (d,), 0.0),
)
_TOP_LEAVES = (
    (("tok_emb", "embedding"), lambda s: (s["vocab_size"], s["n_embd"]), 0.0),
    (("pos_emb", "embedding"),
     lambda s: (s["n_positions"], s["n_embd"]), 0.0),
    (("ln_f", "scale"), lambda s: (s["n_embd"],), 1.0),
    (("ln_f", "bias"), lambda s: (s["n_embd"],), 0.0),
)


def _put(tree: Dict[str, Any], path, leaf) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = leaf


def root_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed) % 2 ** 32)


def _leaf(key, shape, centre):
    return centre + STD * jax.random.normal(key, shape, jnp.float32)


def make_params(key: jax.Array, sizes: Dict[str, int],
                stacked: bool = False) -> Dict[str, Any]:
    """The whole tree (trace this under jit). ``sizes``: n_embd, n_layer,
    n_head, n_inner, n_positions, vocab_size."""
    d, h, f = sizes["n_embd"], sizes["n_head"], sizes["n_inner"]
    n = sizes["n_layer"]
    out: Dict[str, Any] = {}
    for i, (path, shape, centre) in enumerate(_TOP_LEAVES):
        _put(out, path, _leaf(jax.random.fold_in(key, i), shape(sizes),
                              centre))
    for j, (path, shape, centre) in enumerate(_LAYER_LEAVES):
        k_leaf = jax.random.fold_in(key, 100 + j)
        shp = shape(d, h, f)
        if stacked:
            _put(out.setdefault("blocks", {}), path, jax.vmap(
                lambda li: _leaf(jax.random.fold_in(k_leaf, li), shp,
                                 centre))(jnp.arange(n)))
        else:
            for li in range(n):
                _put(out.setdefault(f"layer_{li}", {}), path,
                     _leaf(jax.random.fold_in(k_leaf, li), shp, centre))
    return out


def stack_like_reference(tree: Dict[str, Any], n_layer: int
                         ) -> Dict[str, Any]:
    """Program-layout tree -> the reference's stacked layout (used to
    compare a program-side quantity leaf by leaf with the reference's)."""
    out = {k: v for k, v in tree.items() if not k.startswith("layer_")}
    layers = [tree[f"layer_{i}"] for i in range(n_layer)]
    out["blocks"] = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *layers)
    return out
