#!/usr/bin/env python3
"""How far the program's selected sets and the plain reference's overlap,
and what a swap at the ``index_topk``-th place does to a logit, at a
configuration's published widths with seeded random weights.

    python3 perfbench/tools/selection_overlap.py --workload glm52-serve-longctx \\
        --seed 5 --positions 6144 [--rehearse]

Selection is discrete: the program scores in bfloat16 operands, the
reference in float32, so near the ``index_topk``-th score the two keep
different keys. This tool runs ONE context of ``--positions`` random
tokens through the program's own model (its prefill, through the cache)
and through the reference, and prints for each ``full`` layer the share
of (query, key) pairs both kept, and for the last position: the largest
change of any logit when the reference is made to attend over the
PROGRAM's sets instead of its own (everything else float32). It measures
nothing that ``correct`` compares; it says how much of the served-token
gap is the selection's.
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.dirname(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import common
    from harness.loader import Cell

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--positions", type=int, default=6144)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    ref = cell.model
    sizes = cell.sizes(args.rehearse)
    src = (cell.config["rehearsal"]["sizes"] if args.rehearse
           else cell.config)

    from tensorflow_distributed_tpu.models import glm_moe_dsa as G
    model = G.GlmMoeDsaLM(G.config_from_source(
        dict(src), compute_dtype=jnp.bfloat16))
    params = jax.jit(lambda k: ref.make_params(k, sizes))(
        common.root_key(args.seed))
    L = args.positions
    toks = jax.random.randint(jax.random.PRNGKey(args.seed), (1, L), 0,
                              sizes["vocab_size"])

    @jax.jit
    def program(p, t):
        logits, state = model.apply(
            {"params": p}, t, decode=True, positions=jnp.arange(L)[None],
            mutable=["cache"], logits_at=jnp.asarray([L - 1]),
            capture_intermediates=lambda m, _: isinstance(
                m, G.LatentAttention))
        keeps = [state["intermediates"][f"layer_{i}"]["attn"]["__call__"]
                 [0][1][0] for i in range(len(sizes["layers"]))]
        return logits[0, 0], keeps

    got, keeps = program(params, toks)
    full = [i for i, (_, ix) in enumerate(sizes["layers"]) if ix == "full"]

    def reference(p, t, forced):
        """The reference's last-position logits and its own sets."""
        feats, own = ref.forward_with_selections(p, t, sizes, "f32", forced)
        return ref._mm("ld,dv->lv", feats[-1:], p["lm_head"]["kernel"],
                       "f32")[0], own

    want, own = jax.jit(lambda p, t: reference(p, t, None))(params, toks[0])
    print(f"[overlap] {L} positions, index_topk {sizes['index_topk']}, "
          f"seed {args.seed}, device {jax.devices()[0].device_kind}")
    for i in full:
        a, b = np.asarray(keeps[i]), np.asarray(own[i])
        both, either = (a & b).sum(), b.sum()
        late = slice(sizes["index_topk"], L)
        print(f"[overlap] layer {i}: {both} of {either} kept pairs in "
              f"common ({100.0 * both / either:.3f}%); over the queries "
              f"past index_topk {100.0 * (a[late] & b[late]).sum() / b[late].sum():.3f}%; "
              f"last query: {(a[-1] & b[-1]).sum()} of {b[-1].sum()}")
    forced, _ = jax.jit(lambda p, t, k: reference(
        p, t, {i: k[i] for i in full}))(params, toks[0], keeps)
    d = np.abs(np.asarray(forced) - np.asarray(want))
    g = np.abs(np.asarray(got) - np.asarray(want))
    print(f"[overlap] last position, reference on the PROGRAM's sets "
          f"against its own: largest logit change {d.max():.6g}, mean "
          f"{d.mean():.6g}; program (bfloat16) against reference: "
          f"largest {g.max():.6g}, mean {g.mean():.6g}; logits span "
          f"{float(np.ptp(np.asarray(want))):.4g}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
