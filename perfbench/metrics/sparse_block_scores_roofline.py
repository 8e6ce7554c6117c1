"""Layer: kernels. The pooled-key score kernel's share of its roofline
(``%sparse_block_scores``): the least time for the pooled keys up to each
LIVE row's depth a step (one a ``kernel_stride`` of the program's
``select_keys_available``: ``block_scores_cost``) over the kernel's
device time a call."""

from harness import hybrid_parts as H


def read(ctx):
    k, s = H.decode_kernels(ctx.trace), H.counts(ctx)
    if not k or not s or not k["scores_calls"] or ctx.peaks is None \
            or not s["available"]:
        return None
    windows = s["available"] \
        / ctx.model.sparse_of(ctx.sizes)["kernel_stride"]
    ops, byts = ctx.model.block_scores_cost(ctx.sizes, windows)
    return H.roofline(ctx, "sparse_block_scores_roofline", ops, byts,
                      k["scores_s"], k["scores_calls"],
                      f"{windows:.0f} pooled windows")
