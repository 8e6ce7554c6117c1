"""Layer: kernels. Device ms a decode step spends choosing and attending
blocks in its sparse layers: from each layer's pooled-key score kernel
(``%sparse_block_scores``) through the selection's own ops (block maxima,
forced blocks, top-k, the attend's mask; read by order) to the end of its
attend kernel (``%sparse_block_attend``)."""

from harness import hybrid_parts as H


def read(ctx):
    k = H.decode_kernels(ctx.trace)
    return 1e3 * k["select_s"] / k["steps"] if k and k["attend_calls"] \
        else None
