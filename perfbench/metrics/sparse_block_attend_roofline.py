"""Layer: kernels. The block attend's share of its roofline
(``%sparse_block_attend``): the least time for the K and V of the blocks
the capture's LIVE rows kept a step (the program's ``select_keys_kept`` a
live row, a group's positions; both groups read: ``block_attend_cost``) over the kernel's
device time a call."""

from harness import hybrid_parts as H


def read(ctx):
    k, s = H.decode_kernels(ctx.trace), H.counts(ctx)
    if not k or not s or not k["attend_calls"] or ctx.peaks is None \
            or not s["kept"]:
        return None
    kept = s["kept"]
    ops, byts = ctx.model.block_attend_cost(ctx.sizes, kept)
    return H.roofline(ctx, "sparse_block_attend_roofline", ops, byts,
                      k["attend_s"], k["attend_calls"],
                      f"{kept:.0f} kept positions a group")
