"""Layer: kernels. The state step's share of its roofline
(``%lightning_state_step``): the least time for the states of the LIVE
rows a step of the capture (the program's ``live`` count on its fetch
spans), each read once and written once from HBM (``state_step_cost``),
over the kernel's device time a call. A kernel that moved every slot's state would read the live
share of the slots."""

from harness import hybrid_parts as H


def read(ctx):
    k, s = H.decode_kernels(ctx.trace), H.counts(ctx)
    if not k or not s or not k["state_calls"] or ctx.peaks is None:
        return None
    live = s["live"]
    ops, byts = ctx.model.state_step_cost(ctx.sizes, live)
    return H.roofline(ctx, "lightning_state_step_roofline", ops, byts,
                      k["state_s"], k["state_calls"],
                      f"{live:.2f} live rows' states")
