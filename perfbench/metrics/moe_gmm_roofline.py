"""Layer: kernels. The held experts' grouped matmuls' share of their
roofline (``%gmm`` inside the decode program): the least time of a step's
expert calls over their device time a step, in percent. The least time
(``expert_step_cost``) is the larger of the held pairs' operations at the
MXU's peak and, at the memory's, the bytes of the experts REACHED, each read
whole once, plus the pair rows in and out. Pairs and experts reached are
the program's own counts (``serve_summary.moe_held_pairs`` and
``moe_experts_hit`` over the run's decode steps), brought to the live rows
the CAPTURE's steps had (its fetch spans): the pairs in proportion, the
experts reached by the chance that none of a step's rows picks an expert
(``nemotron_parts.step_counts``)."""

from harness import hybrid_parts as H
from harness import nemotron_parts as N


def read(ctx):
    k, s = N.decode_gmm(ctx.trace), N.step_counts(ctx)
    if not k or not s or ctx.peaks is None \
            or not hasattr(ctx.model, "expert_step_cost"):
        return None
    ops, byts = ctx.model.expert_step_cost(ctx.sizes, s["held_pairs"],
                                           s["experts_hit"])
    return H.roofline(
        ctx, "moe_gmm_roofline", ops, byts, k["gmm_s"], k["steps"],
        f"{s['held_pairs']:.0f} held pairs on {s['experts_hit']:.1f} "
        f"experts reached ({s['live']:.2f} live rows, {k['gmm_calls']:.0f} "
        f"kernel calls over {k['steps']:.0f} steps; a call here is a step)")
