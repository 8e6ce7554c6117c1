"""Layer: kernels. Device ms a decode step spends in the held experts'
grouped matmuls (``%gmm``: up and down of every expert layer, and gate
where the experts are gated), over the executions of the decode program in
the trace that ran any. What ``serve.moe_expert_ms_per_step`` cannot give a
program without the latent family's attention kernels, by which its split
finds a step. A step whose experts left megablox for XLA's ragged dot (rows
that are not whole row tiles) has no ``%gmm`` and reads nothing."""

from harness import nemotron_parts as N


def read(ctx):
    k = N.decode_gmm(ctx.trace)
    return 1e3 * k["gmm_s"] / k["steps"] if k else None
