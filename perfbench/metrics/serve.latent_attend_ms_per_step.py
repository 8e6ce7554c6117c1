"""Layer: kernels. Device ms a decode step spends in the latent attend
kernel of every layer (``%mla_latent_attend``: softmax over the gathered
selected rows). The row gathers that feed it are anonymous fusions the
reduced trace cannot tell from matmuls: they stay in the step's
remainder (PERF.md section 7)."""

from harness import decode_parts as D


def read(ctx):
    parts = D.decode_parts(ctx.trace)
    return parts["attend_ms"] if parts else None
