"""Layer: kernels. Device ms a decode step spends in the held experts'
grouped matmuls (``%gmm``: gate, up and down of every expert layer).
Routing, the sort of the pairs and the one-hot moves are anonymous ops in
the step's remainder."""

from harness import decode_parts as D


def read(ctx):
    parts = D.decode_parts(ctx.trace)
    if parts:
        ctx.say("decode step split (device ms a step over "
                f"{parts['steps']} steps): select "
                f"{parts['select_ms']:.3f}, latent attend "
                f"{parts['attend_ms']:.3f}, routed experts "
                f"{parts['experts_ms']:.3f}, the rest "
                f"{parts['step_ms'] - parts['select_ms'] - parts['attend_ms'] - parts['experts_ms']:.3f}"
                f" of {parts['step_ms']:.3f}")
    return parts["experts_ms"] if parts else None
