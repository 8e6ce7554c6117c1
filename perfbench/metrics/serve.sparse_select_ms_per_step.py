"""Layer: kernels. Device ms a decode step spends choosing its keys: the
index-score kernel of each ``full`` layer (``%dsa_index_scores``) and the
exact top-k's sort ops that follow it before that layer's attend
(``harness/decode_parts.py`` says how order ties them)."""

from harness import decode_parts as D


def read(ctx):
    parts = D.decode_parts(ctx.trace)
    return parts["select_ms"] if parts else None
