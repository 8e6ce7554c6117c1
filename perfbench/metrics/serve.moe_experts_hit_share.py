"""Layer: serve engine. Of the routed experts held here, the share a decode
step's live rows reached at all, in percent: the program's own count over
the run (``serve_summary.moe_experts_hit``, summed over the expert layers
and the decode steps) over held experts x layers x steps. A grouped matmul
skips an empty group, so this is the share of the held experts' weights a
step reads."""

from harness import decode_parts as D


def read(ctx):
    s = D.summary_of(ctx.records)
    if not s or s.get("moe_experts_hit") is None \
            or not s.get("decode_steps") \
            or not ctx.sizes.get("experts_held"):
        return None
    held = len(ctx.sizes["experts_held"]) * s["moe_layers"]
    return 100.0 * s["moe_experts_hit"] / (held * s["decode_steps"])
