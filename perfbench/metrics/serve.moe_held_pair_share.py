"""Layer: serve engine. Of the (token, expert) pairs the live rows' routers
picked over ALL published experts, the share that landed on an expert held
here, in percent: the program's own counts over the run
(``serve_summary.moe_held_pairs`` over ``moe_pairs_routed``). Held over
published experts by the configuration (25 for 128 of 512); what the seeded
router really sends here moves the step's expert time with it."""

from harness import decode_parts as D


def read(ctx):
    s = D.summary_of(ctx.records)
    if not s or not s.get("moe_pairs_routed") \
            or s.get("moe_held_pairs") is None:
        return None
    return 100.0 * s["moe_held_pairs"] / s["moe_pairs_routed"]
