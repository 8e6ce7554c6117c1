"""Layer: serve engine. Routed (token, expert) pairs that landed on an
expert held here, per held expert, expert layer and decode step: the
program's own count over the run (``serve_summary``). The deployment's
chip would see 32 times this (PERF.md section 4); the spread over the
experts (max / mean) is printed beside it."""

from harness import decode_parts as D


def read(ctx):
    s = D.summary_of(ctx.records)
    if not s or s.get("moe_pairs_per_expert_step") is None:
        return None
    ctx.say(f"serve.moe_pairs_per_expert_step: {s['moe_held_pairs']} pairs "
            f"on the held experts over {s.get('decode_steps')} steps and "
            f"{s['moe_layers']} layers, by expert "
            f"{s['moe_held_pairs_by_expert']}, max / mean "
            f"{s['moe_pairs_spread']}; cache bytes a slot by kind "
            f"{s.get('cache_bytes_per_slot_by_kind')}")
    return float(s["moe_pairs_per_expert_step"])
