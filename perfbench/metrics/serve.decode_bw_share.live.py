"""Layer: kernels. ``serve.decode_bw_share`` for a program that reads by
what the step's LIVE rows need and says so: the bytes a decode step must
read, from the program's own counts a step (``serve_summary``: live rows,
the held experts a routed pair reached, the latent rows the selection
kept, the index keys up to each live row's depth), at the chip's peak
bandwidth, over the decode program's device time, in percent. The generic
reader is told the engine's slot count only: right where every slot's
whole cache row is attended (the dense engine), several times too much
where a fifth of the slots are live and an expert nobody reached is
skipped."""

from harness import decode_parts as D
from harness.loader import load_reader


def read(ctx):
    s = D.summary_of(ctx.records)
    if ctx.peaks is None or not s or not s.get("decode_live_rows") \
            or s.get("moe_experts_hit") is None:
        return None
    step_ms = load_reader("serve.decode_step_device_ms")(ctx)
    if step_ms is None:
        return None
    steps = s["decode_steps"]
    live, hit, kept, avail = (s[k] / steps for k in (
        "decode_live_rows", "moe_experts_hit", "select_keys_kept",
        "select_keys_available"))
    need = ctx.model.decode_step_bytes(
        ctx.param_bytes, ctx.sizes, live, experts_hit=hit, keys_kept=kept,
        keys_available=avail)
    floor_ms = 1e3 * need / ctx.peaks.hbm_bytes_per_s
    full = ctx.model.decode_step_bytes(ctx.param_bytes, ctx.sizes, ctx.slots)
    ctx.say(f"serve.decode_bw_share.live: a step has {live:.2f} live rows "
            f"of {ctx.slots}, reaches {hit:.2f} held experts over "
            f"{s['moe_layers']} layers, keeps {kept:.0f} latent rows of "
            f"{avail:.0f}: {need / 1e9:.3f} GB is {floor_ms:.3f} ms at "
            f"{ctx.peaks.hbm_bytes_per_s / 1e9:.0f} GB/s, against "
            f"{step_ms:.3f} ms measured (with every slot live: "
            f"{full / 1e9:.3f} GB)")
    return 100.0 * floor_ms / step_ms
