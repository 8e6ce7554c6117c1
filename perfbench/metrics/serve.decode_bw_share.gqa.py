"""Layer: kernels. ``serve.decode_bw_share`` for a model of window and full
grouped-query attention layers and routed experts: the bytes a decode step
must move, from the program's own counts (the capture's live rows a step
from its fetch spans; of ``serve_summary``: the held experts a step's routed
pairs reached, brought to those rows as ``moe_gmm_roofline`` brings them,
and the cached positions a live row attends, to its depth on a full layer
and to the window on a ring) through the model's
``decode_step_bytes`` (the weights a step can reach with the held experts
REACHED, K and V of those positions), at the chip's peak bandwidth, over the
decode program's device time, in percent. A program without the counters
gives nothing to read."""

from harness import decode_parts as D
from harness import nemotron_parts as N
from harness.loader import load_reader


def read(ctx):
    s = D.summary_of(ctx.records)
    if ctx.peaks is None or not s or s.get("full_attend_keys") is None:
        return None
    step_ms = load_reader("serve.decode_step_device_ms")(ctx)
    counts = N.step_counts(ctx)
    if step_ms is None or not counts:
        return None
    live, hit = counts["live"], counts["experts_hit"]
    kept = live * s["select_keys_kept"] / s["decode_live_rows"]
    need = ctx.model.decode_step_bytes(
        ctx.param_bytes, ctx.sizes, live, keys_kept=kept, experts_hit=hit)
    floor_ms = 1e3 * need / ctx.peaks.hbm_bytes_per_s
    ctx.say(f"serve.decode_bw_share.gqa: a step has {live:.2f} live rows of "
            f"{ctx.slots}, reaches {hit:.1f} held experts over the layers, "
            f"attends {kept:.0f} cached positions: {need / 1e9:.3f} GB is "
            f"{floor_ms:.3f} ms at {ctx.peaks.hbm_bytes_per_s / 1e9:.0f} "
            f"GB/s, against {step_ms:.3f} ms measured")
    return 100.0 * floor_ms / step_ms
