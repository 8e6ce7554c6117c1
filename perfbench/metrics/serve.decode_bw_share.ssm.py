"""Layer: kernels. ``serve.decode_bw_share`` for a model of state-space
layers and routed experts: the bytes a decode step must move, from the
program's own counts (the capture's live rows a step from its fetch spans;
of ``serve_summary``: the held experts a step's routed pairs reached, the
cached positions a live row's attention layers attend) through the model's
``decode_step_bytes`` (the weights a step can reach, the live rows' states
read and written, their convolution rings, K and V of the live rows), at
the chip's peak bandwidth, over the decode program's device time, in
percent."""

from harness import ssm_parts as S
from harness.loader import load_reader


def read(ctx):
    s = S.counts(ctx)
    if ctx.peaks is None or not s:
        return None
    step_ms = load_reader("serve.decode_step_device_ms")(ctx)
    if step_ms is None:
        return None
    live, keys, hit = s["live"], s["keys"], s["experts_hit"]
    need = ctx.model.decode_step_bytes(
        ctx.param_bytes, ctx.sizes, live, keys_kept=keys, experts_hit=hit)
    floor_ms = 1e3 * need / ctx.peaks.hbm_bytes_per_s
    ctx.say(f"serve.decode_bw_share.ssm: a step has {live:.2f} live rows "
            f"of {ctx.slots}, reaches "
            f"{'every' if hit is None else format(hit, '.1f')} held expert "
            f"over the layers, attends {keys:.0f} cached positions: "
            f"{need / 1e9:.3f} GB is {floor_ms:.3f} ms at "
            f"{ctx.peaks.hbm_bytes_per_s / 1e9:.0f} GB/s, against "
            f"{step_ms:.3f} ms measured")
    return 100.0 * floor_ms / step_ms
