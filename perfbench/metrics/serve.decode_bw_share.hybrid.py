"""Layer: kernels. ``serve.decode_bw_share`` for a hybrid of linear and
block-sparse layers: the bytes a decode step must move, from the program's
own counts (the capture's live rows a step from its fetch spans; of
``serve_summary`` a live row: the positions their sparse layers kept,
their depths) through the model's
``decode_step_bytes`` (layer weights and head, the live rows' recurrent
states read and written, the kept blocks' K and V, the pooled keys to
depth), at the chip's peak bandwidth, over the decode program's device
time, in percent."""

from harness import hybrid_parts as H
from harness.loader import load_reader


def read(ctx):
    s = H.counts(ctx)
    if ctx.peaks is None or not s:
        return None
    step_ms = load_reader("serve.decode_step_device_ms")(ctx)
    if step_ms is None:
        return None
    live, kept, avail = s["live"], s["kept"], s["available"]
    need = ctx.model.decode_step_bytes(
        ctx.param_bytes, ctx.sizes, live, keys_kept=kept,
        keys_available=avail)
    floor_ms = 1e3 * need / ctx.peaks.hbm_bytes_per_s
    ctx.say(f"serve.decode_bw_share.hybrid: a step has {live:.2f} live "
            f"rows of {ctx.slots}, keeps {kept:.0f} positions a group of "
            f"{avail:.0f}: {need / 1e9:.3f} GB is {floor_ms:.3f} ms at "
            f"{ctx.peaks.hbm_bytes_per_s / 1e9:.0f} GB/s, against "
            f"{step_ms:.3f} ms measured")
    return 100.0 * floor_ms / step_ms
