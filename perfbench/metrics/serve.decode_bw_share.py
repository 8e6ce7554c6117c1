"""Layer: kernels (no serve kernel exists: the decode program stands in).
The bytes a decode step must read (parameters as stored, plus the whole
[slots, max_len] cache the dense engine attends over) at the chip's peak
bandwidth, over the decode program's device time, in percent."""

from harness.loader import load_reader


def read(ctx):
    step_ms = load_reader("serve.decode_step_device_ms")(ctx)
    if step_ms is None or ctx.peaks is None:
        return None
    need = ctx.model.decode_step_bytes(ctx.param_bytes, ctx.sizes, ctx.slots)
    floor_ms = 1e3 * need / ctx.peaks.hbm_bytes_per_s
    ctx.say(f"serve.decode_bw_share: {need / 1e9:.3f} GB a step is "
            f"{floor_ms:.3f} ms at {ctx.peaks.hbm_bytes_per_s / 1e9:.0f} "
            f"GB/s, against {step_ms:.3f} ms measured")
    return 100.0 * floor_ms / step_ms
