"""Layer: kernels. The latent attend kernel's share of its roofline: the
least time the chip could take for the attends the run NEEDED (the keys
the live slots kept, from the program's count; the kernel computes every
slot's full ``index_topk`` rows whether live or not, which is why this is
below what its own shapes would give) over the kernel's device time."""

from harness import decode_parts as D


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t, n = D.kernel_time(ctx.trace, D.ATTEND)
    s = D.summary_of(ctx.records)
    if not n or not s or not s.get("select_keys_kept"):
        return None
    layers = len(ctx.sizes["layers"])
    kept_a_step = s["select_keys_kept"] / s["decode_steps"]
    ops, byts = ctx.model.latent_attend_cost(ctx.sizes, 1, kept_a_step)
    t_ops, t_bytes = (ops / ctx.peaks.bf16_flops,
                      byts / ctx.peaks.hbm_bytes_per_s)
    ctx.say(f"mla_latent_attend_roofline: {n} calls, {1e6 * t / n:.1f} us "
            f"each; a call needs {kept_a_step:.0f} kept rows: "
            f"{1e6 * t_ops:.1f} us of operations, {1e6 * t_bytes:.1f} us "
            f"of bytes ({layers} calls a step)")
    return 100.0 * max(t_ops, t_bytes) / (t / n)
