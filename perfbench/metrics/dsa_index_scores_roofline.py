"""Layer: kernels. The decode index-score kernel's share of its roofline:
the least time for the index keys the live slots' queries had to be
scored against (every position up to each live slot's depth, from the
program's count) over the kernel's device time. The kernel reads whole
blocks of 2,048 positions and one block for every free slot."""

from harness import decode_parts as D


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t, n = D.kernel_time(ctx.trace, D.INDEX)
    s = D.summary_of(ctx.records)
    if not n or not s or not s.get("select_keys_available"):
        return None
    keys_a_step = s["select_keys_available"] / s["decode_steps"]
    ops, byts = ctx.model.index_scores_cost(ctx.sizes, 1, keys_a_step)
    t_ops, t_bytes = (ops / ctx.peaks.bf16_flops,
                      byts / ctx.peaks.hbm_bytes_per_s)
    ctx.say(f"dsa_index_scores_roofline: {n} calls, {1e6 * t / n:.1f} us "
            f"each; a call needs {keys_a_step:.0f} keys scored: "
            f"{1e6 * t_ops:.1f} us of operations, {1e6 * t_bytes:.1f} us "
            f"of bytes")
    return 100.0 * max(t_ops, t_bytes) / (t / n)
