"""Layer: kernels. The dense latent attend kernel's share of its roofline
(``%mla_latent_attend_dense``: one query a row over that row's whole
latent cache row, in place): the least time the chip could take for the
positions the LIVE rows needed a step (the program's own count,
``select_keys_available / decode_steps``, through the model's
``dense_attend_cost``), the larger of operations at the MXU's peak and
bytes at the memory's, over the kernel's device time a call. Whatever
implements the attend, the numerator is the same work: blocks visited
past a row's depth or in free slots lower the share, they do not raise
it. A program without the kernel or the counter (the parent of the PR
that added them, any other model) gives nothing to read."""

import re

from harness import decode_parts as D

KERNEL = re.compile(r"^%mla_latent_attend_dense")


def read(ctx):
    if ctx.trace is None or ctx.peaks is None \
            or not hasattr(ctx.model, "dense_attend_cost"):
        return None
    t, n = D.kernel_time(ctx.trace, KERNEL)
    s = D.summary_of(ctx.records)
    if not n or not s or not s.get("select_keys_available") \
            or not s.get("decode_steps"):
        return None
    positions = s["select_keys_available"] / s["decode_steps"]
    ops, byts = ctx.model.dense_attend_cost(ctx.sizes, positions)
    t_ops, t_bytes = (ops / ctx.peaks.bf16_flops,
                      byts / ctx.peaks.hbm_bytes_per_s)
    ctx.say(f"mla_dense_attend_roofline: {n} calls, {1e6 * t / n:.1f} us "
            f"each; a call needs {positions:.0f} live positions: "
            f"{1e6 * t_ops:.1f} us of operations, {1e6 * t_bytes:.1f} us "
            f"of bytes ({'operations' if t_ops > t_bytes else 'bytes'} "
            f"bound; {len(ctx.sizes['layers'])} calls a step)")
    return 100.0 * max(t_ops, t_bytes) / (t / n)
