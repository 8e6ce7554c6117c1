"""Layer: kernels. The flash-attention kernels' share of their roofline:
the least time the chip could take for every flash call in the trace (the
larger of operations over peak FLOP/s and bytes over peak bandwidth, from
shapes, by harness/flops.py) over the kernels' device time."""

import re

from harness import flops
from harness import trace as T

# What the trace of a v5e shows (PERF.md section 3): each Pallas call is an
# op named after the flax scope, `%attn.N tpu_custom_call <result type>`,
# and the three kernels differ in what they return: forward (o, f32 row
# statistics), dq one tensor, dkv two tensors.
_T = r"[a-z0-9]+\[[0-9,]+\]"
KINDS = {
    "fwd": re.compile(rf"tpu_custom_call \({_T}, ?f32\[[0-9,]+\]\)$"),
    "dq": re.compile(rf"tpu_custom_call {_T}$"),
    "dkv": re.compile(rf"tpu_custom_call \((bf16|f32)\[[0-9,]+\], ?\1\[[0-9,]+\]\)$"),
}


def kind_of(name: str):
    for kind in ("dkv", "fwd", "dq"):
        if KINDS[kind].search(name):
            return kind
    return None


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    s = ctx.sizes
    rows = ctx.shape["rows_per_chip"]
    least, spent, bound_by, seen = 0.0, 0.0, set(), {}
    for kind in KINDS:
        t, n = T.op_time(ctx.trace, lambda name: kind_of(name) == kind)
        if not n:
            return None
        ops, byts = flops.flash_attention_cost(
            rows, s["n_head"], ctx.shape["seq_len"],
            s["n_embd"] // s["n_head"], kind)
        t_ops, t_bytes = (ops / ctx.peaks.bf16_flops,
                          byts / ctx.peaks.hbm_bytes_per_s)
        bound_by.add("compute" if t_ops >= t_bytes else "bandwidth")
        least += n * max(t_ops, t_bytes)
        spent += t
        seen[kind] = (n, 1e6 * t / n, 1e6 * max(t_ops, t_bytes))
    ctx.say("train.flash_roofline_share: bound by " + "/".join(
        sorted(bound_by)) + "; " + "; ".join(
            f"{k}: {n} calls, {us:.1f} us each against {lo:.1f} us least"
            for k, (n, us, lo) in seen.items()))
    return 100.0 * least / spent
