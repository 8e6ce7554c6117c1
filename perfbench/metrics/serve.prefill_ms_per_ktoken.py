"""Layer: serve engine. Device ms of a bucketed prefill per 1,000 tokens
it computes (its bucket: the prompt and its padding), over the prefill
programs in the trace. Long prompts cost more a token (attention and the
index scores grow with the square), so this moves with the mix."""

from harness import decode_parts as D
from harness import trace as T


def read(ctx):
    if ctx.trace is None:
        return None
    ms, tokens = 0.0, 0
    for dev in ctx.trace.devices.values():
        for name, s, dur in dev["modules"]:
            m = D.PREFILL_MODULE.match(name)
            if not m:
                continue
            ops = D.ops_inside(dev, s, dur)
            ms += T.total(T.union([(o[1], min(o[1] + o[2], s + dur))
                                   for o in ops])) / 1e6
            tokens += int(m.group(1))
    return 1e3 * ms / tokens if tokens else None
