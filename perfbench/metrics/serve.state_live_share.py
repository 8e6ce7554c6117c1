"""Layer: serve engine. Of the slot-rows whose recurrent state the decode
steps moved (``serve_summary.state_rows_stepped``: the state step's own
trip count), the share a live row needed, in percent: ``decode_live_rows``
a linear layer over it. 100 is a step that touches the states of live
slots only; one that moved every slot's state reads the live share of the
slots."""

from harness import decode_parts as D


def read(ctx):
    s = D.summary_of(ctx.records)
    if not s or not s.get("state_rows_stepped"):
        return None
    linear = ctx.model.layer_counts(ctx.sizes)[0]
    return 100.0 * s["decode_live_rows"] * linear / s["state_rows_stepped"]
