"""Layer: serve engine. Of the cached positions the attends' blocks covered,
over ALL slots, attention layers and decode steps, the share the live rows'
queries could see, in percent: the program's own counts over the run
(``serve_summary``: ``select_keys_kept / attend_positions_visited``; kept is
a live row's depth on a full layer and at most the window on a ring). 100
would be attends that stop exactly at what each live row sees; far below
is an attend that walks free slots (the rings' slot-blind XLA attend reads
every slot's ring), whole rows, or a window layer's depth. A program
without the counters gives nothing to read."""

from harness import decode_parts as D


def read(ctx):
    s = D.summary_of(ctx.records)
    if not s or not s.get("attend_positions_visited") \
            or s.get("full_attend_keys") is None:
        return None
    return 100.0 * s["select_keys_kept"] / s["attend_positions_visited"]
