"""Layer: serve engine. Of the cached positions the dense slot engine's
decode attends' blocks covered, over ALL slots, layers and decode launches,
the share the live rows' queries could see, in percent: the program's own
counts over the run (``serve_summary``: ``kv_attend_positions_seen /
kv_attend_positions_visited``, which the engine's host side keeps at every
launch from the positions it hands the step; seen is a row's position + 1).
100 would be attends that stop exactly at each live row's depth; what is
missing is the rest of each row's last block. An attend over the whole
``[slots, max_len]`` leaf would read near the live share of the cache, 7-11%
in the GPT-2 cells. A program without the counters (the parent of the PR
that added them, a family that counts its own attends) gives nothing to
read."""

from harness import decode_parts as D


def read(ctx):
    s = D.summary_of(ctx.records)
    if not s or not s.get("kv_attend_positions_visited"):
        return None
    return (100.0 * s["kv_attend_positions_seen"]
            / s["kv_attend_positions_visited"])
