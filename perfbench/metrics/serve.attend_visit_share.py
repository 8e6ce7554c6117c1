"""Layer: serve engine. Of the cached positions the dense attend's blocks
covered, over ALL slots and decode steps, the share a live row needed, in
percent: the program's own counts over the run (``serve_summary``:
``select_keys_available / attend_positions_visited``). 100 would be a
kernel that stops exactly at each live row's depth; what is missing is
the tail of each row's last block, and a kernel that walked free slots or
whole cache rows would read far below. A program without the counter
gives nothing to read."""

from harness import decode_parts as D


def read(ctx):
    s = D.summary_of(ctx.records)
    if not s or not s.get("attend_positions_visited"):
        return None
    return (100.0 * s["select_keys_available"]
            / s["attend_positions_visited"])
