"""Layer: serve engine. Of the keys a decode step's selection could
choose from (every position up to each live slot's depth), the share it
kept, in percent: the program's own count over the run
(``serve_summary.select_keys_kept / select_keys_available``). 100 means
no context was past ``index_topk`` and the sparse path did nothing."""

from harness import decode_parts as D


def read(ctx):
    s = D.summary_of(ctx.records)
    if not s or not s.get("select_keys_available"):
        return None
    return 100.0 * s["select_keys_kept"] / s["select_keys_available"]
