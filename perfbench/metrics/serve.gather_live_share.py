"""Layer: kernels. Of the latent cache rows the decode steps' gathers
moved, the share a live slot's attend needed, in percent: the program's
own counts over the run (``serve_summary``: ``select_keys_kept`` a layer
with a selection over ``select_rows_gathered``). 100 is a gather that
visits live slots only, each past ``index_topk``; a gather that moved
every slot's ``index_topk`` rows reads the live share of the slots (7 of
32: 22.5), and a live slot shallower than ``index_topk`` leaves it below
100. A program without the counter gives nothing to read."""

from harness import decode_parts as D


def read(ctx):
    s = D.summary_of(ctx.records)
    if not s or not s.get("select_rows_gathered"):
        return None
    selecting = sum(indexer != "none" for _, indexer in ctx.sizes["layers"])
    return 100.0 * s["select_keys_kept"] * selecting \
        / s["select_rows_gathered"]
